"""The four dttcheck passes — each one turns a jaxpr-level fact into a
named finding (rules DTC001-DTC004; DTC000 is reserved for a scenario
that fails to build or trace, which is itself a finding: a step the
verifier cannot even trace is a step nobody has proven anything about).

DTC001 ledger-proof        every ``comm_ledger`` row corresponds to
                           collectives actually present in the traced
                           computation and the summed wire bytes match
                           EXACTLY (both directions: an unpriced
                           collective is a finding, a phantom row is a
                           finding)
DTC002 spmd-deadlock       ``lax.cond``/``switch`` branches carry
                           identical collective signatures; collective
                           axis names exist on the mesh the function
                           is lowered for; no collective hides inside
                           a ``while`` (unbounded trip count)
DTC003 donation-audit      every donated input buffer has a same-
                           shape/dtype output to alias (the jaxpr's
                           actual aliasing opportunity) — the runtime
                           complement of dttlint's AST-level DTT008
DTC004 replication-drift   every leaf the ParallelismPlan declares
                           sharded is actually split by the lowered
                           shard_map (and vice versa) — a leaf whose
                           jaxpr shape shows full replication while
                           the plan claims a shard is silent HBM waste
                           and a wrong memory budget

Finding keys are stable (scenario name + symbol, never line numbers);
paths point at the module that owns the violated fact (the mode's
``parallel/`` module for ledger rows, the builder for the rest).
"""

from __future__ import annotations

from collections import Counter

from tools._analysis_common import Finding
from tools.dttcheck.inventory import Inventory

#: ledger row "collective" name prefix -> inventory family
ROW_FAMILY = {
    "all_reduce": "psum", "psum": "psum", "pmean": "psum",
    "psum_scatter": "reduce_scatter", "reduce_scatter": "reduce_scatter",
    "all_gather": "all_gather", "ppermute": "ppermute",
    "all_to_all": "all_to_all",
    "pull": "host", "push": "host",
}

#: which parallel/ module owns each mode's row builders (finding paths)
MODE_PATH = {
    "dp": "distributed_tensorflow_tpu/parallel/data_parallel.py",
    "zero1": "distributed_tensorflow_tpu/parallel/zero.py",
    "zero3": "distributed_tensorflow_tpu/parallel/zero.py",
    "pp": "distributed_tensorflow_tpu/parallel/pipeline_parallel.py",
    "tp": "distributed_tensorflow_tpu/parallel/tensor_parallel.py",
    "ep": "distributed_tensorflow_tpu/parallel/expert_parallel.py",
    "sp": "distributed_tensorflow_tpu/parallel/sequence_parallel.py",
    "ps": "distributed_tensorflow_tpu/parallel/ps_emulation.py",
}


def row_family(row: dict) -> str:
    name = row.get("collective", "").split("(", 1)[0].strip()
    return ROW_FAMILY.get(name, name or "?")


def _fmt(n: int) -> str:
    return f"{n:,} B"


def pass_ledger(target, inv: Inventory, ledger: dict) -> list:
    """DTC001: rows <-> traced collectives, byte-exact per
    (family, axis) group. Host-wire rows (the ps topology's pull/push)
    are exempt from jaxpr matching by design — they price TCP + PCI
    traffic the device program never sees — but then the device
    program must be collective-free, which the generic both-direction
    check enforces (a device collective would have no matching row)."""
    out = []
    expected: dict = {}
    for row in ledger.get("rows", ()):
        fam = row_family(row)
        if fam == "host" or row.get("axis") == "host":
            continue
        key = (fam, (row["axis"],))
        expected[key] = expected.get(key, 0) + int(row["bytes"])
    actual = inv.grouped()
    for key in sorted(set(expected) | set(actual)):
        fam, axes = key
        exp, act = expected.get(key, 0), actual.get(key, 0)
        if exp == act:
            continue
        sites = sorted({e.site for e in inv.priced()
                        if (e.family, e.axes) == key})
        rows = [r["collective"] for r in ledger.get("rows", ())
                if (row_family(r), (r.get("axis"),)) == key]
        if exp == 0:
            what = (f"UNPRICED collective: the traced step moves "
                    f"{_fmt(act)} of {fam} over axis {axes[0]!r} "
                    f"(sites: {', '.join(sites) or '?'}) but the "
                    f"comm_ledger has no row for it")
        elif act == 0:
            what = (f"PHANTOM row(s) {rows}: the ledger prices "
                    f"{_fmt(exp)} of {fam} over axis {axes[0]!r} but "
                    f"the traced step contains no such collective")
        else:
            what = (f"ledger drift: rows {rows} price {_fmt(exp)} of "
                    f"{fam} over axis {axes[0]!r}, the traced step "
                    f"moves {_fmt(act)} "
                    f"(sites: {', '.join(sites) or '?'})")
        out.append(Finding(
            "DTC001", f"ledger:{target.name}:{fam}:{axes[0]}",
            MODE_PATH.get(target.mode, "tools/dttcheck"), 0,
            f"[{target.name}] {what}"))
    return out


def pass_deadlock(target, inv: Inventory, ledger: dict | None) -> list:
    """DTC002: the static twin of the r11 watchdog's two documented
    deadlock classes — divergent collective sequences across cond
    branches, and collectives over axis names the lowered mesh does
    not carry (plus the unprovable case: a collective under `while`)."""
    out = []
    path = MODE_PATH.get(target.mode, "tools/dttcheck")
    for site, sigs in inv.cond_mismatches:
        short = [tuple((f, a) for f, a, _ in s) for s in sigs]
        out.append(Finding(
            "DTC002", f"cond:{target.name}:{site}", path, 0,
            f"[{target.name}] divergent cond/switch branches at {site}: "
            f"collective signatures differ across branches "
            f"({short}) — ranks taking different branches rendezvous "
            f"on different collectives and deadlock"))
    for site, axes, env in inv.bad_axes:
        out.append(Finding(
            "DTC002", f"axis:{target.name}:{site}:{','.join(axes)}",
            path, 0,
            f"[{target.name}] collective at {site} names axis(es) "
            f"{axes} not bound by the enclosing mesh {tuple(env)}"))
    for site in inv.unbounded:
        out.append(Finding(
            "DTC002", f"while:{target.name}:{site}", path, 0,
            f"[{target.name}] collective inside a while loop at {site}: "
            f"trip count is not static, wire bytes are unprovable "
            f"(the entry is excluded from the byte proof)"))
    for i, (op, line) in enumerate(getattr(inv, "unparsed", ())):
        out.append(Finding(
            "DTC002", f"unparsed:{target.name}:{op}:{i}", path, 0,
            f"[{target.name}] compiled HLO contains a {op} the "
            f"inventory parser could not read ({line!r}) — its wire "
            f"bytes are uncounted, so nothing about this step is "
            f"proven; extend tools/dttcheck/inventory.hlo_inventory"))
    mesh_axes = (set(target.mesh.axis_names)
                 if target.mesh is not None else set())
    for row in (ledger or {}).get("rows", ()):
        axis = row.get("axis")
        if axis in (None, "host") or row_family(row) == "host":
            continue
        if mesh_axes and axis not in mesh_axes:
            out.append(Finding(
                "DTC002", f"row-axis:{target.name}:{axis}", path, 0,
                f"[{target.name}] ledger row {row.get('collective')!r} "
                f"claims axis {axis!r}, which does not exist on the "
                f"mesh {sorted(mesh_axes)} this step lowers for"))
    return out


def _jit_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            yield eqn


def pass_donation(target, closed) -> list:
    """DTC003: donated inputs verified against the jaxpr's actual
    aliasing opportunity. XLA aliases a donated buffer only to an
    output of identical shape/dtype; a donated invar with no matching
    output is a wasted donation (the buffer dies for nothing), and a
    builder that promises donation but lowers none has silently lost
    the in-place update path."""
    out = []
    path = MODE_PATH.get(target.mode, "tools/dttcheck")
    if not target.donate:
        return out
    donated_any = False
    for eqn in _jit_eqns(closed.jaxpr):
        donated = eqn.params.get("donated_invars", ())
        if not any(donated):
            continue
        donated_any = True
        outs = Counter((tuple(v.aval.shape), str(v.aval.dtype))
                       for v in eqn.outvars)
        for i, (don, var) in enumerate(zip(donated, eqn.invars)):
            if not don:
                continue
            sig = (tuple(var.aval.shape), str(var.aval.dtype))
            if outs[sig] > 0:
                outs[sig] -= 1
            else:
                out.append(Finding(
                    "DTC003",
                    f"donate:{target.name}:arg{i}:"
                    f"{sig[1]}{list(sig[0])}",
                    path, 0,
                    f"[{target.name}] donated input {i} "
                    f"({sig[1]}{list(sig[0])}) has no same-shape/dtype "
                    f"output to alias — the buffer is freed for "
                    f"nothing (XLA will warn and copy)"))
    if not donated_any:
        out.append(Finding(
            "DTC003", f"donate:{target.name}:none", path, 0,
            f"[{target.name}] the builder promises donation "
            f"(donate=True) but the lowered jaxpr donates no input — "
            f"the in-place state update was silently lost"))
    return out


def pass_replication(target, closed) -> list:
    """DTC004: the declared plan vs the lowered split. For shard_map
    modes the jaxpr records, per input, exactly which dims split over
    which axes (``in_specs``); a leaf the plan declares sharded but the
    jaxpr replicates (or vice versa) is layout drift the memory budget
    and checkpoint layouts silently inherit. GSPMD (TP) targets carry
    no plan here — their commitment check is placement-based
    (pass_replication_gspmd)."""
    out = []
    path = MODE_PATH.get(target.mode, "tools/dttcheck")
    if target.plan is None:
        return out
    for eqn in _jit_eqns(closed.jaxpr):
        inner = eqn.params["jaxpr"].jaxpr
        sm = next((e for e in inner.eqns
                   if e.primitive.name == "shard_map"), None)
        if sm is None:
            continue
        in_specs = sm.params.get("in_specs", ())
        pos_of = {id(v): j for j, v in enumerate(sm.invars)}
        import jax

        from distributed_tensorflow_tpu.utils.pytree import path_key

        flat_paths = [
            path_key(kp)
            for kp, _ in jax.tree_util.tree_flatten_with_path(
                target.args)[0]]
        for i, expected in enumerate(target.plan):
            if i >= len(inner.invars):
                break
            j = pos_of.get(id(inner.invars[i]))
            if j is None or j >= len(in_specs):
                continue  # leaf transformed before entering shard_map
            actual = tuple(
                a for axes in in_specs[j] if axes is not None
                for a in (axes if isinstance(axes, tuple) else (axes,)))
            leaf = flat_paths[i] if i < len(flat_paths) else f"leaf{i}"
            if set(expected) - set(actual):
                out.append(Finding(
                    "DTC004", f"replication:{target.name}:{leaf}", path,
                    0,
                    f"[{target.name}] plan declares leaf {leaf!r} "
                    f"sharded over {tuple(expected)} but the lowered "
                    f"shard_map replicates it (in_specs="
                    f"{in_specs[j]}) — a full copy per device "
                    f"where the budget prices a shard"))
            elif set(actual) - set(expected):
                out.append(Finding(
                    "DTC004", f"replication:{target.name}:{leaf}", path,
                    0,
                    f"[{target.name}] plan declares leaf {leaf!r} "
                    f"replicated but the lowered shard_map splits it "
                    f"over {tuple(actual)} — the standard-layout "
                    f"contract (checkpoints, budgets) is broken"))
        break  # one shard_map per step — the repo's builders' shape
    return out


def pass_replication_gspmd(target) -> list:
    """DTC004 for GSPMD targets: every leaf ``tp_param_specs`` declares
    split must be COMMITTED split on the mesh (the partitioner derives
    all collectives from these placements — a silently replicated leaf
    voids the whole sharding story)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_tpu.parallel.tensor_parallel import (
        tp_param_specs,
    )

    from distributed_tensorflow_tpu.utils.pytree import path_key

    out = []
    state = target.args[0]
    specs = tp_param_specs(state.params)
    flat_specs = jax.tree.leaves(specs,
                                 is_leaf=lambda v: isinstance(v, P))
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    declared_split = 0
    for (kp, leaf), spec in zip(flat, flat_specs):
        name = path_key(kp)
        if spec == P():
            continue
        declared_split += 1
        if isinstance(leaf, jax.Array) and leaf.is_fully_replicated:
            out.append(Finding(
                "DTC004", f"replication:{target.name}:{name}",
                MODE_PATH["tp"], 0,
                f"[{target.name}] tp_param_specs declares {name!r} "
                f"split {spec} but the committed placement is fully "
                f"replicated — GSPMD will derive no collective and "
                f"every chip holds the full leaf"))
    if declared_split == 0:
        out.append(Finding(
            "DTC004", f"replication:{target.name}:no-split",
            MODE_PATH["tp"], 0,
            f"[{target.name}] tp_param_specs declares NO split leaf "
            f"for this model — tensor parallelism would shard nothing "
            f"(the has_tp_specs guard class)"))
    return out
