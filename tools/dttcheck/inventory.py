"""Collective inventory extraction — the measurement half of dttcheck.

``trace_inventory`` runs ``jax.make_jaxpr`` on a step function (trace
only — no XLA compile, no chip) and walks every equation, recursing
into ``jit`` / ``shard_map`` / ``scan`` / ``cond`` / ``while`` /
``remat`` / custom-vjp bodies, to produce a :class:`Inventory`: one
entry per collective equation with its primitive FAMILY, mesh AXES,
and analytic WIRE BYTES (trip-count-multiplied — a ppermute inside a
``lax.scan`` of length T moves T payloads, bubble ticks included:
that is what the lowered program puts on the interconnect, which is
exactly where hand-maintained ledgers drift).

Wire-byte conventions (must match the ``*_comm_rows`` builders' —
docs/ARCHITECTURE.md "Resource plane"):

=================  =============================================
``psum``           2 x operand bytes (ring all-reduce moves ~2N)
``reduce_scatter`` operand bytes (each rank feeds N, keeps N/D)
``all_gather``     output bytes (each rank ends with the full N)
``ppermute``       operand bytes (point-to-point payload)
``all_to_all``     operand bytes
=================  =============================================

Control-plane exemption (documented, both directions of the ledger
proof honor it): an equation whose float payloads are ALL rank-0
scalars (metrics/loss reductions, clip-norm totals) or whose payload
is entirely non-float (PRNG/u32 machinery, routing indices) is
CONTROL traffic — excluded from the byte proof, but still counted and
reported so nothing disappears silently.

``hlo_inventory`` is the second source, for GSPMD modes (tensor
parallelism) whose jaxpr is global-view by design — the collectives
exist only AFTER the SPMD partitioner runs. It parses the compiled
HLO text (CPU backend, no chip) for ``all-reduce`` / ``all-gather`` /
``reduce-scatter`` / ``collective-permute`` ops, maps each op's
``replica_groups`` back onto the mesh's named axes, and applies the
same byte and exemption conventions. Known limit: HLO collectives
inside ``while`` bodies count once (the repo's GSPMD steps compile no
loops; the jaxpr walker is the loop-exact path).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

CONTROL_FAMILIES = ("axis_index",)  # index reads move nothing

#: jaxpr primitive name -> inventory family
PRIM_FAMILY = {
    "psum": "psum",
    # what psum / all_gather stage as inside a check_vma=True shard_map
    # body; the repo's builders trace check_vma=False but the walker
    # must not go blind on a checked caller
    "psum_invariant": "psum",
    "all_gather_invariant": "all_gather",
    "reduce_scatter": "reduce_scatter",   # lax.psum_scatter lowers here
    "all_gather": "all_gather",
    "ppermute": "ppermute",
    "all_to_all": "all_to_all",
}

#: HLO op name -> inventory family
HLO_FAMILY = {
    "all-reduce": "psum",
    "reduce-scatter": "reduce_scatter",
    "all-gather": "all_gather",
    "collective-permute": "ppermute",
    "all-to-all": "all_to_all",
}


@dataclass
class Entry:
    """One collective equation (or HLO op), trip-multiplied."""

    family: str
    axes: tuple            # mesh axis names the collective runs over
    wire_bytes: int        # per the conventions above, x trips
    payload_bytes: int     # one trip's operand payload
    trips: int             # static trip count (scan lengths multiplied)
    site: str              # human locator ("scan/shard_map/psum", ...)
    control: bool = False  # exempt scalar/non-float control traffic
    provable: bool = True  # False under `while`: trip count unknowable,
                           # so the bytes must NOT enter the ledger
                           # proof (DTC002 already names the site)


@dataclass
class Inventory:
    entries: list = field(default_factory=list)
    #: (site, branch signatures) for every cond whose branches disagree
    cond_mismatches: list = field(default_factory=list)
    #: (site, axes, env) for collectives naming an unbound axis
    bad_axes: list = field(default_factory=list)
    #: sites of collectives under a `while` (trip count unprovable)
    unbounded: list = field(default_factory=list)
    #: HLO lines that LOOK collective but the parser could not read —
    #: a proof tool must fail loudly on these, never skip (DTC002)
    unparsed: list = field(default_factory=list)

    def priced(self):
        return [e for e in self.entries
                if not e.control and e.provable]

    def control(self):
        return [e for e in self.entries if e.control]

    def grouped(self) -> dict:
        """(family, axes) -> total wire bytes over the priced entries."""
        out: dict = {}
        for e in self.priced():
            key = (e.family, e.axes)
            out[key] = out.get(key, 0) + e.wire_bytes
        return out

    def total_bytes(self) -> int:
        return sum(e.wire_bytes for e in self.priced())


def _is_float(dtype) -> bool:
    return "float" in str(dtype) or str(dtype) in ("bfloat16",)


def _aval_bytes(aval) -> int:
    import numpy as np

    size = 1
    for d in getattr(aval, "shape", ()):
        size *= int(d)
    return size * np.dtype(aval.dtype).itemsize


def _collective_payload(eqn):
    """(float_bytes, control: bool) for one collective eqn. Control =
    all float operands rank-0, or no float operands at all."""
    avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
    floats = [a for a in avals if _is_float(a.dtype)]
    if not floats:
        return 0, True
    if all(not a.shape for a in floats):
        return sum(_aval_bytes(a) for a in floats), True
    return sum(_aval_bytes(a) for a in floats), False


def _wire_bytes(eqn, family: str, payload: int) -> int:
    if family == "psum":
        return 2 * payload
    if family == "all_gather":
        out = sum(_aval_bytes(v.aval) for v in eqn.outvars
                  if _is_float(v.aval.dtype))
        return out
    return payload  # reduce_scatter / ppermute / all_to_all: input bytes


def _collective_axes(eqn) -> tuple:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _sub_jaxprs(value):
    """Jaxpr-like objects reachable from one eqn param value."""
    if hasattr(value, "eqns"):
        return [value]
    if hasattr(value, "jaxpr"):
        return [value.jaxpr]
    if isinstance(value, (tuple, list)):
        out = []
        for v in value:
            out.extend(_sub_jaxprs(v))
        return out
    return []


def _signature(jaxpr, env: tuple) -> tuple:
    """The collective SIGNATURE of a (branch) jaxpr: the ordered tuple
    of (family, axes, payload) every rank would execute — the SPMD
    deadlock invariant: branches of a ``lax.cond``/``switch`` must
    carry identical signatures, else ranks taking different branches
    rendezvous on different collectives and hang (the r11 watchdog's
    documented deadlock class, statically)."""
    sig = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in PRIM_FAMILY:
            payload, _ = _collective_payload(eqn)
            sig.append((PRIM_FAMILY[name], _collective_axes(eqn), payload))
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                mult = eqn.params.get("length", 1) \
                    if name == "scan" else 1
                sig.extend(_signature(sub, env) * int(mult or 1))
    return tuple(sig)


def walk_jaxpr(jaxpr, inv: Inventory, *, trips: int = 1,
               env: tuple = (), site: str = "") -> None:
    """Recursive equation walk accumulating ``inv``. ``trips`` is the
    product of enclosing static scan lengths; ``env`` the axis names
    bound by enclosing shard_maps."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        here = f"{site}/{name}" if site else name
        if name in PRIM_FAMILY:
            family = PRIM_FAMILY[name]
            payload, control = _collective_payload(eqn)
            axes = _collective_axes(eqn)
            if env and not set(axes) <= set(env):
                inv.bad_axes.append((here, axes, env))
            inv.entries.append(Entry(
                family=family, axes=axes,
                wire_bytes=_wire_bytes(eqn, family, payload) * trips,
                payload_bytes=payload, trips=trips, site=here,
                control=control))
            continue
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            axes = tuple(getattr(mesh, "axis_names", ()))
            body = eqn.params.get("jaxpr")
            body = body.jaxpr if hasattr(body, "jaxpr") else body
            walk_jaxpr(body, inv, trips=trips, env=env + axes, site=here)
            continue
        if name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            length = int(eqn.params.get("length") or 1)
            walk_jaxpr(body, inv, trips=trips * length, env=env,
                       site=here)
            continue
        if name == "while":
            sub = Inventory()
            for key in ("cond_jaxpr", "body_jaxpr"):
                cj = eqn.params.get(key)
                if cj is not None:
                    walk_jaxpr(cj.jaxpr, sub, trips=1, env=env, site=here)
            if sub.priced():
                inv.unbounded.append(here)
            for e in sub.entries:
                # the trip count is unknowable: keep the entry visible
                # (control()/reporting) but OUT of the byte proof — a
                # 1-trip guess entering grouped() would fabricate a
                # drift (or worse, spuriously prove a guessed ledger)
                e.provable = False
            inv.entries.extend(sub.entries)
            inv.cond_mismatches.extend(sub.cond_mismatches)
            inv.bad_axes.extend(sub.bad_axes)
            continue
        if name == "cond":
            branches = eqn.params.get("branches", ())
            sigs = [_signature(b.jaxpr, env) for b in branches]
            if len(set(sigs)) > 1:
                inv.cond_mismatches.append((here, sigs))
            if branches:
                # count one branch: signatures equal in a deadlock-free
                # program, and a mismatch is already its own finding
                walk_jaxpr(branches[0].jaxpr, inv, trips=trips, env=env,
                           site=here)
            continue
        # generic recursion: jit, remat/checkpoint, custom_vjp/jvp,
        # closed_call, ... — anything carrying sub-jaxprs in params
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                walk_jaxpr(sub, inv, trips=trips, env=env, site=here)


def trace_inventory(fn, args) -> tuple:
    """(closed_jaxpr, Inventory) for ``fn(*args)``. The jaxpr is DCE'd
    with all outputs live first, so dead code a builder traces but the
    compiler would drop (e.g. the overlap prefetch gather in a one-step
    host-fed wrapper) doesn't register as phantom traffic — the
    inventory reflects the computation XLA actually lowers."""
    import jax
    from jax.interpreters import partial_eval as pe

    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr
    try:
        jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    except Exception:  # noqa: BLE001 — DCE is an optimization, not a need
        pass
    inv = Inventory()
    walk_jaxpr(jaxpr, inv)
    return closed, inv


# ----------------------------------------------------------- HLO source


_HLO_OP = re.compile(
    r"=\s*(\w+)\[([\d,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)\(")
#: the combined form: XLA's collective combiner merges neighbouring
#: all-reduces into ONE variadic op whose result is a tuple — every
#: element is its own payload (and its own control/priced verdict).
#: all-reduce only: there an element's operand has its result's shape
#: (optimized HLO prints operands by name); any other variadic
#: collective stays UNPARSED, loudly
_HLO_TUPLE_OP = re.compile(r"=\s*\(([^()]*)\)\s+all-reduce\(")
_HLO_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
#: loose probe: any instruction CALLING a collective op (hyphenated
#: names with an open paren only occur at instruction position — jax
#: metadata op_names use underscores). A line this hits that _HLO_OP
#: cannot parse (variadic/tuple-shaped result, an async -start form)
#: is recorded as UNPARSED and becomes a DTC002 finding: a proof tool
#: fails loudly on traffic it cannot read, it never skips it.
_HLO_COLLECTIVE_CALL = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start)?\(")
_HLO_OPERAND = re.compile(r"\(\s*(\w+)\[([\d,]*)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{(\{[\d,{} ]*\})\}")
_GROUPS_IOTA = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS = re.compile(r"source_target_pairs=\{([\d,{} ]*)\}")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2,
                "u32": 4, "s32": 4, "u64": 8, "s64": 8, "u8": 1, "s8": 1,
                "pred": 1, "u16": 2, "s16": 2}


def _shape_bytes(dtype: str, dims: str) -> tuple:
    size = 1
    shape = tuple(int(d) for d in dims.split(",") if d.strip())
    for d in shape:
        size *= d
    return size * _DTYPE_BYTES.get(dtype, 4), shape


def _mesh_axis_groups(mesh) -> dict:
    """axis name -> the set of device-id groups an all-reduce over that
    axis uses (devices enumerated row-major over the mesh, the XLA
    convention for a committed NamedSharding)."""
    import numpy as np

    names = tuple(mesh.axis_names)
    shape = tuple(mesh.shape[n] for n in names)
    ids = np.arange(int(np.prod(shape))).reshape(shape)
    out = {}
    for i, name in enumerate(names):
        moved = np.moveaxis(ids, i, -1).reshape(-1, shape[i])
        out[name] = frozenset(frozenset(int(x) for x in row)
                              for row in moved)
    out["+".join(names)] = frozenset(
        {frozenset(int(x) for x in ids.reshape(-1))})
    return out


def _classify_groups(groups, axis_groups: dict) -> tuple:
    gset = frozenset(frozenset(g) for g in groups)
    for name, expected in axis_groups.items():
        if gset == expected:
            return tuple(name.split("+"))
    return ("?",)


def _parse_groups(line: str, n_devices: int):
    m = _GROUPS_LIST.search(line)
    if m:
        return [[int(x) for x in g.split(",") if x.strip()]
                for g in re.findall(r"\{([\d, ]*)\}", m.group(1))]
    m = _GROUPS_IOTA.search(line)
    if m:
        import numpy as np

        out_dims = [int(x) for x in m.group(1).split(",")]
        iota_dims = [int(x) for x in m.group(2).split(",")]
        perm = ([int(x) for x in m.group(3).split(",")]
                if m.group(3) else list(range(len(iota_dims))))
        ids = np.arange(int(np.prod(iota_dims))).reshape(iota_dims)
        ids = ids.transpose(perm).reshape(out_dims)
        return [list(map(int, row)) for row in ids]
    return [list(range(n_devices))]


def _classify_pairs(line: str, mesh) -> tuple:
    """collective-permute axis: every source->target pair moves along
    exactly one mesh axis coordinate."""
    import numpy as np

    m = _PAIRS.search(line)
    if not m:
        return ("?",)
    pairs = [[int(x) for x in p.split(",")]
             for p in re.findall(r"\{(\d+,\d+)\}", m.group(0))]
    names = tuple(mesh.axis_names)
    shape = tuple(mesh.shape[n] for n in names)
    coords = {i: np.unravel_index(i, shape) for i in range(
        int(np.prod(shape)))}
    moved = set()
    for s, t in pairs:
        cs, ct = coords[s], coords[t]
        for i, name in enumerate(names):
            if cs[i] != ct[i]:
                moved.add(name)
    return tuple(sorted(moved)) if moved else ("?",)


def hlo_inventory(hlo_text: str, mesh) -> Inventory:
    """Inventory from compiled (post-SPMD-partitioning) HLO text — the
    GSPMD modes' source. Same families, byte conventions, and control
    exemption as the jaxpr walker."""
    inv = Inventory()
    axis_groups = _mesh_axis_groups(mesh)
    n_dev = 1
    for n in mesh.axis_names:
        n_dev *= mesh.shape[n]
    for line in hlo_text.splitlines():
        m = _HLO_OP.search(line)
        tm = None if m else _HLO_TUPLE_OP.search(line)
        if not m and not tm:
            probe = _HLO_COLLECTIVE_CALL.search(line)
            if probe:
                inv.unparsed.append(
                    (probe.group(1), line.strip()[:160]))
            continue
        if m:
            op = m.group(3)
            results = [(m.group(1), m.group(2))]
            om = _HLO_OPERAND.search(line[m.end() - 1:])
            operands = [(om.group(1), om.group(2))] if om else results
        else:
            op = "all-reduce"
            results = operands = _HLO_SHAPE.findall(tm.group(1))
        family = HLO_FAMILY[op]
        if family == "ppermute":
            axes = _classify_pairs(line, mesh)
        else:
            axes = _classify_groups(_parse_groups(line, n_dev),
                                    axis_groups)
        for (dtype, dims), (in_dtype, in_dims) in zip(results, operands):
            out_bytes, out_shape = _shape_bytes(dtype, dims)
            in_bytes, in_shape = _shape_bytes(in_dtype, in_dims)
            is_float = dtype in ("f64", "f32", "bf16", "f16")
            control = (not is_float) or (not out_shape and not in_shape)
            payload = in_bytes
            if family == "psum":
                wire = 2 * payload
            elif family == "all_gather":
                wire = out_bytes
            else:
                wire = payload
            inv.entries.append(Entry(
                family=family, axes=axes, wire_bytes=wire,
                payload_bytes=payload, trips=1,
                site=f"hlo/{op}", control=control))
    return inv
