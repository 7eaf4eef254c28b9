"""Aggregate per-op device time from a jax.profiler trace, and print
static pipeline schedules.

The per-op instrument: the trace's device "XLA Ops" lane durations sum
to the wall, per-op, where dispatch-polluted microbenchmarks do not. Loads the newest
``*.trace.json.gz`` under a profile dir, selects the XLA Ops thread,
and prints a table: op name, calls, total ms, share, bytes accessed.

``--schedule K M [V] [gpipe|interleaved|zb]`` instead prints the static
pipeline tick table the --pipeline step compiles for K stages x M
microbatches x V virtual stage groups (parallel/pp_schedule.py — GPipe
when V=1, interleaved when V>1), with the per-stage useful-tick
fraction and total scheduled block-group computations: the masked-tick
cost model at a glance, no chip required. ``zb`` prints the combined
zero-bubble F/B/W table (B and W ticks distinguished) with the
useful-fraction comparison against the interleaved baseline.

``--faults`` lists every registered fault-injection point with the
--fault_spec grammar (utils/faults.py) — how the spec strings are
discovered.

``--flops MODEL [BATCH]`` prints the STATIC per-layer FLOPs budget for
one training step of ``MODEL`` at ``BATCH`` (utils/efficiency.
flops_budget — the same accounting behind every loop's ``mfu`` /
``model_flops_per_sec`` scalars and bench.py's efficiency facts), plus
the jitted-lowering ``cost_analysis()`` cross-check where the backend
reports FLOPs. The --mem printer's sibling: memory there, compute here.

``--mem MODEL D [--zero Z] [--optimizer OPT]`` prints the STATIC
per-chip memory budget for ``MODEL`` sharded ``--zero``-style over a
D-way data axis (parallel/zero.zero_memory_budget — jax.eval_shape, no
chip, no compute): param/grad/optimizer bytes per leaf and the per-chip
totals for replicated DP vs ZeRO-1 vs ZeRO-3, plus the per-step
comm-volume comparison (all-reduce 2|G| vs reduce-scatter+all-gather
|G|+|P|) — the D-fold saving auditable anywhere.

``--comm MODEL D [--model_axis K] [--batch B]`` prints the STATIC
per-step collective-comm ledger (utils/resources.comm_ledger — the
parallel modules' own row builders) for every applicable mode at one
glance: DP all-reduce, ZeRO-1/3 reduce-scatter+gather, PP boundary
ppermutes, TP/EP activation psums, SP ring hops — wire bytes per step,
per mode, no chip. The --mem/--flops printers' third sibling: memory,
compute, and now the wire.

``--jaxpr MODEL D [--mode M] [--model_axis K] [--batch B]`` prints the
TRACED collective inventory for one (mode, model) step function — the
fourth sibling of --mem/--flops/--comm: memory, compute, the analytic
wire, and now the wire AS LOWERED. The step is traced chip-free over
the virtual CPU mesh (``tools/dttcheck``'s walker: ``jax.make_jaxpr``
+ a recursive equation walk with static trip counts; GSPMD modes read
compiled CPU HLO), one row per collective equation with family, mesh
axes, trips, and wire bytes — what the analytic ledger row SHOULD say,
measured.

``--predict MODEL D [--mode M] [--batch B]`` prints the PREDICTED STEP
TIME for one (mode, model) cell — the sixth sibling of
--mem/--flops/--comm/--jaxpr/--threads: memory, compute, the wire, the
wire as lowered, the thread plane, and now TIME. The same
``tools.dttperf.predict_step_time`` composition the performance
contract bands bench records against (max(compute/peak,
exposed_comm/bandwidth) + host costs), term by term with each term's
machine-checked provenance — what the DTP001 ceiling IS, shown built.

``--threads`` prints the discovered THREAD INVENTORY — every
concurrent entry point in the tree (Thread/Timer construction sites,
threaded-server handler classes, excepthook/atexit/signal hooks, crash
contexts) with file:line, the shared attributes each root's class
touches, and the guarding locks (tools/dttsan's inventory + lock-set
model, chip-free). The fifth sibling: memory, compute, the wire, the
wire as lowered, and the host thread plane.

The static-analysis siblings of this whole printer family are
``python -m tools.dttlint`` (AST invariants, rules DTT001-DTT011),
``python -m tools.dttcheck`` (jaxpr-level proofs, passes DTC001-DTC004
— the ledger/SPMD verifier whose inventory --jaxpr prints),
``python -m tools.dttsan`` (the host-plane concurrency analyzer whose
inventory --threads prints; passes SAN001-SAN004), and ``python -m
tools.dttperf`` (the performance-contract analyzer whose prediction
--predict prints; passes DTP000-DTP003): where
--schedule/--mem/--flops/--comm/--jaxpr/--threads/--predict PRINT the
tree's static facts, those four ENFORCE them (docs/ARCHITECTURE.md
"Static analysis", "Jaxpr verification", "Concurrency analysis", and
"Performance contracts").

Usage: python tools/trace_ops.py /tmp/profile-dir [top_n]
       python tools/trace_ops.py --schedule K M [V] [gpipe|interleaved|zb]
       python tools/trace_ops.py --faults
       python tools/trace_ops.py --threads
       python tools/trace_ops.py --mem MODEL D [--zero Z] [--optimizer OPT]
       python tools/trace_ops.py --flops MODEL [BATCH]
       python tools/trace_ops.py --comm MODEL D [--model_axis K] [--batch B]
                                 [--zero_overlap] [--bucket_mb N]
       python tools/trace_ops.py --jaxpr MODEL D [--mode M]
                                 [--model_axis K] [--batch B]
       python tools/trace_ops.py --predict MODEL D [--mode M] [--batch B]
       python -m tools.dttlint [--json] [--baseline PATH] [--fix]
       python -m tools.dttcheck [--json] [--mode M] [--model M]
       python -m tools.dttsan [--json] [--baseline PATH] [--threads]
       python -m tools.dttperf [--json] [--mode M] [--model M]
       python -m tools.analyze [--json]
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import sys


def load_trace(profile_dir: str) -> dict:
    paths = sorted(
        glob.glob(os.path.join(profile_dir, "**", "*.trace.json.gz"),
                  recursive=True),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.trace.json.gz under {profile_dir}")
    with gzip.open(paths[-1], "rt") as f:
        return json.load(f)


def xla_op_events(trace: dict) -> list[dict]:
    """Complete events on any thread named 'XLA Ops' (the device lane)."""
    tid_names: dict[tuple, str] = {}
    for e in trace.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tid_names[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", ""))
    out = []
    for e in trace.get("traceEvents", []):
        if e.get("ph") == "X" and "dur" in e:
            if "XLA Ops" in tid_names.get((e.get("pid"), e.get("tid")), ""):
                out.append(e)
    return out


def aggregate(events: list[dict]) -> list[dict]:
    agg: dict[str, dict] = collections.defaultdict(
        lambda: {"calls": 0, "us": 0.0, "bytes": 0})
    for e in events:
        name = e.get("name", "?")
        a = agg[name]
        a["calls"] += 1
        a["us"] += float(e["dur"])
        args = e.get("args", {})
        try:
            a["bytes"] += int(args.get("bytes_accessed", 0))
        except (TypeError, ValueError):
            pass
    rows = [{"op": k, **v} for k, v in agg.items()]
    rows.sort(key=lambda r: -r["us"])
    return rows


def print_schedule(k_stages: int, microbatches: int,
                   virtual_stages: int = 1,
                   schedule: str = "auto") -> None:
    """Print the static (K, M, V) pipeline tick table + schedule cost
    facts — the same builder the compiled step closes over, so what
    prints here IS what runs. ``schedule="zb"`` prints the combined
    zero-bubble F/B/W table with B and W ticks distinguished (and the
    useful-fraction comparison against the interleaved baseline)."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from distributed_tensorflow_tpu.parallel.pp_schedule import (
        build_pp_schedule,
        build_zb_schedule,
        format_schedule,
        format_zb_schedule,
        normalize_pp_schedule,
    )

    if normalize_pp_schedule(schedule, virtual_stages) == "zb":
        print(format_zb_schedule(
            build_zb_schedule(k_stages, microbatches, virtual_stages)))
        return
    sched = build_pp_schedule(k_stages, microbatches, virtual_stages)
    print(format_schedule(sched))
    per_group = f"num_blocks/{k_stages * virtual_stages}"
    print(f"\nscheduled block-group computations per step: "
          f"{sched.num_ticks * k_stages} x ({per_group} blocks each)")


# --mem model configs: the flagship shapes the bench/tests exercise —
# fixed here so the printout is reproducible without a flag parse
_MEM_MODELS = {
    "mlp": dict(image_size=28, channels=1, num_classes=10),
    "deep_cnn": dict(image_size=28, channels=1, num_classes=10),
    "resnet20": dict(image_size=32, channels=3, num_classes=10),
    "lm": dict(vocab_size=32768, seq_len=1024, d_model=256, num_heads=4,
               num_blocks=4),
}


def _fmt_bytes(n: int) -> str:
    for unit, div in (("GB", 2**30), ("MB", 2**20), ("KB", 2**10)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n} B"


def print_mem(model_name: str, d: int, zero_level: int | None = None,
              optimizer: str = "adam") -> None:
    """Print the static per-chip memory budget (replicated vs ZeRO-1 vs
    ZeRO-3 over a D-way data axis) for one of the flagship models — the
    same ``zero_memory_budget`` accounting bench.py records, so what
    prints here IS what the artifact reports. No chip, no compute
    (``jax.eval_shape``)."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from distributed_tensorflow_tpu.models import get_model
    from distributed_tensorflow_tpu.parallel.zero import zero_memory_budget
    from distributed_tensorflow_tpu.training import get_optimizer

    if model_name not in _MEM_MODELS:
        raise SystemExit(f"--mem: unknown model {model_name!r}; "
                         f"available: {sorted(_MEM_MODELS)}")
    if zero_level is not None and zero_level not in (0, 1, 3):
        raise SystemExit(f"--zero={zero_level} must be 0, 1 or 3")
    model = get_model(model_name, **_MEM_MODELS[model_name])
    budget = zero_memory_budget(model, get_optimizer(optimizer, 1e-3), d)

    print(f"static per-chip memory budget — model={model_name} D={d} "
          f"optimizer={optimizer} (jax.eval_shape; padding included)")
    print(f"{'kind':<6} {'leaf':<44} {'elements':>10} {'bytes':>12} "
          f"{'1/D bytes':>12}")
    for r in budget["rows"]:
        print(f"{r['kind']:<6} {r['leaf'][:44]:<44} {r['elements']:>10} "
              f"{r['bytes']:>12} "
              f"{r['sharded_bytes'] if r['chunked'] else r['bytes']:>12}")
    print()
    levels = ((0, "replicated"), (1, "zero1"), (3, "zero3"))
    if zero_level is not None:
        levels = tuple(lv for lv in levels if lv[0] == zero_level)
    print(f"{'mode':<12} {'params/chip':>12} {'opt/chip':>12} "
          f"{'grads/chip':>12} {'total/chip':>12}")
    for _, key in levels:
        pc = budget["per_chip"][key]
        total = pc["params"] + pc["opt"] + pc["grads"]
        print(f"{key:<12} {_fmt_bytes(pc['params']):>12} "
              f"{_fmt_bytes(pc['opt']):>12} {_fmt_bytes(pc['grads']):>12} "
              f"{_fmt_bytes(total):>12}")
    print(f"\nopt-state reduction (zero1/zero3 vs replicated): "
          f"{budget['opt_reduction']:.2f}x")
    print(f"param reduction (zero3 vs replicated): "
          f"{budget['param_reduction']:.2f}x")
    g = budget["param_bytes"]  # grads mirror the param leaves
    print(f"per-step comm volume: all-reduce 2|G| = {_fmt_bytes(2 * g)}; "
          f"reduce-scatter+all-gather |G|+|P| = "
          f"{_fmt_bytes(g + budget['param_bytes'])} "
          f"(zero3 re-gathers params in forward/backward instead)")


def print_flops(model_name: str, batch: int = 128) -> None:
    """Print the static per-layer FLOPs budget for one training step
    (utils/efficiency.flops_budget — the exact accounting the loops'
    ``mfu``/``model_flops_per_sec`` scalars use, so what prints here IS
    what the metrics report), with the XLA ``cost_analysis()``
    cross-check where the backend reports it. No chip required for the
    analytic half."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from distributed_tensorflow_tpu.models import get_model
    from distributed_tensorflow_tpu.utils.efficiency import (
        TRAIN_FLOPS_MULTIPLIER,
        flops_budget,
    )

    if model_name not in _MEM_MODELS:
        raise SystemExit(f"--flops: unknown model {model_name!r}; "
                         f"available: {sorted(_MEM_MODELS)}")
    if batch < 1:
        raise SystemExit(f"--flops: batch must be >= 1, got {batch}")
    model = get_model(model_name, **_MEM_MODELS[model_name])
    b = flops_budget(model, batch, xla=True)

    print(f"static FLOPs budget — model={model_name} batch={batch} "
          f"(analytic per-layer forward; training = "
          f"{TRAIN_FLOPS_MULTIPLIER}x forward)")
    total = b["fwd_flops_per_example"]
    print(f"{'layer':<24} {'fwd FLOPs/example':>18} {'share':>7}")
    for r in b["rows"]:
        print(f"{r['layer']:<24} {r['flops']:>18,} "
              f"{r['flops'] / total:>7.1%}")
    print(f"{'TOTAL forward':<24} {total:>18,}")
    print(f"\ntrain FLOPs/example (fwd+bwd): "
          f"{b['train_flops_per_example']:,}")
    print(f"train FLOPs/step at batch {batch}: {b['flops_per_step']:,}")
    if b["xla_flops_per_step"] is not None:
        ratio = b["xla_flops_per_step"] / b["flops_per_step"]
        print(f"XLA cost_analysis cross-check: "
              f"{int(b['xla_flops_per_step']):,} FLOPs/step "
              f"({ratio:.2f}x analytic)")
    else:
        print("XLA cost_analysis cross-check: n/a (backend reports no "
              "FLOPs or no backend)")


def print_comm(model_name: str, d: int, model_axis: int = 2,
               batch: int = 128, zero_overlap: bool = False,
               bucket_mb: float = 4.0) -> None:
    """Print the static per-step collective-comm ledger for every mode
    that applies to ``MODEL`` on ``D`` chips — the same
    ``utils/resources.comm_ledger`` accounting behind every loop's
    ``comm_bytes_per_step`` scalar, so what prints here IS what the
    metrics report. No chip (jax.eval_shape only). ``--zero_overlap``
    [--bucket_mb N] prices the ZeRO rows under the bucketed/prefetched
    overlap pattern — the exposed column shows what stays on the
    critical path."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from distributed_tensorflow_tpu.models import get_model
    from distributed_tensorflow_tpu.utils.resources import comm_ledger

    if model_name not in _MEM_MODELS:
        raise SystemExit(f"--comm: unknown model {model_name!r}; "
                         f"available: {sorted(_MEM_MODELS)}")
    if d < 1:
        raise SystemExit(f"--comm: D={d} must be >= 1")
    model_axis = max(2, model_axis)
    model = get_model(model_name, **_MEM_MODELS[model_name])
    is_tf = model_name in ("lm",)
    modes = [("dp", dict(data_ways=d)),
             ("zero1", dict(data_ways=d, zero_level=1)),
             ("zero3", dict(data_ways=d, zero_level=3)),
             # the reference topology: per-worker pull/push over the
             # HOST wire (parallel/ps_emulation.ps_comm_rows) — both
             # cycle shapes, since --ps_mirror zeroes the pull row
             ("ps", dict(data_ways=d)),
             ("ps-full", dict(data_ways=d, ps_mirror=False,
                              ps_wire="bf16"))]
    if is_tf and d >= model_axis:
        dw = max(1, d // model_axis)
        modes += [("pp", dict(data_ways=dw, model_axis=model_axis)),
                  ("pp-zb", dict(data_ways=dw, model_axis=model_axis)),
                  ("tp", dict(data_ways=dw, model_axis=model_axis)),
                  ("sp", dict(data_ways=dw, model_axis=model_axis))]
    print(f"static per-step comm ledger — model={model_name} D={d} "
          f"batch={batch}"
          + (f" model_axis={model_axis}" if is_tf else "")
          + (f" zero_overlap bucket={bucket_mb:g}MB" if zero_overlap
             else "")
          + " (analytic; all-reduce ~2|G|, reduce-scatter |G|, "
            "all-gather |P|)")
    for mode, cfg in modes:
        kw = dict(cfg)
        if mode == "pp-zb":
            mode, kw["pp_schedule"] = "pp", "zb"
            label = "pp (zb)"
        elif mode == "ps-full":
            mode = "ps"
            label = "ps (full pulls, bf16 wire)"
        else:
            label = mode
        if mode.startswith("zero") and zero_overlap:
            kw.update(zero_overlap=True, zero_bucket_mb=bucket_mb)
        led = comm_ledger(model, None, batch, mode=mode, **kw)
        print(f"\n{label} (data x model = {led['data_ways']} x "
              f"{led['model_axis']}): "
              f"{_fmt_bytes(led['comm_bytes_per_step'])}/step, "
              f"{_fmt_bytes(led['comm_exposed_bytes_per_step'])} exposed")
        for r in led["rows"]:
            print(f"  {r['collective']:<42} {r['axis']:<6} "
                  f"{_fmt_bytes(r['bytes']):>12} "
                  f"{_fmt_bytes(r.get('exposed_bytes', r['bytes'])):>12}"
                  f"  {r.get('note', '')}")
        if not led["rows"]:
            print("  (no collectives — single-chip layout)")


def print_jaxpr_inventory(model_name: str, d: int, mode: str = "dp",
                          model_axis: int = 2,
                          batch: int = 128) -> None:
    """Print the traced per-step collective inventory for one
    (mode, model) cell — the same walker behind ``python -m
    tools.dttcheck``'s ledger proof, so what prints here IS what the
    proof measured. Chip-free: the step traces over the virtual
    8-device CPU mesh (forced before jax initializes, the conftest
    strategy); GSPMD modes (tp) compile tiny CPU HLO instead."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.dttcheck.scenarios import ensure_cpu_mesh

    ensure_cpu_mesh()
    from distributed_tensorflow_tpu.models import get_model
    from distributed_tensorflow_tpu.training import get_optimizer
    from tools.dttcheck.inventory import hlo_inventory, trace_inventory
    from tools.dttcheck.scenarios import build_from_config

    if model_name not in _MEM_MODELS:
        raise SystemExit(f"--jaxpr: unknown model {model_name!r}; "
                         f"available: {sorted(_MEM_MODELS)}")
    known = ("dp", "zero1", "zero3", "pp", "tp", "ep", "sp", "ps")
    if mode not in known:
        raise SystemExit(f"--jaxpr: unknown mode {mode!r}; one of "
                         f"{', '.join(known)}")
    kw = _MEM_MODELS[model_name]
    if mode == "sp":
        from distributed_tensorflow_tpu.parallel.mesh import MODEL_AXIS

        kw = dict(kw, seq_axis=MODEL_AXIS)
    model = get_model(model_name, **kw)
    model_ways = model_axis if mode in ("pp", "tp", "ep", "sp") else 1
    target = build_from_config(
        model, get_optimizer("adam", 1e-3), batch,
        mode=mode, data_ways=max(1, d // model_ways),
        model_axis=model_ways,
        zero_level=int(mode[4:]) if mode.startswith("zero") else 0,
        model_name=model_name)
    _, inv = trace_inventory(target.step_fn, target.args)
    if target.hlo:
        compiled = target.step_fn.lower(*target.args).compile()
        inv = hlo_inventory(compiled.as_text(), target.mesh)
    print(f"traced collective inventory — model={model_name} "
          f"mode={mode} D={d} batch={batch} "
          f"(source: {'compiled CPU HLO' if target.hlo else 'jaxpr'}; "
          f"wire conventions: all-reduce 2x, reduce-scatter in, "
          f"all-gather out, ppermute payload)")
    print(f"{'family':<16} {'axes':<14} {'trips':>6} {'payload':>12} "
          f"{'wire bytes':>12}  site")
    for e in sorted(inv.priced(), key=lambda e: -e.wire_bytes):
        print(f"{e.family:<16} {','.join(e.axes):<14} {e.trips:>6} "
              f"{_fmt_bytes(e.payload_bytes):>12} "
              f"{_fmt_bytes(e.wire_bytes):>12}  {e.site}")
    ctrl = inv.control()
    print(f"\ntotal: {len(inv.priced())} priced collective(s), "
          f"{_fmt_bytes(inv.total_bytes())}/step on the wire; "
          f"{len(ctrl)} control-plane (scalar metrics / rng) exempt")
    for key, bytes_ in sorted(inv.grouped().items()):
        fam, axes = key
        print(f"  {fam} over {','.join(axes)}: {_fmt_bytes(bytes_)}")


def print_predict(model_name: str, d: int, mode: str = "dp",
                  batch: int | None = None) -> None:
    """Print the predicted step time for one (mode, model) cell — the
    same ``tools.dttperf.predict_step_time`` composition the
    performance contract (DTP001) bands bench records against, shown
    term by term with each term's provenance. Chip-free (pure Python +
    ``jax.eval_shape``). The sixth sibling: memory, compute, the wire,
    the wire as lowered, the thread plane, and now TIME."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.dttperf import predict_step_time
    from tools.dttperf.scenarios import FLAGSHIP_BATCH, flagship_model

    if model_name not in FLAGSHIP_BATCH:
        raise SystemExit(f"--predict: unknown model {model_name!r}; "
                         f"available: {sorted(FLAGSHIP_BATCH)}")
    known = ("dp", "zero1", "zero3", "pp", "tp", "ep", "sp", "ps")
    if mode not in known:
        raise SystemExit(f"--predict: unknown mode {mode!r}; one of "
                         f"{', '.join(known)}")
    model_ways = 2 if mode in ("pp", "tp", "ep", "sp") else 1
    data_ways = max(1, d // model_ways)
    plan = dict(mode=mode, data_ways=data_ways, model_axis=model_ways,
                zero_level=int(mode[4:]) if mode.startswith("zero")
                else 0)
    if batch is None:
        batch = FLAGSHIP_BATCH[model_name] * data_ways
    pred = predict_step_time(plan, flagship_model(model_name), d,
                             global_batch=batch)

    print(f"predicted step time — model={model_name} mode={mode} D={d} "
          f"global_batch={pred['global_batch']} "
          f"hardware={pred['hardware']} (ceiling: spec peak, analytic "
          f"terms; DTP001 bands measured rates against this)")
    print(f"{'term':<14} {'seconds':>12}  source")
    for t in pred["terms"]:
        print(f"{t['term']:<14} {t['seconds']:>12.6f}  {t['source']}")
    us = pred["useful_fraction"]
    extra = f", pp useful fraction {us:.3f}" if us < 1.0 else ""
    print(f"\nstep = max(compute, exposed_comm) + host = "
          f"{pred['step_time_s'] * 1e3:.3f} ms ({pred['bound']}-bound"
          f"{extra})")
    print(f"flops/step {pred['flops_per_step']:,}; wire "
          f"{pred['comm_bytes_per_step']:,} B/step "
          f"({pred['comm_exposed_bytes_per_step']:,} exposed)")
    print(f"ceiling: {pred['examples_per_sec']:,.0f} examples/s "
          f"({pred['examples_per_sec_per_chip']:,.0f} per chip)")


def print_threads() -> None:
    """Print the discovered thread inventory — every concurrent entry
    point in the tree (Thread/Timer sites, threaded-server handler
    classes, excepthook/atexit/signal hooks, crash contexts) with its
    file:line, the shared ``self.*`` attributes its class touches, and
    the locks that guard them. The fifth sibling of
    --mem/--flops/--comm/--jaxpr: memory, compute, the wire, the wire
    as lowered, and now the HOST THREAD PLANE — enforced by
    ``python -m tools.dttsan`` (the concurrency analyzer whose
    inventory this prints; registry in tools/dttsan/registry.json)."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.dttsan import threads_table
    from tools.dttsan.__main__ import print_threads as _pt

    _pt(threads_table())


def print_faults() -> None:
    """List the fault-injection registry (the --fault_spec grammar's
    source of truth — utils/faults.INJECTION_POINTS)."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from distributed_tensorflow_tpu.utils.faults import describe_points

    print(describe_points())


def main(profile_dir: str, top_n: int = 25) -> None:
    rows = aggregate(xla_op_events(load_trace(profile_dir)))
    total_us = sum(r["us"] for r in rows)
    print(f"total device op time: {total_us / 1e3:.2f} ms "
          f"across {sum(r['calls'] for r in rows)} op executions")
    print(f"{'op':<52} {'calls':>6} {'ms':>9} {'share':>6} {'GB':>8}")
    for r in rows[:top_n]:
        print(f"{r['op'][:52]:<52} {r['calls']:>6} {r['us'] / 1e3:>9.2f} "
              f"{r['us'] / total_us:>6.1%} {r['bytes'] / 2**30:>8.2f}")
    rest = rows[top_n:]
    if rest:
        us = sum(r["us"] for r in rest)
        print(f"{'(other ' + str(len(rest)) + ' ops)':<52} "
              f"{sum(r['calls'] for r in rest):>6} {us / 1e3:>9.2f} "
              f"{us / total_us:>6.1%}")


if __name__ == "__main__":
    if sys.argv[1] == "--schedule":
        k, m = int(sys.argv[2]), int(sys.argv[3])
        rest = sys.argv[4:]
        sched = "auto"
        if rest and not rest[-1].isdigit():
            sched = rest[-1]
            rest = rest[:-1]
        v = int(rest[0]) if rest else 1
        print_schedule(k, m, v, sched)
    elif sys.argv[1] == "--faults":
        print_faults()
    elif sys.argv[1] == "--threads":
        print_threads()
    elif sys.argv[1] == "--flops":
        print_flops(sys.argv[2],
                    int(sys.argv[3]) if len(sys.argv) > 3 else 128)
    elif sys.argv[1] == "--jaxpr":
        rest = sys.argv[2:]
        mode = "dp"
        model_axis = 2
        batch = 128
        if "--mode" in rest:
            i = rest.index("--mode")
            mode = rest[i + 1]
            rest = rest[:i] + rest[i + 2:]
        if "--model_axis" in rest:
            i = rest.index("--model_axis")
            model_axis = int(rest[i + 1])
            rest = rest[:i] + rest[i + 2:]
        if "--batch" in rest:
            i = rest.index("--batch")
            batch = int(rest[i + 1])
            rest = rest[:i] + rest[i + 2:]
        print_jaxpr_inventory(rest[0],
                              int(rest[1]) if len(rest) > 1 else 8,
                              mode, model_axis, batch)
    elif sys.argv[1] == "--predict":
        rest = sys.argv[2:]
        mode = "dp"
        batch = None
        if "--mode" in rest:
            i = rest.index("--mode")
            mode = rest[i + 1]
            rest = rest[:i] + rest[i + 2:]
        if "--batch" in rest:
            i = rest.index("--batch")
            batch = int(rest[i + 1])
            rest = rest[:i] + rest[i + 2:]
        print_predict(rest[0], int(rest[1]) if len(rest) > 1 else 8,
                      mode, batch)
    elif sys.argv[1] == "--comm":
        rest = sys.argv[2:]
        model_axis = 2
        batch = 128
        zero_overlap = False
        bucket_mb = 4.0
        if "--model_axis" in rest:
            i = rest.index("--model_axis")
            model_axis = int(rest[i + 1])
            rest = rest[:i] + rest[i + 2:]
        if "--batch" in rest:
            i = rest.index("--batch")
            batch = int(rest[i + 1])
            rest = rest[:i] + rest[i + 2:]
        if "--bucket_mb" in rest:
            i = rest.index("--bucket_mb")
            bucket_mb = float(rest[i + 1])
            rest = rest[:i] + rest[i + 2:]
        if "--zero_overlap" in rest:
            rest.remove("--zero_overlap")
            zero_overlap = True
        print_comm(rest[0], int(rest[1]) if len(rest) > 1 else 8,
                   model_axis, batch, zero_overlap, bucket_mb)
    elif sys.argv[1] == "--mem":
        rest = sys.argv[2:]
        zero_level = None
        optimizer = "adam"
        if "--zero" in rest:
            i = rest.index("--zero")
            zero_level = int(rest[i + 1])
            rest = rest[:i] + rest[i + 2:]
        if "--optimizer" in rest:
            i = rest.index("--optimizer")
            optimizer = rest[i + 1]
            rest = rest[:i] + rest[i + 2:]
        print_mem(rest[0], int(rest[1]), zero_level, optimizer)
    else:
        main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
