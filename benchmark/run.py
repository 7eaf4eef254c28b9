#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the main thread: the trainer's own entry
(``mnist_dist.main`` -> ``training.loop.train``) is given the flags of the
cell's configuration and mix and trains until the window has closed; the
window is read from the trainer's ``metrics.jsonl``; a SIGTERM at its close
takes the trainer's own preemption path (drain, final checkpoint). Then the
plain reference follows the first three steps from the seed and the
comparison decides ``correct``. The last line of standard output is the
result. See ``benchmark/README.md``.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import compare, manifest, spans, window  # noqa: E402

NO_DEVICE = 3
CKPT_SAMPLE = 6
WAIT_FOR_ROWS_S = 0.05


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class Watcher(threading.Thread):
    """Tails ``metrics.jsonl``: finds the window's first and last row as
    they are written, starts and stops the profiler in a traced run, and
    sends the process SIGTERM when the window has closed."""

    def __init__(self, path, seconds, open_step, probe, trace_dir, trace_rows):
        super().__init__(daemon=True, name="bench-watcher")
        self.path, self.seconds, self.open_step = path, seconds, open_step
        self.probe = probe
        self.trace_dir, self.trace_rows = trace_dir, trace_rows
        self.opened_at = self.sigterm_at = None
        self.trace_window = None
        self.cancel = threading.Event()

    def run(self):
        import jax

        tracing = False
        trace_t0 = None
        while not self.cancel.is_set():
            rows = window.read_rows(self.path)
            opened, closed = (None, None)
            if self.probe.done.is_set():
                opened, closed = window.find_window(rows, self.seconds,
                                                    self.open_step)
            if opened is not None and self.opened_at is None:
                self.opened_at = opened["time"]
                if self.trace_dir:
                    jax.profiler.start_trace(self.trace_dir)
                    tracing, trace_t0 = True, time.time()
                    # ties the trace's clock to the epoch of the spans
                    with jax.profiler.TraceAnnotation(
                            f"bench_epoch_mark:{time.time()!r}"):
                        time.sleep(0.001)
            if tracing:
                seen = [r for r in window.synced(rows)
                        if r["time"] > trace_t0]
                if len(seen) >= self.trace_rows or closed is not None:
                    t1 = time.time()
                    jax.profiler.stop_trace()
                    tracing = False
                    self.trace_window = (trace_t0, t1)
            if closed is not None:
                self.sigterm_at = time.time()
                os.kill(os.getpid(), signal.SIGTERM)
                return
            time.sleep(WAIT_FOR_ROWS_S)
        if tracing:
            jax.profiler.stop_trace()


def keep_records(logdir: str, dest: str):
    os.makedirs(dest, exist_ok=True)
    for dirpath, _, files in os.walk(logdir):
        for f in files:
            if f.endswith((".jsonl", ".xplane.pb")):
                shutil.copy(os.path.join(dirpath, f), os.path.join(dest, f))


def check_device(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if require_tpu and (found["platform"] != "tpu" or len(devices) != chips):
        log(f"this cell needs {chips} TPU chip(s) and measures on nothing "
            f"else; JAX found {found}")
        raise SystemExit(NO_DEVICE)
    return found


def memory_peak_bytes() -> tuple[int, dict]:
    """Peak bytes on the fullest chip, and that chip's whole reading. On
    this runtime ``peak_bytes_in_use`` counts live arrays only; what a
    running program takes for its temporaries is booked as reserved. The
    chip is at its fullest while the step runs, holding both."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats]
    fullest = max(range(len(stats)), key=lambda i: peaks[i])
    return int(peaks[fullest]), stats[fullest]


def state_names(state) -> dict:
    """path name -> leaf of the train state, named as the checkpoint does."""
    import jax

    paths, _ = jax.tree_util.tree_flatten_with_path(state)
    out = {}
    for path, leaf in paths:
        parts = [str(getattr(k, "name", getattr(k, "key", getattr(k, "idx", k))))
                 for k in path]
        out["/".join(parts)] = leaf
    return out


def checkpoint_mismatch(logdir: str, state, seed: int) -> tuple[int, str]:
    """Arrays of the newest checkpoint that differ from ``state``, of a
    sample drawn from the seed (with the step), and what was looked at."""
    import numpy as np

    live = state_names(state)
    step = int(live["step"])
    path = os.path.join(logdir, f"ckpt-{step}.npz")
    if not os.path.exists(path):
        return 1 + CKPT_SAMPLE, f"no {os.path.basename(path)} in the logdir"
    with np.load(path) as z:
        stored = [k for k in z.files if k in live and k != "step"]
        picked = random.Random(seed).sample(sorted(stored),
                                            min(CKPT_SAMPLE, len(stored)))
        bad = [k for k in picked
               if not np.array_equal(z[k], np.asarray(live[k]))]
        if int(z["step"]) != step:
            bad.append("step")
    return len(bad), (f"{os.path.basename(path)}: {len(picked)} of "
                      f"{len(stored)} arrays compared, differing: {bad}")


@functools.lru_cache(maxsize=2)
def _first_batches(family, seed, sizes, rows_per_shard, shards, prng):
    return family.first_batches(seed, 3, dict(sizes), rows_per_shard, shards,
                                prng)


def reference_numbers(cell, seed: int, precision="f32", keep_rows=None,
                      learning_rate=None, **kw):
    """The first three steps of the plain reference of the cell's family,
    for this cell and seed."""
    family = cell.family()
    prng = {"threefry": "threefry2x32"}.get(cell.config["trainer"]["prng"],
                                            cell.config["trainer"]["prng"])
    sizes = cell.sizes
    batches = _first_batches(family, seed, tuple(sorted(sizes.items())),
                             cell.mix["batch_per_chip"], cell.chips, prng)
    if learning_rate is None:
        learning_rate = cell.config["trainer"]["learning_rate"]
    return family.first_steps(
        seed, sizes, batches, learning_rate, config=cell.config, mix=cell.mix,
        precision=precision, keep_rows=keep_rows, prng=prng, **kw)


def main(argv=None, *, require_tpu: bool = True, root: str = manifest.ROOT,
         before_train=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default="", help="copy the run's records "
                    "(rows, spans, trace) into this directory; not used by "
                    "the driver")
    args = ap.parse_args(argv)

    cell = manifest.load_cell(args.workload, root)
    import mnist_dist  # defines the trainer's flags; fails where the repo is absent

    import jax

    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    from benchmark.harness.probe import FirstStepsProbe

    # the program's own placement: $JAX_COMPILATION_CACHE_DIR, else the
    # fixed .jax_cache/ of the checkout; every program, however small
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = check_device(cell.chips, require_tpu)
    log(f"device {device}; compile cache {cache_dir}")

    logdir = tempfile.mkdtemp(prefix="dtt-bench-")
    trace_dir = os.path.join(logdir, "trace") if args.trace else ""
    argv_trainer = manifest.trainer_argv(cell, args.seed, logdir)
    log("trainer flags:", " ".join(argv_trainer))
    mnist_dist.FLAGS._parse(argv_trainer)

    probe = FirstStepsProbe(cell.family().leaf_names).install()
    watcher = Watcher(os.path.join(logdir, "metrics.jsonl"), args.seconds,
                      cell.mix["display_step"], probe, trace_dir,
                      cell.mix.get("trace_rows", 2))
    if before_train is not None:
        before_train(probe)
    watcher.start()
    try:
        try:
            rc = mnist_dist.main([])
        finally:
            t_returned = time.time()
            watcher.cancel.set()
            watcher.join()
            probe.uninstall()
        if rc:
            log(f"the trainer returned {rc}")
            return int(rc)
        result = reduce_run(cell, args, device, logdir, trace_dir, probe,
                            watcher, t_returned)
    finally:
        if args.keep:
            keep_records(logdir, args.keep)
        shutil.rmtree(logdir, ignore_errors=True)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'NOT OK'} ({c['detail']})")
    log(f"correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


def reduce_run(cell, args, device, logdir, trace_dir, probe, watcher,
               t_returned) -> dict:
    rows = window.read_rows(os.path.join(logdir, "metrics.jsonl"))
    win = window.reduce_window(rows, args.seconds, cell.mix["display_step"],
                               cell.tokens_per_step, cell.chips)
    if watcher.sigterm_at is None:
        raise RuntimeError("the trainer ended before the window closed")
    span_rows = spans.read_spans(logdir)
    peak, stats = memory_peak_bytes()
    log(f"memory_stats of the fullest device: {stats}")
    setup_s = win["open"]["time"] - T_START
    run = {
        "cell": cell, "rows": rows, "window": win, "spans": span_rows,
        "peaks": manifest.peaks_for(device["kind"]) if device["platform"] == "tpu" else None,
        "setup_s": setup_s, "drain_s": t_returned - watcher.sigterm_at,
        "sigterm_at": watcher.sigterm_at, "trace": None,
    }
    log(f"window: steps {win['steps']} in {win['seconds']:.3f} s over "
        f"{win['rows']} rows; compiles inside it: {win['compiles_in_window']}; "
        f"compile_cache_hits at open: "
        f"{(win['scalars_open'] or {}).get('compile_cache_hits')}")
    slow = dict(win["slowest_interval"])
    slow["host_s"] = spans.inside(span_rows, slow["start"],
                                  slow["start"] + slow["seconds"])
    log(f"slowest interval: steps {slow['from_step']}..{slow['to_step']} in "
        f"{slow['seconds']:.3f} s, {slow['start'] - win['open']['time']:.1f} s "
        f"into the window; the host spent it in "
        + ", ".join(f"{n} {s:.3f}" for n, s in sorted(
            slow["host_s"].items(), key=lambda kv: -kv[1])))

    # 1. what the drain wrote, against the state the last step left
    mismatch, looked = checkpoint_mismatch(logdir, probe.last_state,
                                           args.seed)
    program = probe.result
    probe.release()
    gc.collect()

    # 2. the traced window
    device_out = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if args.trace:
        from benchmark.harness import trace as trace_mod

        run["trace"] = trace_mod.reduce_trace(trace_dir, span_rows)
        device_out["busy_s"] = run["trace"]["busy_s"]
        device_out["window_s"] = run["trace"]["window_s"]
        breakdown = run["trace"]["breakdown"]

    # 3. the reference, once the program's state is freed
    t0 = time.time()
    reference = reference_numbers(
        cell, args.seed, first_gradient_of_other=probe.first_gradient)
    log(f"reference: three steps in {time.time() - t0:.1f} s")
    numbers = compare.training_numbers(program, reference)
    numbers["ckpt_mismatch"] = (mismatch, looked)
    if win["compiles_in_window"]:
        log(f"COMPILED INSIDE THE WINDOW: {win['compiles_in_window']} programs")
    correct, checks = compare.judge(numbers, cell.limits())
    failed = sum(1 for x in win["losses"] if not x == x or abs(x) == float("inf"))
    correct = correct and failed == 0

    e2e = {"tokens_per_s_per_chip": win["tokens_per_s_per_chip"],
           "setup_s": setup_s}
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            value = cell.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": win["steps"],
              "failed": failed, "metrics": metrics, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"steps": win["steps"], "seconds": win["seconds"],
                        "rows": win["rows"],
                        "compiles_in_window": win["compiles_in_window"],
                        "step_ms_mean": win["step_ms_mean"],
                        "step_ms_slowest": win["step_ms_slowest"],
                        "slowest_interval": slow}
    result["checks"] = checks
    return result


if __name__ == "__main__":
    sys.exit(main())
