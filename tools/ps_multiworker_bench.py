"""Multi-worker async PS measurement: fan-in, cycle scaling, staleness.

The reference's deployment is N worker PROCESSES hammering the ps
(MNISTDist.py:94-95,188); this measures how this build's PS emulation
behaves as worker count grows — with real processes (r5: the r4
version used threads, which confounded per-worker rates with the GIL
and host compute contention; worker processes isolate what the ps
actually serializes). Compute runs on the CPU platform, by design and
stated here, not as a fallback: the object of measurement is the ps
fan-in, dedup table, and the mirror desync/resync protocol under
contention, not chip throughput — and an accelerator belongs to one
process at a time, so N worker processes on one host cannot each have
the chip. Each worker process owns a PSClient
(own sockets + client id) driving MirrorCycle in the documented
multi-worker degraded mode: every foreign push desyncs the mirror,
forcing a resync pull — the reference's staleness model.

Per N in {1, 2, 4, 8}: aggregate pushes/s, per-worker cycle rate, the
observed STALENESS distribution (per push: how many foreign pushes
landed since this worker's mirror state — ``new_step - my_step - 1``),
and the exactly-once check (global step total == N * cycles: no push
lost, none double-applied, under full contention). Prints one JSON
line per N.

Start protocol: workers print READY after connecting + initial sync,
the parent touches a go-file once all are ready, workers spin on it —
so the timed windows overlap maximally without shared-memory
primitives.

Usage: python tools/ps_multiworker_bench.py [cycles_per_worker]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

# runnable as `python tools/ps_multiworker_bench.py` from anywhere:
# sys.path[0] is tools/, the package root is one level up
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

BATCH = 64


def worker_main(widx: int, n_workers: int, address: str, cycles: int,
                gofile: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"  # by design: see the docstring
    import jax

    from distributed_tensorflow_tpu.data import read_data_sets
    from distributed_tensorflow_tpu.models import get_model
    from distributed_tensorflow_tpu.parallel.ps_emulation import (
        MirrorCycle,
        PSClient,
        assign_shards,
        flatten_params,
        make_grad_fn,
    )

    ds = read_data_sets("", dataset="mnist")
    model = get_model("mlp", hidden_units=100)
    template = model.init(jax.random.PRNGKey(0))
    assignment = assign_shards(list(flatten_params(template)), 1)
    grad_fn = make_grad_fn(model, keep_prob=1.0, devices=jax.devices()[:1])
    client = PSClient([address])
    data = ds.train.shard(widx, n_workers)
    cyc = MirrorCycle(client, grad_fn, template, assignment,
                      learning_rate=0.01, resync_steps=10**9)
    cyc.maybe_sync()
    rng = jax.random.PRNGKey(widx)
    print("READY", flush=True)
    while not os.path.exists(gofile):
        time.sleep(0.005)
    staleness: list[int] = []
    desyncs = 0
    t0 = time.perf_counter()
    for i in range(cycles):
        before = cyc.step
        cyc.run_cycle(data.next_batch(BATCH), jax.random.fold_in(rng, i))
        if cyc.step > before:  # a push happened this cycle
            staleness.append(cyc.step - before - 1)
        if cyc.needs_resync:
            desyncs += 1
            cyc.maybe_sync()
    cyc.drain()
    dt = time.perf_counter() - t0
    client.close()
    print(json.dumps({"widx": widx, "dt": dt, "staleness": staleness,
                      "desyncs": desyncs}), flush=True)


def _spawn_worker(widx: int, n: int, address: str, cycles: int,
                  gofile: str, errdir: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH", ""), _REPO_ROOT) if p)
    # stderr goes to a FILE, not a pipe: a crashing worker can dump
    # >64KB of logging+traceback, and an undrained stderr pipe would
    # block its write -> stdout never reaches EOF -> parent deadlocks
    err_path = os.path.join(errdir, f"worker{widx}.err")
    errf = open(err_path, "w")
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", str(widx),
         str(n), address, str(cycles), gofile],
        stdout=subprocess.PIPE, stderr=errf, text=True, env=env)
    p.err_path = err_path  # type: ignore[attr-defined]
    errf.close()  # the child holds the fd
    return p


def _err_tail(p, limit: int = 500) -> str:
    try:
        with open(p.err_path) as f:
            return f.read()[-limit:]
    except OSError:
        return "<no stderr captured>"


def main(cycles: int = 60):
    os.environ["JAX_PLATFORMS"] = "cpu"  # by design: see the docstring
    import jax
    import numpy as np

    from distributed_tensorflow_tpu.models import get_model
    from distributed_tensorflow_tpu.parallel.ps_emulation import (
        PSClient,
        PSServer,
        assign_shards,
        flatten_params,
    )

    model = get_model("mlp", hidden_units=100)
    flat = flatten_params(model.init(jax.random.PRNGKey(0)))

    for n_workers in (1, 2, 4, 8):
        server = PSServer(0, "127.0.0.1:0")
        server.start_background()
        init_client = PSClient([server.address])
        assignment = assign_shards(list(flat), 1)
        init_client.init_params(flat, assignment, optimizer="sgd",
                                learning_rate=0.01, num_workers=n_workers)
        tmp = tempfile.mkdtemp(prefix="psbench-")
        gofile = os.path.join(tmp, "go")
        procs = []
        try:
            import threading

            procs = [_spawn_worker(w, n_workers, server.address, cycles,
                                   gofile, tmp) for w in range(n_workers)]
            results = []
            errors = []
            for p in procs:
                # bound the READY wait: a worker wedged in init would
                # otherwise block this readline forever. The killer
                # makes readline return EOF ("") instead. A worker
                # dying here is a per-N error record, not an abort —
                # the remaining N still get measured.
                killer = threading.Timer(300.0, p.kill)
                killer.start()
                try:
                    while True:  # skip stray library chatter on stdout
                        line = p.stdout.readline()
                        if line == "":
                            errors.append(f"worker died/hung before "
                                          f"READY: {_err_tail(p)}")
                            break
                        if line.strip() == "READY":
                            break
                finally:
                    killer.cancel()
            if not errors:
                with open(gofile, "w"):
                    pass
                for p in procs:
                    try:
                        out, _ = p.communicate(timeout=600)
                    except subprocess.TimeoutExpired:
                        p.kill()
                        out, _ = p.communicate()
                        errors.append(f"worker timed out: {_err_tail(p)}")
                        continue
                    if p.returncode != 0:
                        errors.append(_err_tail(p))
                        continue
                    try:
                        results.append(
                            json.loads(out.strip().splitlines()[-1]))
                    except (ValueError, IndexError):
                        errors.append(f"worker emitted no result JSON "
                                      f"(stdout {out[-200:]!r}); "
                                      f"{_err_tail(p)}")
            if errors:
                print(json.dumps({"n_workers": n_workers,
                                  "errors": errors}), flush=True)
                continue

            total = server.dispatch({"op": "get_step"})["global_step"]
            st = np.array(sum((r["staleness"] for r in results), []))
            wall = max(r["dt"] for r in results)
            rec = {
                "n_workers": n_workers,
                "workers": "processes",
                "global_step_total": int(total),
                "pushes_expected": n_workers * cycles,
                "exactly_once": int(total) == n_workers * cycles,
                "aggregate_pushes_per_sec": round(total / wall, 2),
                "per_worker_cycles_per_sec": sorted(
                    round(cycles / r["dt"], 2) for r in results),
                "desyncs_total": int(sum(r["desyncs"] for r in results)),
                "staleness_mean": (round(float(st.mean()), 3)
                                   if len(st) else 0),
                "staleness_p95": (int(np.percentile(st, 95))
                                  if len(st) else 0),
                "staleness_max": int(st.max()) if len(st) else 0,
            }
            print(json.dumps(rec), flush=True)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            init_client.close()
            server.close()
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)  # go-file + .err files


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        worker_main(int(sys.argv[2]), int(sys.argv[3]), sys.argv[4],
                    int(sys.argv[5]), sys.argv[6])
    else:
        main(int(sys.argv[1]) if len(sys.argv) > 1 else 60)
