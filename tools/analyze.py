"""``python -m tools.analyze`` — the one-command static-analysis gate:
dttlint (AST invariants) + dttcheck (jaxpr proofs) + dttsan (host-plane
concurrency) + dttperf (performance contracts), one merged exit code.

The four analyzers prove four layers of the same tree — what the
source SAYS (dttlint, rules DTT001-DTT011), what the compiler LOWERS
(dttcheck, passes DTC001-DTC004), what the host THREADS do (dttsan,
passes SAN001-SAN004), and what the program COSTS in time (dttperf,
passes DTP000-DTP003: predicted step time per canonical cell banded
against the measured bench records) — and they share one suppression
discipline (``tools/_analysis_common``: baseline by stable key,
mandatory reasons, stale entries fail loudly). This runner is the
verify-pipeline entry: exit 0 only when ALL FOUR are clean, ``--json``
merges the four reports into one object keyed by analyzer.

dttcheck needs an 8-device mesh that must exist BEFORE jax initializes;
like bench's jaxprcheck_phase it runs in a subprocess with a forced CPU
mesh (started first, collected last — the other three run meanwhile),
so this command is chip-free end to end. dttperf is chip-free by
construction (pure Python + ``jax.eval_shape``). The acceptance budget
is < 45 s for all four (DTP003 budget ``analyze_umbrella_wall_s``).

Usage: python -m tools.analyze [--json] [--skip dttcheck] ...
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools._analysis_common import REPO_ROOT  # noqa: E402

ANALYZERS = ("dttlint", "dttcheck", "dttsan", "dttperf")


def _run_dttlint() -> dict:
    from tools.dttlint import run_lint

    return run_lint().to_json()


def _run_dttsan() -> dict:
    from tools.dttsan import run_san

    return run_san().to_json()


def _run_dttperf() -> dict:
    """In-process like dttlint/dttsan: predictions are pure Python +
    ``jax.eval_shape`` — no mesh, no devices, so no subprocess."""
    from tools.dttperf import run_perf

    return run_perf().to_json()


def _start_dttcheck() -> subprocess.Popen:
    """Subprocess with its own forced 8-device CPU mesh (the bench
    jaxprcheck_phase pattern): this process's jax may already be bound
    to real chips, and the verifier's mesh must exist before jax
    initializes. ``JAX_PLATFORMS=cpu`` keeps the child off the TPU
    library altogether (tests/test_chip_smoke.py), so it cannot contend
    for a chip its parent holds. Started FIRST and collected last: it
    is the longest of the four and shares nothing with the others."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    return subprocess.Popen(
        [sys.executable, "-m", "tools.dttcheck", "--json"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO_ROOT, env=env)


def _finish_dttcheck(proc: subprocess.Popen) -> dict:
    try:
        out, err = proc.communicate(timeout=300)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return {"ok": False, "error": "dttcheck subprocess: no end "
                                      "after 300s"}
    try:
        return json.loads(out.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"ok": False,
                "error": f"dttcheck subprocess failed (rc={proc.returncode}): "
                         f"{err.strip()[-400:]}"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.analyze",
        description="run dttlint + dttcheck + dttsan + dttperf with "
                    "one merged exit code")
    ap.add_argument("--json", action="store_true",
                    help="emit one merged machine-readable JSON object")
    ap.add_argument("--skip", action="append", default=[],
                    choices=ANALYZERS,
                    help="skip one analyzer (repeatable; bring-up "
                         "ergonomics)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    check = (_start_dttcheck() if "dttcheck" not in args.skip else None)
    runners = {"dttlint": _run_dttlint,
               "dttcheck": lambda: _finish_dttcheck(check),
               "dttsan": _run_dttsan, "dttperf": _run_dttperf}
    merged: dict = {}
    ok = True
    # the in-process three first, dttcheck's wait last
    for name in ("dttlint", "dttsan", "dttperf", "dttcheck"):
        if name in args.skip:
            continue
        t0 = t_start if name == "dttcheck" else time.perf_counter()
        try:
            res = runners[name]()
        except Exception as e:  # a crashed analyzer is a failed gate
            res = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        res["time_s"] = round(time.perf_counter() - t0, 3)
        merged[name] = res
        ok = ok and bool(res.get("ok"))
        if not args.json:
            n_find = len(res.get("findings", []))
            n_base = len(res.get("baselined", []))
            n_stale = len(res.get("stale_suppressions", []))
            status = "clean" if res.get("ok") else "FAILED"
            extra = (f" ({res['error'][:120]})" if "error" in res
                     else "")
            print(f"{name:8} {status:7} {n_find} finding(s), {n_base} "
                  f"baselined, {n_stale} stale — {res['time_s']}s"
                  f"{extra}")
    merged["ok"] = ok
    if args.json:
        print(json.dumps(merged))
    else:
        print(f"analyze: {'ALL CLEAN' if ok else 'GATE FAILED'} "
              f"({', '.join(n for n in ANALYZERS if n not in args.skip)})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
