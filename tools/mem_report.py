#!/usr/bin/env python
"""Render a run's memory story as ONE table: the live hbm_* scalars a
training run (or serving replica) logged against the static analytic
budget for its configuration — the resource plane's offline half
(utils/resources; the live half is the MemoryMeter emitting into
metrics.jsonl at the display cadence).

Reads ``metrics.jsonl`` (and ``serve_metrics.jsonl``) under a logdir for
the ``hbm_in_use_bytes`` / ``hbm_peak_bytes`` / ``hbm_headroom_pct`` /
``compiles_total`` / ``comm_bytes_per_step`` series — last value + peak
over the run — and prints them next to the analytic per-chip budget
(``resource_budget``: per-leaf params/opt with the mode's sharding rule,
plus the activation estimate) with the live-vs-analytic ratio the bench
asserts on. The scalar half is pure stdlib; the analytic half costs one
``jax.eval_shape`` (no chip, no compute).

Usage:
    python tools/mem_report.py LOGDIR
    python tools/mem_report.py LOGDIR --model deep_cnn --optimizer adam \
        --batch 128 [--d 8] [--zero 1] [--model_axis 2] [--pipeline]
    python tools/mem_report.py LOGDIR --no-analytic   # scalars only
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

HBM_KEYS = ("hbm_in_use_bytes", "hbm_peak_bytes", "hbm_headroom_pct",
            "hbm_analytic_bytes", "compiles_total", "compile_time_s",
            "compile_cache_hits", "recompiles_total", "comm_bytes_per_step")


def _fmt_bytes(n) -> str:
    """None-tolerant wrapper over the one byte formatter
    (tools/trace_ops — this module already imports from it)."""
    if n is None:
        return "-"
    from tools.trace_ops import _fmt_bytes as fmt

    return fmt(int(n))


def load_scalar_series(logdir: str) -> dict[str, list]:
    """{key: [(step, value), ...]} for the resource-plane keys, merged
    over every metrics JSONL in the logdir (trainer + serving files)."""
    series: dict[str, list] = {k: [] for k in HBM_KEYS}
    for path in sorted(glob.glob(os.path.join(logdir, "*metrics*.jsonl"))):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    step = rec.get("step", 0)
                    for k in HBM_KEYS:
                        # serving prefixes its scalars per route
                        # (serve_predict_hbm_in_use_bytes); match both
                        for rk, v in rec.items():
                            if (rk == k or rk.endswith(f"_{k}")) \
                                    and isinstance(v, (int, float)):
                                series[k].append((step, float(v)))
        except OSError:
            continue
    return series


def print_scalars(series: dict[str, list], out=None) -> dict:
    out = out if out is not None else sys.stdout
    print(f"{'scalar':<24} {'last':>14} {'peak':>14} {'samples':>8}",
          file=out)
    summary = {}
    for k in HBM_KEYS:
        vals = series.get(k) or []
        if not vals:
            print(f"{k:<24} {'-':>14} {'-':>14} {0:>8}", file=out)
            continue
        last = vals[-1][1]
        peak = max(v for _s, v in vals)
        summary[k] = {"last": last, "peak": peak, "n": len(vals)}
        byteish = k.endswith("_bytes") or k == "comm_bytes_per_step"
        fmt = _fmt_bytes if byteish else (lambda v: f"{v:g}")
        print(f"{k:<24} {fmt(last):>14} {fmt(peak):>14} "
              f"{len(vals):>8}", file=out)
    return summary


def print_analytic(model_name: str, optimizer: str, batch: int, d: int,
                   zero: int, model_axis: int, pipeline: bool,
                   live_peak: float | None, out=None) -> None:
    out = out if out is not None else sys.stdout
    from distributed_tensorflow_tpu.models import get_model
    from distributed_tensorflow_tpu.training import get_optimizer
    from distributed_tensorflow_tpu.utils.resources import resource_budget
    from tools.trace_ops import _MEM_MODELS

    if model_name not in _MEM_MODELS:
        raise SystemExit(f"unknown model {model_name!r}; available: "
                         f"{sorted(_MEM_MODELS)}")
    mode = (f"zero{zero}" if zero else
            "pp" if pipeline else
            "tp" if model_axis > 1 else "dp")
    model = get_model(model_name, **_MEM_MODELS[model_name])
    budget = resource_budget(
        model, get_optimizer(optimizer, 1e-3), batch, mode=mode,
        data_ways=max(1, d // max(1, model_axis)), model_axis=model_axis,
        zero_level=zero)
    pc = budget["per_chip"]
    print(f"\nanalytic per-chip budget — model={model_name} "
          f"optimizer={optimizer} batch={batch} mode={mode} d={d} "
          f"(jax.eval_shape; activations are an estimate)", file=out)
    print(f"{'column':<14} {'bytes/chip':>14}", file=out)
    for k in ("params", "opt", "grads", "activations"):
        print(f"{k:<14} {_fmt_bytes(pc[k]):>14}", file=out)
    print(f"{'state total':<14} "
          f"{_fmt_bytes(budget['per_chip_state_bytes']):>14}", file=out)
    top = sorted(budget["rows"], key=lambda r: -r["per_chip_bytes"])[:8]
    print(f"\nlargest leaves (per chip):", file=out)
    for r in top:
        print(f"  {r['kind']:<6} {r['leaf'][:44]:<44} "
              f"{_fmt_bytes(r['per_chip_bytes']):>12}"
              f"{'  (1/' + str(r['shard']) + ')' if r['shard'] > 1 else ''}",
              file=out)
    if live_peak:
        ratio = live_peak / max(budget["per_chip_state_bytes"], 1)
        print(f"\nlive peak vs analytic state: "
              f"{_fmt_bytes(live_peak)} / "
              f"{_fmt_bytes(budget['per_chip_state_bytes'])} = "
              f"{ratio:.2f}x  (>1 expected transiently — grads, "
              f"staging, --device_data's resident split; >> analytic "
              f"total means an unaccounted consumer)", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="One-table memory report: a run's live hbm_* "
                    "scalars next to the analytic budget")
    ap.add_argument("logdir")
    ap.add_argument("--model", default="deep_cnn")
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--d", type=int, default=1,
                    help="total chips (data x model ways)")
    ap.add_argument("--zero", type=int, default=0)
    ap.add_argument("--model_axis", type=int, default=1)
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--no-analytic", action="store_true",
                    help="scalars only (no jax import)")
    args = ap.parse_args(argv)

    if not os.path.isdir(args.logdir):
        print(f"no such logdir: {args.logdir}", file=sys.stderr)
        return 2
    series = load_scalar_series(args.logdir)
    print(f"memory report — {args.logdir}")
    summary = print_scalars(series)
    if not any(series[k] for k in HBM_KEYS):
        print("\n(no resource-plane scalars found — was the run pre-r13, "
              "or --telemetry=false / --hbm_sample_every=0?)")
    if not args.no_analytic:
        live_peak = summary.get("hbm_peak_bytes", {}).get("peak")
        print_analytic(args.model, args.optimizer, args.batch, args.d,
                       args.zero, args.model_axis, args.pipeline,
                       live_peak)
    return 0


if __name__ == "__main__":
    sys.exit(main())
