"""Milliseconds of the host-only work of one display inside the window, the
worst display: its ``display_stage`` span (drawing and staging the eval
batch) plus its ``display_log`` span (sentinel, both rows, both flushes).
Through ``display_log`` the device's queue is empty: each of its
milliseconds is a millisecond of idle device. ``display_stage`` runs while
the steps enqueued before the display still compute and costs the device
nothing unless it outlasts them. A stall of the host at a display falls in
one of the two; which one, and at which step, goes to stderr."""

import sys
from collections import defaultdict

from benchmark.harness import spans

PARTS = ("display_stage", "display_log")


def read(run):
    win = run["window"]
    by_step = defaultdict(dict)
    for name in PARTS:
        for s in spans.named(run["spans"], name, win["open"]["time"],
                             win["close"]["time"]):
            by_step[s.get("step")][name] = s["dur_s"]
    whole = {step: parts for step, parts in by_step.items()
             if len(parts) == len(PARTS)}
    if not whole:
        return None
    step, parts = max(whole.items(), key=lambda kv: sum(kv[1].values()))
    longer = max(PARTS, key=lambda n: parts[n])
    print(f"display_host_ms_worst: step {step}, "
          + ", ".join(f"{n} {1e3 * parts[n]:.3f} ms" for n in PARTS)
          + f"; the longer is {longer}", file=sys.stderr, flush=True)
    return 1e3 * sum(parts.values())
