"""The trainer's host spans (``<logdir>/spans-worker-<i>.jsonl``): one JSON
object a line with ``name``, ``ts`` (start, seconds since the epoch) and
``dur_s``. They are host intervals: ``device_chunk`` times the enqueue, not
the device, and is never read as device time."""

from __future__ import annotations

import glob
import json
import os


def read_spans(logdir: str) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(logdir, "spans-worker-*.jsonl"))):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "ts" in rec and "dur_s" in rec and not rec.get("instant"):
                    out.append(rec)
    return sorted(out, key=lambda r: r["ts"])


def named(spans: list[dict], name: str, t0: float = float("-inf"),
          t1: float = float("inf")) -> list[dict]:
    """Spans of that name that start inside [t0, t1]."""
    return [s for s in spans if s["name"] == name and t0 <= s["ts"] <= t1]
