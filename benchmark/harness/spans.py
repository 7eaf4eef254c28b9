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


def inside(spans: list[dict], t0: float, t1: float,
           thread: str = "MainThread") -> dict[str, float]:
    """Seconds of [t0, t1] that the thread spent in each of its top-level
    spans (``parent`` null), by name, and under ``no_span`` what none of
    them covers: what the host was doing while an interval ran long."""
    out, covered = {}, 0.0
    for s in spans:
        if s.get("parent") is not None or s.get("thread") != thread:
            continue
        overlap = min(t1, s["ts"] + s["dur_s"]) - max(t0, s["ts"])
        if overlap > 0:
            out[s["name"]] = out.get(s["name"], 0.0) + overlap
            covered += overlap
    out["no_span"] = max(0.0, t1 - t0 - covered)
    return out
