#!/usr/bin/env python
"""Render telemetry span files as a per-step text timeline, and export
Chrome-trace JSON.

Reads the JSONL the telemetry spine writes — ``<logdir>/spans-<host>.jsonl``
(raw span records) or ``<logdir>/flightrec-<host>.jsonl`` (a crash
postmortem: meta/scalars/note records are carried along, spans render) —
no jax, no framework import beyond utils/telemetry.

Accepts MULTIPLE files: each record is tagged with the host parsed from
its filename (``spans-worker-1.jsonl`` -> ``worker-1``), the timeline
shows the host column, and the Chrome-trace export gives every host its
own named track (one pid per host) — load a whole fleet's span files
and see all hosts on one clock. ``tools/fleet_report.py`` builds on the
same loaders to ALIGN the clocks and attribute stragglers.

    python tools/trace_view.py /tmp/train_logs/spans-worker-0.jsonl
    python tools/trace_view.py /tmp/train_logs/spans-*.jsonl
    python tools/trace_view.py spans.jsonl --last 50
    python tools/trace_view.py spans.jsonl --step 100 200   # step range
    python tools/trace_view.py spans-*.jsonl --chrome trace.json
        # then load trace.json in chrome://tracing or ui.perfetto.dev
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

# sys.path[0] is tools/ when run as a script; the package root is one up
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from distributed_tensorflow_tpu.utils.telemetry import chrome_trace  # noqa: E402

_HOST_RE = re.compile(r"^(?:spans|flightrec)-(.+)\.jsonl$")


def host_from_path(path: str) -> str:
    """``.../spans-worker-1.jsonl`` -> ``worker-1`` (filename convention
    of telemetry.configure); the bare filename stem otherwise."""
    name = os.path.basename(path)
    m = _HOST_RE.match(name)
    if m:
        return m.group(1)
    return os.path.splitext(name)[0]


def load_records(path: str, host: str | None = None) -> list[dict]:
    """Span records from a spans-*.jsonl or flightrec-*.jsonl file.
    Flight-recorder events are enveloped ``{"kind": ..., ...}``; only
    span events carry a timeline, the rest are dropped here (``--raw``
    in a pager shows them). ``host`` tags every record (defaults to the
    filename's host)."""
    host = host_from_path(path) if host is None else host
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = rec.get("kind")
            if kind is None and "name" in rec:  # raw span record
                span = rec
            elif kind == "span":  # flight-recorder envelope
                span = {k: v for k, v in rec.items()
                        if k not in ("kind", "t")}
                if "name" not in span:
                    continue
            else:
                continue
            span.setdefault("host", host)
            out.append(span)
    return out


def load_many(paths: list[str]) -> list[dict]:
    """All files' span records, host-tagged, time-sorted."""
    records: list[dict] = []
    for p in paths:
        records.extend(load_records(p))
    records.sort(key=lambda r: float(r.get("ts", 0.0)))
    return records


def fleet_chrome_trace(records: list[dict]) -> dict:
    """Chrome-trace JSON with ONE TRACK PER HOST: records are bucketed
    by their ``host`` tag, each host gets its own pid plus a
    ``process_name`` metadata event, so a fleet export renders as
    side-by-side per-host lanes instead of one interleaved soup."""
    hosts = sorted({r.get("host", "?") for r in records})
    pid_of = {h: i for i, h in enumerate(hosts)}
    tagged = [dict(r, pid=pid_of.get(r.get("host", "?"), 0))
              for r in records]
    out = chrome_trace(tagged)
    out["traceEvents"] = [
        {"ph": "M", "name": "process_name", "pid": pid_of[h],
         "args": {"name": h}} for h in hosts
    ] + out["traceEvents"]
    return out


def render_timeline(records: list[dict], out=None) -> None:
    """Per-step text timeline: wall-clock offset from the first span,
    duration, host (when several), thread, nesting by depth, step/attr
    tags."""
    out = out if out is not None else sys.stdout
    if not records:
        print("(no span records)", file=out)
        return
    t0 = min(float(r.get("ts", 0.0)) for r in records)
    records = sorted(records, key=lambda r: float(r.get("ts", 0.0)))
    multi_host = len({r.get("host") for r in records}) > 1
    last_step = object()
    core = ("name", "ts", "pc", "dur_s", "tid", "thread", "depth",
            "instant", "host", "id", "parent")
    for r in records:
        step = r.get("step")
        if step != last_step and step is not None:
            print(f"--- step {step} ---", file=out)
            last_step = step
        off = float(r.get("ts", 0.0)) - t0
        dur = float(r.get("dur_s", 0.0))
        extras = {k: v for k, v in r.items() if k not in core
                  and k != "step"}
        mark = "!" if r.get("instant") else " "
        host_col = (f"<{r.get('host', '?')}> " if multi_host else "")
        print(f"{off:12.6f}s {mark}{dur * 1e3:10.3f}ms "
              f"{host_col}[{r.get('thread', '?')}] "
              f"{'  ' * int(r.get('depth', 0))}{r.get('name', '?')}"
              f"{'  ' + str(extras) if extras else ''}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Render telemetry span JSONL as a text timeline / "
                    "Chrome trace (multiple spans-*.jsonl = one track "
                    "per host)")
    ap.add_argument("files", nargs="+",
                    help="spans-<host>.jsonl and/or "
                         "flightrec-<host>.jsonl (several = fleet view)")
    ap.add_argument("--last", type=int, default=0,
                    help="only the newest N spans")
    ap.add_argument("--step", type=int, nargs=2, metavar=("LO", "HI"),
                    default=None,
                    help="only spans whose step tag is in [LO, HI]")
    ap.add_argument("--chrome", metavar="OUT.json", default=None,
                    help="write Chrome-trace/Perfetto JSON and exit")
    args = ap.parse_args(argv)

    records = load_many(args.files)
    if args.step is not None:
        lo, hi = args.step
        records = [r for r in records
                   if isinstance(r.get("step"), int) and
                   lo <= r["step"] <= hi]
    if args.last:
        records = records[-args.last:]
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(fleet_chrome_trace(records), f)
        hosts = sorted({r.get("host", "?") for r in records})
        print(f"wrote {len(records)} spans from {len(hosts)} host(s) to "
              f"{args.chrome} (load in chrome://tracing or "
              f"https://ui.perfetto.dev)")
        return 0
    render_timeline(records)
    return 0


if __name__ == "__main__":
    sys.exit(main())
