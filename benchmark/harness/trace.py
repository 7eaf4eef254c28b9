"""From the profiler's ``.xplane.pb`` to the numbers the per-layer metrics read.

Read with ``jax.profiler.ProfileData`` alone. A device is a plane named
``/device:TPU:<i>``; its line ``XLA Ops`` holds one event for every operation
that ran (a ``while`` holds its body's operations inside its own interval),
its line ``XLA Modules`` one event for every program. The core runs its
operations one after another; what is in flight beside them (copies,
collectives between their start and their done) is on another line and is
not read. Busy time is the union of the operations' intervals; the traced window runs from the first
operation's start to the last one's end over all devices; an operation's own
time is its interval less what its children cover.

The host's clock: the watcher writes a ``bench_epoch_mark:<seconds>`` event
into the trace as it starts it, which ties the trace's nanoseconds to the
epoch seconds of the trainer's spans, so that an idle gap can be given to
the host span that covers it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

MARK = "bench_epoch_mark:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")
NAME_LENGTH = 120
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """The parts of merged intervals ``a`` that no interval of merged ``b``
    covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events) -> list[tuple[str, int]]:
    """(name, own ns) of every event of one line: its interval less its
    children's. ``events`` are (start, end, name)."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            ps, pe, pname, covered = stack.pop()
            out.append((pname, pe - ps - covered))
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, name, 0])
    while stack:
        ps, pe, pname, covered = stack.pop()
        out.append((pname, pe - ps - covered))
    return out


def short_name(hlo: str) -> str:
    """``%fusion.7 fusion bf16[8,2048]`` from the HLO text the trace gives
    as an operation's name: the name, the opcode and the result's shape
    without its layout."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:NAME_LENGTH]
    shape = re.sub(r"\{[^}]*\}", "", rest)
    opcode = re.search(r"\)?\s*([a-z][a-z\-]*)\(", shape)
    result = shape[:opcode.start(1)].strip() if opcode else ""
    return f"{head} {opcode.group(1) if opcode else ''} {result}"[:NAME_LENGTH]


def is_collective(name: str) -> bool:
    """By the opcode of a short name: ``all-reduce``, ``all-reduce-start``,
    ``all-reduce-done`` and their kin."""
    opcode = name.split(" ")[1] if " " in name else name
    return any(opcode == c or opcode in (f"{c}-start", f"{c}-done")
               for c in COLLECTIVES)


def read_planes(path: str) -> dict:
    """{'devices': {plane name: {'ops': [...], 'modules': [...]}},
    'mark': (trace ns, epoch s) or None}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, mark = {}, None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append((int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns),
                                    short_name(ev.name)))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        modules.append((int(ev.start_ns),
                                        int(ev.start_ns + ev.duration_ns),
                                        ev.name))
            if ops:
                devices[plane.name] = {"ops": ops, "modules": modules}
        elif mark is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK):
                        mark = (int(ev.start_ns),
                                float(ev.name[len(MARK):]))
                        break
                if mark:
                    break
    return {"devices": devices, "mark": mark}


def reduce_planes(planes: dict, host_spans=()) -> dict:
    devices = planes["devices"]
    if not devices:
        raise RuntimeError("the trace holds no operation on any TPU device")
    t0 = min(o[0] for d in devices.values() for o in d["ops"])
    t1 = max(o[1] for d in devices.values() for o in d["ops"])
    window_ns = t1 - t0
    busy, exposed = {}, {}
    for name, d in devices.items():
        all_ops = union((s, e) for s, e, _ in d["ops"])
        busy[name] = total(all_ops)
        # the core runs its operations one after another (a collective in
        # flight beside compute is on the line of asynchronous operations,
        # not here): whatever time it spends inside a collective operation,
        # starting it, running it or waiting for it to be done, it computes
        # nothing
        coll = [ns for n, ns in self_times(d["ops"]) if is_collective(n)]
        exposed[name] = sum(coll) if coll else None
    first = sorted(devices)[0]
    ops0 = devices[first]["ops"]
    # operations of one opcode and result shape are one group: the same
    # fusion in every block and in every turn of a loop
    own, count = defaultdict(int), defaultdict(int)
    for name, ns in self_times(ops0):
        group = name.partition(" ")[2] or name
        own[group] += ns
        count[group] += 1
    # the training chunk is the program that took most of the device's time
    by_module = defaultdict(list)
    for s, e, name in devices[first]["modules"]:
        by_module[name].append((s, e))
    steps, step_module, step_ns, other_ns = 0, None, 0, 0
    if by_module:
        step_module = max(by_module, key=lambda n: total(by_module[n]))
        # a program cut by an edge of the trace is no whole step
        whole = [(s, e) for s, e in by_module[step_module]
                 if s > t0 and e < t1]
        steps, step_ns = len(whole), total(whole)
        other_ns = sum(total(v) for n, v in by_module.items()
                       if n != step_module)
    gaps = subtract([(t0, t1)], union((s, e) for s, e, _ in ops0))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    exposed_vals = [v for v in exposed.values() if v is not None]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "busy_s_worst": max(busy.values()) / 1e9,
        "busy_s_least": min(busy.values()) / 1e9,
        "steps": steps, "step_module": step_module,
        "step_s": step_ns / 1e9, "other_programs_s": other_ns / 1e9,
        "other_programs": sorted(n for n in by_module if n != step_module),
        "collective_exposed_s": (max(exposed_vals) / 1e9
                                 if exposed_vals else None),
        "breakdown": {
            "device_ops": [[f"{n} x{count[n]}", ns / 1e9] for n, ns in
                           sorted(own.items(), key=lambda x: -x[1])[:TOP]],
            "idle_gaps": [[host_doing(g, planes["mark"], host_spans),
                           (g[1] - g[0]) / 1e9] for g in gaps],
        },
    }


def host_doing(gap, mark, host_spans) -> str:
    """Name of the trainer's host span that covers most of an idle gap."""
    if mark is None:
        return "unknown"
    to_epoch = lambda ns: mark[1] + (ns - mark[0]) / 1e9
    g0, g1 = to_epoch(gap[0]), to_epoch(gap[1])
    best, best_cover = "none", 0.0
    for s in host_spans:
        cover = min(g1, s["ts"] + s["dur_s"]) - max(g0, s["ts"])
        if cover > best_cover:
            best, best_cover = s["name"], cover
    return best


def reduce_trace(trace_dir: str, host_spans=()) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir)), host_spans)
