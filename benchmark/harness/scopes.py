"""The device's time by named scope: which block of the program each
operation of the trace belongs to.

The program names its blocks with ``jax.named_scope`` (the scope catalog,
``distributed_tensorflow_tpu.utils.telemetry.SCOPES``, imported here and not
repeated). XLA keeps the name in every operation's ``op_name`` path, through
``jvp(...)``, ``transpose(jvp(...))`` and ``checkpoint/rematted_computation``,
and the profiler writes that path into the **metadata** of the operation's
events (the stat ``tf_op``: ``<op_name>:<op type>``), beside ``hlo_category``,
``flops`` and ``bytes_accessed``. ``jax.profiler.ProfileData`` shows only an
event's own three stats, so this file reads the ``.xplane.pb`` itself.

It does so with a wire-format reader of its own (varints and
length-delimited fields, the ten field numbers below) and not with
``tensorflow.tsl.profiler.protobuf.xplane_pb2``: importing TensorFlow took
8.4 s and 830 MB here, in the process that holds the chip and is about to run
the reference. The field numbers were checked against that module's
descriptors once (PR 25), and ``tests/benchmark/test_scopes.py`` holds the
reader to ``ProfileData`` on a trace recorded on a v5e.

An operation's own time is its interval less its children's
(``trace.self_times``, imported, as a ``while`` holds its body's operations).
It belongs to the innermost catalogued scope of its path. A fusion carries
the metadata of one of its instructions, so at the edge between two blocks
the attribution is approximate; what lies under no scope at all is reported
as ``unscoped``, which is the guard of the whole reading.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
import struct
import sys
from collections import defaultdict

from benchmark.harness import trace

UNSCOPED = "unscoped"
REMAT = "rematted_computation"
KEPT_STATS = ("tf_op", "hlo_category", "flops", "bytes_accessed")
# a path element that wraps a scope in a transform: jvp(mlp),
# transpose(jvp(mlp)); jit(f) and pjit(f) name a function, not a scope
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_FUNCTIONS = ("jit", "pjit")


# ---------------------------------------------------------------- the wire

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start=0, end=None):
    """(field number, wire type, value) of one message: an int for a
    varint, (start, end) into ``buf`` for a length-delimited field, the raw
    8 or 4 bytes for a fixed one."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = (i, i + size)
            i += size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield number, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names):
    """(stat name, value) of one XStat; a ``ref_value`` is the name of
    another stat's metadata, used for strings that repeat."""
    name = value = None
    for number, wire, v in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = _text(buf, v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf, span):
    key = value = None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def read_device_planes(path: str) -> dict:
    """{plane name: {'ops': [(start ns, end ns, metadata id)], 'modules':
    [(start ns, end ns, program name)], 'meta': {metadata id: {'name',
    'op_name', 'hlo_category', 'flops', 'bytes_accessed'}}}} of every
    ``/device:TPU:<i>`` plane: its operations and the runs of its programs
    (the line ``XLA Modules``). Times are what ``harness/trace.py`` gets
    from ``ProfileData``, which cuts an offset and a duration to whole
    nanoseconds each."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = {}
    for number, _, span in _fields(buf):
        if number != 1:
            continue
        top = list(_fields(buf, *span))
        name = next((_text(buf, v) for n, _, v in top if n == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for n, _, v in top:
            if n == 5:
                key, value = _map_entry(buf, v)
                for m, _, w in _fields(buf, *value):
                    if m == 2:
                        stat_names[key] = _text(buf, w)
        ops, runs = [], []
        for n, _, v in top:
            if n != 3:
                continue
            line = list(_fields(buf, *v))
            line_name = next((_text(buf, w) for m, _, w in line if m == 2), "")
            if line_name not in (trace.OPS_LINE, trace.MODULES_LINE):
                continue
            events = ops if line_name == trace.OPS_LINE else runs
            line_ns = next((_signed(w) for m, _, w in line if m == 3), 0)
            for m, _, w in line:
                if m != 4:
                    continue
                meta_id = offset_ps = duration_ps = 0
                for k, wire, x in _fields(buf, *w):
                    if wire != 0:
                        continue
                    if k == 1:
                        meta_id = x
                    elif k == 2:
                        offset_ps = _signed(x)
                    elif k == 3:
                        duration_ps = _signed(x)
                start = line_ns + offset_ps // 1000
                events.append((start, start + duration_ps // 1000, meta_id))
        if not ops:
            continue
        used = {e[2] for e in ops + runs}
        meta = {}
        for n, _, v in top:
            if n != 4:
                continue
            key, value = _map_entry(buf, v)
            if key not in used:
                continue
            entry = {"name": "", "op_name": "", "hlo_category": "",
                     "flops": 0, "bytes_accessed": 0}
            for m, _, w in _fields(buf, *value):
                if m == 2:
                    entry["name"] = _text(buf, w)
                elif m == 5:
                    stat, x = _stat(buf, w, stat_names)
                    if stat == "tf_op":
                        # "<op_name>:<op type>"; the type is empty in JAX
                        entry["op_name"] = str(x).rpartition(":")[0] or str(x)
                    elif stat in KEPT_STATS and x is not None:
                        entry[stat] = x
            meta[key] = entry
        modules = [(s, e, meta[k]["name"]) for s, e, k in runs]
        for k in {k for _, _, k in runs}:
            del meta[k]
        planes[name] = {"ops": ops, "modules": modules, "meta": meta}
    return planes


# -------------------------------------------------------------- the scopes

def catalog() -> tuple:
    """The program's scope catalog, or () where the program has none (a
    commit from before the scopes: every scope metric is then left out)."""
    try:
        from distributed_tensorflow_tpu.utils.telemetry import SCOPES
    except ImportError:
        return ()
    return tuple(SCOPES)


def path_elements(op_name: str) -> list[str]:
    """The elements of an ``op_name`` path with the transform wrappers
    taken off: ``transpose(jvp(mlp))`` is ``mlp``; ``jit(f)`` stays."""
    out = []
    for element in op_name.split("/"):
        while True:
            m = _WRAPPED.match(element)
            if not m or m.group(1) in _FUNCTIONS:
                break
            element = m.group(2)
        out.append(element)
    return out


def scope_of(op_name: str, scopes: tuple) -> str:
    """The innermost catalogued scope of a path, else ``unscoped``."""
    for element in reversed(path_elements(op_name)):
        if element in scopes:
            return element
    return UNSCOPED


def whole_runs(plane: dict) -> list[tuple[int, int, str]]:
    """(start, end, program) of the programs' runs that lie whole between
    the plane's first operation and its last, in time order: a run cut by
    an edge of the trace holds only part of its operations."""
    t0 = min(s for s, _, _ in plane["ops"])
    t1 = max(e for _, e, _ in plane["ops"])
    return sorted(r for r in plane["modules"] if r[0] > t0 and r[1] < t1)


def reduce_device(plane: dict, scopes: tuple) -> dict:
    """One device: {'busy_ns', 'scopes': {scope: {'own_ns', 'flops',
    'bytes_accessed', 'by_category': {hlo category: own ns}, 'by_run':
    {program: [own ns in each of its whole runs]}}}, 'remat_ns',
    'has_remat'}. ``flops`` and ``bytes_accessed`` are summed over the
    events of operations that hold no other (a ``while`` or a call repeats
    its body's). ``by_run`` gives an operation to the whole run
    (``whole_runs``) it starts in, and has an entry for every scope that
    ran in one; what runs in a cut program is in ``own_ns`` alone."""
    meta = plane["meta"]
    runs = whole_runs(plane)
    run_starts = [r[0] for r in runs]
    runs_of = defaultdict(list)
    for i, (_, _, program) in enumerate(runs):
        runs_of[program].append(i)
    per_scope = {}
    remat_ns, has_remat = 0, False
    which = {k: scope_of(m["op_name"], scopes) for k, m in meta.items()}
    remat = {k: REMAT in path_elements(m["op_name"])
             for k, m in meta.items()}
    holders = {"while", "conditional", "call"}
    nameless = {"hlo_category": "", "flops": 0, "bytes_accessed": 0}
    busy = 0
    for (meta_id, start), ns in trace.self_times(
            (s, e, (k, s)) for s, e, k in plane["ops"]):
        m = meta.get(meta_id, nameless)
        scope = which.get(meta_id, UNSCOPED)
        entry = per_scope.get(scope)
        if entry is None:
            entry = per_scope[scope] = {
                "own_ns": 0, "flops": 0, "bytes_accessed": 0,
                "by_category": defaultdict(int),
                "by_run": defaultdict(lambda: defaultdict(int))}
        entry["own_ns"] += ns
        i = bisect.bisect_right(run_starts, start) - 1
        if i >= 0 and start < runs[i][1]:
            entry["by_run"][runs[i][2]][i] += ns
        entry["by_category"][m["hlo_category"]] += ns
        if m["hlo_category"] not in holders:
            entry["flops"] += m["flops"]
            entry["bytes_accessed"] += m["bytes_accessed"]
        busy += ns
        if remat.get(meta_id):
            remat_ns += ns
            has_remat = True
    for entry in per_scope.values():
        entry["by_category"] = dict(entry["by_category"])
        # every whole run of a program the scope ran in, 0 where it did not
        entry["by_run"] = {program: [by_index[i] for i in runs_of[program]]
                           for program, by_index in entry["by_run"].items()}
    return {"busy_ns": busy, "scopes": per_scope, "remat_ns": remat_ns,
            "has_remat": has_remat}


@functools.lru_cache(maxsize=2)
def reduce_file(path: str, scopes: tuple, window_s: float = 0.0) -> dict:
    """{plane name: ``reduce_device``} of one ``.xplane.pb``; kept, as six
    metrics read the same file. With the traced window's length, the first
    reading also writes the shares to stderr (``log_shares``)."""
    devices = {name: reduce_device(plane, scopes)
               for name, plane in read_device_planes(path).items()}
    if window_s:
        log_shares(devices, scopes, window_s)
    return devices


def log_shares(devices: dict, scopes: tuple, window_s: float) -> None:
    """One line a device on stderr: every scope, ``unscoped`` and idle, in
    percent of the traced window; they sum to 100."""
    for name in sorted(devices):
        d = devices[name]
        own = {s: d["scopes"].get(s, {"own_ns": 0})["own_ns"]
               for s in scopes + (UNSCOPED,)}
        parts = [f"{s} {100e-9 * ns / window_s:.2f}" for s, ns in own.items()]
        idle = 100.0 * (1.0 - d["busy_ns"] / 1e9 / window_s)
        print(f"scopes {name}: " + ", ".join(parts)
              + f", idle {idle:.2f} (% of {window_s:.3f} s; remat "
              f"{100e-9 * d['remat_ns'] / window_s:.2f})",
              file=sys.stderr, flush=True)


# ----------------------------------------------------------------- the run

def logdir_of(run) -> str | None:
    """The trainer's ``--logdir``. ``run`` carries no path: the trainer ran
    in this process under ``mnist_dist.FLAGS``, which still hold it (a
    hand-built ``run`` may say ``logdir`` itself)."""
    if "logdir" in run:
        return run["logdir"]
    flags = getattr(sys.modules.get("mnist_dist"), "FLAGS", None)
    return getattr(flags, "logdir", None)


def of_run(run) -> dict | None:
    """``reduce_file`` of the traced window of ``run``; None where there is
    no trace, no catalog, or no file."""
    scopes = catalog()
    logdir = logdir_of(run)
    if not run.get("trace") or not scopes or not logdir:
        return None
    try:
        path = trace.find_xplane(os.path.join(logdir, "trace"))
    except FileNotFoundError:
        return None
    return reduce_file(path, scopes, run["trace"]["window_s"]) or None


def worst_ns(devices: dict, pick) -> int:
    """The largest reading over the devices: ``pick(device) -> ns``."""
    return max(pick(d) for d in devices.values())


def scope_pct(run, scope: str) -> float | None:
    """Own time of ``scope`` on the worst device over the traced window,
    in percent; None where nothing is there to read."""
    devices = of_run(run)
    if not devices or not run["trace"]["window_s"]:
        return None
    if scope != UNSCOPED and scope not in catalog():
        return None
    ns = worst_ns(devices, lambda d: d["scopes"].get(
        scope, {"own_ns": 0})["own_ns"])
    return 100.0 * ns / 1e9 / run["trace"]["window_s"]
