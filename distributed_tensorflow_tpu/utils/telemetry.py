"""Telemetry spine: span tracing, step-time breakdown, hang watchdog,
crash flight recorder.

The reference's whole observability story is a cadenced print
(``MNISTDist.py:183-186``); the repro has outgrown it by five subsystems
but until now could not answer "where did this step's milliseconds go?",
"why did the 8-device run hang?", or "what happened in the seconds
before the chief crashed?". This module is the always-on answer; the
deep-dive paths (``--profile_dir`` / ``ServeTraceCapture``) stay what
they are — one-shot investigation artifacts.

Four pieces, one shared ring of recent events:

- **Span tracing** — ``trace_span("ckpt_write", step=...)`` is a
  thread-safe context manager; completed spans land in a fixed-size
  ring (always, ~1-2 µs each — bench asserts < 5 µs) and, when a logdir
  is configured, batch-flush to ``<logdir>/spans-<host>.jsonl``.
  Every record has an ``id`` and the ``parent`` that was open on its
  thread (self time = duration less the children's), the file starts
  with a header line (run id, pid, one epoch/``perf_counter`` pair),
  and ``set_annotator`` mirrors spans into a second tracer (the loop's
  ``jax.profiler.TraceAnnotation``: one clock with the device trace).
  ``chrome_trace`` converts any record set to Chrome-trace/Perfetto
  JSON (``tools/trace_view.py`` is the CLI). ``SCOPES`` is the catalog
  of the compiled programs' ``jax.named_scope`` names.
- **Step-time breakdown** — ``StepTimer`` accumulates host_wait /
  dispatch / device seconds per display window; the training loops emit
  the per-step means as ``step_host_wait_s`` / ``step_dispatch_s`` /
  ``step_device_s`` scalars next to the throughput numbers. Device time
  comes from the EXISTING ``block_until_ready`` calls at the collective
  sync cadence — no new sync points.
- **Hang watchdog** — ``--watchdog_s N`` arms a daemon thread around
  every device dispatch and collective (``armed(...)``); on expiry it
  dumps all-thread stacks (faulthandler), the last K spans, and the
  stalled operation's context, then optionally aborts
  (``--watchdog_abort``). Turns the two known deadlock classes
  (XLA:CPU collective rendezvous interleave, gloo preamble abort — see
  utils/profiling.collective_sync_cadence) from silent timeouts into
  diagnosable reports.
- **Crash flight recorder** — a ring of recent spans/scalars/notes,
  flushed to ``<logdir>/flightrec-<host>.jsonl`` from ``sys.excepthook``
  / ``atexit`` and from any injected ``crash``/``error`` fault
  (utils/faults.py calls ``record_fault`` BEFORE ``os._exit``), so a
  chaos crash leaves a readable last-seconds postmortem.

stdlib-only — no jax, no numpy — so it is importable from any layer
(including utils/faults.py) and from the bench's host-only phases.
"""

from __future__ import annotations

import atexit
import faulthandler
import itertools
import json
import os
import sys
import threading
import time
from collections import deque

SPAN_RING = 2048        # completed spans retained for dumps
FLIGHT_EVENTS = 512     # flight-recorder ring length (--flightrec_events)
WATCHDOG_LAST_SPANS = 32

# The scope catalog: the ``jax.named_scope`` names the compiled programs
# carry (docs/ARCHITECTURE.md, "scope catalog"). Each is put on once,
# where the work is written, so every step, eval and decode program
# inherits it; a trace reader gives an operation to the innermost of these
# in its ``op_name`` path (``benchmark/harness/scopes.py`` imports this
# tuple, tests/test_scopes.py holds the compiled step to it).
SCOPES = ("attention", "attn_proj", "mlp", "lm_head", "embed", "optimizer",
          "sample_batch", "moe_router", "moe_experts", "attention_window",
          "moe_shared", "loop_exit", "linear_attention")


def _json_safe(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, dict):
        return {str(k): _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    return str(v)


class _NoopSpan:
    __slots__ = ()
    dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    """One active span. Cheap by construction: two perf_counter reads,
    one wall-clock read, a thread-local stack push/pop, one deque
    append. ``id`` is unique in the process; ``parent`` is the id of the
    span that was open on this thread when this one started (None at
    the top), so a span's self time is its duration less its children's.
    ``dur_s`` is readable once the span has closed."""

    __slots__ = ("_tracer", "_name", "_attrs", "_t0", "_wall", "_depth",
                 "_id", "_parent", "_annotation", "dur_s")

    def __init__(self, tracer, name, attrs):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        stack = self._tracer._stack()
        self._depth = len(stack)
        self._parent = stack[-1] if stack else None
        self._id = next(_SPAN_IDS)
        stack.append(self._id)
        annotate = _ANNOTATOR
        self._annotation = None
        if annotate is not None:
            self._annotation = annotate(self._name)
            self._annotation.__enter__()
        self._wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_s = dur = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        self._tracer._stack().pop()
        rec = dict(self._attrs) if self._attrs else {}
        rec["name"] = self._name
        rec["id"] = self._id
        rec["parent"] = self._parent
        rec["ts"] = self._wall
        rec["pc"] = self._t0
        rec["dur_s"] = dur
        rec["tid"] = threading.get_ident()
        rec["thread"] = threading.current_thread().name
        rec["depth"] = self._depth
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        self._tracer._finish(rec)
        return False


_SPAN_IDS = itertools.count(1)  # next() is atomic under the GIL

# ``set_annotator``'s factory: name -> context manager entered and left
# with every live span. None (the default): a span costs what it did.
_ANNOTATOR = None


def set_annotator(factory) -> None:
    """Mirror every span into a second tracer: ``factory(name)`` must
    return a context manager. The training loop installs
    ``jax.profiler.TraceAnnotation``, so that while a ``jax.profiler``
    session is live each span also lies on the host plane of the same
    ``.xplane.pb`` as the device's operations, on the trace's own clock
    (with no session live a TraceAnnotation records nothing). This module
    stays stdlib-only: it never imports the factory's package. ``None``
    removes the hook."""
    global _ANNOTATOR
    _ANNOTATOR = factory


class Tracer:
    """Thread-safe span collector: fixed ring + optional batched JSONL
    sink. ``enabled=False`` makes ``span`` return a shared no-op context
    manager (the ``--telemetry=false`` path: zero record cost)."""

    def __init__(self, ring: int = SPAN_RING):
        self.enabled = True
        self._ring: deque = deque(maxlen=ring)
        self._pending: list = []
        self._lock = threading.Lock()
        self._io_lock = threading.Lock()
        self._local = threading.local()
        self._path: str | None = None
        self._file = None
        self._file_path: str | None = None  # path _file was opened for
        self._header: dict | None = None  # owed to the sink's next flush

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, attrs=None):
        if not self.enabled:
            return _NOOP
        return _Span(self, name, attrs)

    def _mark(self, rec: dict) -> dict:
        """Identity and clocks of a record that is no ``with`` block (an
        instant, a retroactively-timed span): its own id, the open span
        of this thread as its parent."""
        stack = self._stack()
        rec.update(id=next(_SPAN_IDS), parent=stack[-1] if stack else None,
                   tid=threading.get_ident(),
                   thread=threading.current_thread().name,
                   depth=len(stack))
        return rec

    def _finish(self, rec: dict) -> None:
        with self._lock:
            self._ring.append(rec)
            if self._path is not None:
                self._pending.append(rec)
        _FLIGHT.record("span", rec)

    def record_instant(self, name: str, **attrs) -> None:
        """A zero-duration marker span (fault injections, notes)."""
        if not self.enabled:
            return
        rec = {k: _json_safe(v) for k, v in attrs.items()}
        rec.update(name=name, ts=time.time(), pc=time.perf_counter(),
                   dur_s=0.0, instant=True)
        self._finish(self._mark(rec))

    def record_complete(self, name: str, ts: float, dur_s: float,
                        attrs=None) -> None:
        """A retroactively-timed COMPLETED span: the caller measured
        ``(ts, dur_s)`` itself and emits after the fact (the request
        plane's phase segments are measured as a request moves through
        the batcher and emitted together at request finish)."""
        if not self.enabled:
            return
        rec = {k: _json_safe(v) for k, v in (attrs or {}).items()}
        rec.update(name=name, ts=float(ts), dur_s=float(dur_s))
        self._finish(self._mark(rec))

    def configure_sink(self, path: str | None) -> None:
        """Set (or clear) the spans JSONL file; flushes are batched —
        the loops call ``flush()`` at the display cadence and every
        flight-recorder dump flushes too. Each configuration owes the
        file one header line, written ahead of its first spans: the
        run's id, the pid, and one reading of the epoch and of
        ``perf_counter`` taken together, which places every record's
        ``pc`` (a monotonic clock) on the epoch of its ``ts``. It has a
        ``kind`` and neither ``ts`` nor ``dur_s``: no span."""
        with self._lock:
            # _path reads/writes stay under _lock (the writers' lock);
            # the file handle swap alone rides _io_lock
            self._path = path
            self._header = None if path is None else {
                "kind": "header", "name": "spans_header",
                "run": os.urandom(6).hex(),
                "pid": os.getpid(), "epoch": time.time(),
                "perf_counter": time.perf_counter()}
        with self._io_lock:
            if self._file is not None and path != self._file_path:
                self._file.close()
                self._file = None
                self._file_path = None

    def flush(self) -> None:
        """Write pending spans to the JSONL sink (batched: the hot path
        never touches the file)."""
        with self._lock:
            if self._path is None or not self._pending:
                return
            pending, self._pending = self._pending, []
            if self._header is not None:
                pending.insert(0, self._header)
                self._header = None
            path = self._path
        with self._io_lock:
            try:
                # the handle must match the path THIS flush snapshotted:
                # a configure_sink racing in between could otherwise
                # leave the handle bound to the OLD path and every later
                # flush would misdirect spans into the previous run's
                # file (the new sink staying silently empty)
                if self._file is not None and self._file_path != path:
                    self._file.close()
                    self._file = None
                if self._file is None:
                    os.makedirs(os.path.dirname(path) or ".",
                                exist_ok=True)
                    self._file = open(path, "a")
                    self._file_path = path
                for rec in pending:
                    self._file.write(json.dumps(
                        {k: _json_safe(v) for k, v in rec.items()}) + "\n")
                self._file.flush()
            except OSError as e:  # telemetry must never kill the run
                print(f"telemetry: span sink write failed: {e}")

    def last(self, k: int = WATCHDOG_LAST_SPANS) -> list:
        with self._lock:
            ring = list(self._ring)
        return ring[-k:]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._pending.clear()


_TRACER = Tracer()


def trace_span(name: str, **attrs):
    """The one span entry point: ``with trace_span("ckpt_write",
    step=step): ...``. Records to the global tracer's ring (and JSONL
    sink when configured); a shared no-op when telemetry is disabled."""
    return _TRACER.span(name, attrs or None)


def get_tracer() -> Tracer:
    return _TRACER


def record_span(name: str, *, ts: float, dur_s: float, **attrs) -> None:
    """Emit a retroactively-timed completed span to the global tracer
    (see ``Tracer.record_complete``) — the serving request plane's
    emission entry point. Subject to the span catalog like
    ``trace_span``/``record_instant`` (dttlint DTT005)."""
    _TRACER.record_complete(name, ts, dur_s, attrs or None)


def last_spans(k: int = WATCHDOG_LAST_SPANS) -> list:
    return _TRACER.last(k)


def chrome_trace(records=None) -> dict:
    """Span records -> a Chrome-trace/Perfetto ``traceEvents`` dict
    (load in ``chrome://tracing`` or https://ui.perfetto.dev). Complete
    spans become ``ph: "X"`` duration events; instant markers (fault
    injections) become ``ph: "i"``."""
    if records is None:
        records = _TRACER.last(10 ** 9)
    pid = os.getpid()
    core = ("name", "ts", "pc", "dur_s", "tid", "thread", "depth",
            "instant")
    events = []
    for r in records:
        args = {k: _json_safe(v) for k, v in r.items() if k not in core}
        ev = {"name": r.get("name", "?"), "pid": r.get("pid", pid),
              "tid": r.get("tid", 0), "ts": float(r.get("ts", 0.0)) * 1e6,
              "cat": "telemetry", "args": args}
        if r.get("instant"):
            ev["ph"] = "i"
            ev["s"] = "t"
        else:
            ev["ph"] = "X"
            ev["dur"] = float(r.get("dur_s", 0.0)) * 1e6
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ------------------------------------------------------ step breakdown


class StepTimer:
    """Per-window step-time breakdown accumulator.

    The loop wraps its three kinds of per-step work and calls ``add``:
    ``host_wait`` (drawing/staging the host batch), ``dispatch`` (the
    async step/chunk call returning), ``device`` (time blocked in the
    EXISTING ``block_until_ready`` at the collective sync cadence — so
    the breakdown adds no sync points; on backends with cadence 0 the
    device column reads 0 and the dispatch column absorbs it).
    ``scalars()`` returns the per-STEP means since the last call and
    resets the window — emitted at the display cadence next to
    ``images_per_sec``. ``cumulative_work()`` survives window turns (it
    clears only on a full ``reset()``, the compile boundary): host-side
    work seconds (host_wait + dispatch — the time this host spent
    producing the step rather than waiting in a collective) plus steps,
    which is the straggler-attribution numerator the multi-host
    coordinator ships in its vote.
    """

    KEYS = ("host_wait", "dispatch", "device")

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._acc = dict.fromkeys(self.KEYS, 0.0)
        self._steps = 0
        self._cum = dict.fromkeys(self.KEYS, 0.0)
        self._cum_steps = 0

    def add(self, key: str, dt: float) -> None:
        self._acc[key] += dt
        self._cum[key] += dt

    def steps(self, n: int = 1) -> None:
        self._steps += n
        self._cum_steps += n

    def cumulative_work(self) -> tuple[float, int]:
        """(host-side work seconds, steps) since the last full reset.
        Work = host_wait + dispatch: a straggler burns its step time
        HERE, while its peers burn the same wall time blocked in the
        device column (the collective wait) — so this is the column
        that attributes the slowness to a host."""
        return self._cum["host_wait"] + self._cum["dispatch"], \
            self._cum_steps

    def scalars(self) -> dict:
        n = max(self._steps, 1)
        out = {f"step_{k}_s": round(self._acc[k] / n, 9)
               for k in self.KEYS}
        self._acc = dict.fromkeys(self.KEYS, 0.0)
        self._steps = 0
        return out


# ------------------------------------------------------------ watchdog


class Watchdog:
    """Hang watchdog: ``arm(what, **ctx)`` brackets an operation that
    must finish within ``timeout_s``; a daemon thread fires when one
    does not — dumping the stalled operation's context, the last K
    spans, and every thread's stack (faulthandler) to ``out``, flushing
    the flight recorder, then optionally hard-exiting (``abort``).

    Fires at most once per armed operation (the report is the product;
    a wedged run must not scroll it away), and a disarm after the fire
    is a no-op. Multiple threads may hold armed ops concurrently (the
    training loop and a serving batcher worker share one process dog).
    ``fired`` counts reports for tests/monitoring."""

    EXIT_CODE = 124  # the timeout(1) convention

    def __init__(self, timeout_s: float, abort: bool = False, out=None):
        if timeout_s <= 0:
            raise ValueError(f"watchdog timeout must be > 0, got "
                             f"{timeout_s}")
        self.timeout_s = float(timeout_s)
        self.abort = bool(abort)
        self._out = out
        self._cv = threading.Condition()
        self._armed: dict[int, tuple] = {}  # gen -> (what, ctx, t0, deadline)
        self._gen = 0
        self._closed = False
        self._thread: threading.Thread | None = None
        self.fired = 0

    class _Armed:
        __slots__ = ("_wd", "_gen")

        def __init__(self, wd, gen):
            self._wd = wd
            self._gen = gen

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            wd = self._wd
            with wd._cv:
                wd._armed.pop(self._gen, None)
                wd._cv.notify_all()
            return False

    def arm(self, what: str, **ctx):
        with self._cv:
            if self._closed:
                return _NOOP
            self._gen += 1
            now = time.monotonic()
            self._armed[self._gen] = (what, ctx, now,
                                      now + self.timeout_s)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, name="telemetry-watchdog",
                    daemon=True)
                self._thread.start()
            self._cv.notify_all()
            return Watchdog._Armed(self, self._gen)

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._armed.clear()
            self._cv.notify_all()

    def _loop(self) -> None:
        cv = self._cv
        cv.acquire()
        try:
            while not self._closed:
                if not self._armed:
                    cv.wait(0.5)
                    continue
                now = time.monotonic()
                expired = [(g, e) for g, e in self._armed.items()
                           if e[3] <= now]
                if not expired:
                    soonest = min(e[3] for e in self._armed.values())
                    cv.wait(min(max(soonest - now, 0.0), 1.0))
                    continue
                for gen, _entry in expired:
                    self._armed.pop(gen, None)  # fire once per armed op
                self.fired += len(expired)
                # dump OUTSIDE the cv: stack-dump + fsync take seconds,
                # and healthy threads arming/disarming (e.g. serving
                # workers sharing the process dog) must not stall
                # behind an unrelated op's report
                cv.release()
                try:
                    for _gen, (what, ctx, armed_at, _dl) in expired:
                        try:
                            self._dump(what, ctx, now - armed_at)
                        except Exception as e:  # must not kill the dog
                            print(f"watchdog dump failed: {e}",
                                  flush=True)
                    if self.abort:
                        os._exit(self.EXIT_CODE)
                finally:
                    cv.acquire()
        finally:
            cv.release()

    def _dump(self, what: str, ctx: dict, waited: float) -> None:
        out = self._out or sys.stderr
        line = "=" * 70
        print(f"\n{line}\nWATCHDOG: {what!r} has not completed after "
              f"{waited:.1f}s (timeout {self.timeout_s}s)\n"
              f"  in-flight op context: "
              f"{ {k: _json_safe(v) for k, v in ctx.items()} }\n"
              f"  (the two known deadlock classes: XLA:CPU collective-"
              f"rendezvous interleave; gloo preamble abort — "
              f"utils/profiling.collective_sync_cadence)",
              file=out, flush=True)
        spans = last_spans(WATCHDOG_LAST_SPANS)
        print(f"last {len(spans)} spans (oldest first):", file=out)
        for r in spans:
            extras = {k: v for k, v in r.items()
                      if k not in ("name", "ts", "pc", "dur_s", "tid",
                                   "thread", "depth", "id", "parent")}
            print(f"  {r.get('ts', 0):.6f} {r.get('dur_s', 0) * 1e3:9.3f}ms "
                  f"[{r.get('thread', '?')}] "
                  f"{'  ' * r.get('depth', 0)}{r.get('name', '?')} "
                  f"{extras if extras else ''}", file=out)
        print("all-thread stacks:", file=out, flush=True)
        try:
            faulthandler.dump_traceback(file=out, all_threads=True)
        except (ValueError, OSError, AttributeError):
            # out has no usable fileno (StringIO etc.) — skip the stacks,
            # keep the span report
            print("  (stream has no file descriptor; stacks skipped)",
                  file=out)
        _FLIGHT.record("note", {"note": f"watchdog fired: {what}",
                                "waited_s": round(waited, 3),
                                **{k: _json_safe(v) for k, v in ctx.items()}})
        _FLIGHT.dump(f"watchdog:{what}")
        print(f"{line}\nend watchdog report ({'aborting' if self.abort else 'continuing'})\n{line}",
              file=out, flush=True)


_WATCHDOG: Watchdog | None = None


def get_watchdog() -> Watchdog | None:
    return _WATCHDOG


def set_watchdog(wd: Watchdog | None) -> Watchdog | None:
    """Install (or with None remove) the process watchdog ``armed()``
    uses; closes any previous one. Returns the new watchdog."""
    global _WATCHDOG
    if _WATCHDOG is not None and _WATCHDOG is not wd:
        _WATCHDOG.close()
    _WATCHDOG = wd
    return wd


def armed(what: str, **ctx):
    """Bracket a device dispatch / collective with the process watchdog
    (no-op when none is armed — the default)."""
    wd = _WATCHDOG
    if wd is None:
        return _NOOP
    return wd.arm(what, **ctx)


# ---------------------------------------------------- flight recorder


class FlightRecorder:
    """Fixed-size ring of recent spans/scalars/notes, dumped to
    ``<logdir>/flightrec-<host>.jsonl`` on crash paths.

    The ring records ALWAYS (a deque append per event); the dump only
    happens when a path is configured. Dumps overwrite (the newest
    postmortem wins) and start with a ``meta`` line naming the reason.
    Installed once per process on ``sys.excepthook`` (chained) and
    ``atexit``; utils/faults.py dumps directly before an injected
    ``crash``'s ``os._exit`` — the one path no hook survives."""

    def __init__(self, maxlen: int = FLIGHT_EVENTS):
        self._ring: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        # dump serialization: a watchdog fire can race the excepthook
        # (a crash DURING a hang is exactly when the postmortem matters)
        # — two mode-"w" writers interleaving would garble the file
        self._dump_lock = threading.Lock()
        self._path: str | None = None
        self._installed = False
        self.last_dump: str | None = None

    def record(self, kind: str, fields: dict) -> None:
        rec = {"kind": kind, "t": time.time()}
        rec.update(fields)
        with self._lock:
            self._ring.append(rec)

    def configure(self, path: str | None, maxlen: int | None = None) -> None:
        with self._lock:
            self._path = path
            # a re-pointed recorder is a new run: its atexit dump must
            # not be suppressed by a previous run's postmortem
            self.last_dump = None
            if maxlen is not None and maxlen != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=max(1, maxlen))
        if path is not None:
            self._install()

    @property
    def path(self) -> str | None:
        with self._lock:
            return self._path

    def _install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        prev_hook = sys.excepthook

        def _hook(exc_type, exc, tb):
            try:
                self.record("note",
                            {"note": f"uncaught {exc_type.__name__}: {exc}"})
                self.dump(f"excepthook:{exc_type.__name__}")
            except Exception:
                pass
            prev_hook(exc_type, exc, tb)

        sys.excepthook = _hook
        atexit.register(self._atexit_dump)

    @staticmethod
    def _holds_postmortem(path: str) -> bool:
        """True when ``path`` already holds a dump whose reason is NOT
        a routine shutdown (crash/watchdog/excepthook/fault)."""
        try:
            with open(path) as f:
                meta = json.loads(f.readline())
            return (meta.get("kind") == "meta"
                    and meta.get("reason", "") != "atexit")
        except (OSError, ValueError):
            return False

    def _atexit_dump(self) -> None:
        try:
            # don't downgrade a real postmortem: if a crash/watchdog/
            # excepthook dump already wrote the file, the clean-shutdown
            # rewrite would replace its meta reason with "atexit"
            with self._lock:
                dumped = self.last_dump
            if dumped is None:
                self.dump("atexit")
        except Exception:
            pass

    def dump(self, reason: str) -> str | None:
        """Write the ring (plus any pending spans) now; returns the
        path, or None when no sink is configured. Also flushes every
        registered flushable (MetricsLogger sinks) so the postmortem's
        neighbors — metrics.jsonl, TB events — keep their buffered
        tails too."""
        _TRACER.flush()
        _run_flush_hooks()
        with self._lock:
            path = self._path
            ring = list(self._ring)
        if path is None:
            return None
        if reason == "atexit" and self._holds_postmortem(path):
            # a clean shutdown must never bury a previous run's crash/
            # watchdog report under an uneventful ring (the orchestrator-
            # relaunch case: run A crashes, run B exits clean — the
            # postmortem must survive the relaunch); real postmortems
            # still overwrite each other (newest wins)
            return None
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with self._dump_lock, open(path, "w") as f:
                f.write(json.dumps({
                    "kind": "meta", "reason": reason, "t": time.time(),
                    "pid": os.getpid(), "events": len(ring)}) + "\n")
                for rec in ring:
                    f.write(json.dumps(
                        {k: _json_safe(v) for k, v in rec.items()}) + "\n")
                f.flush()
                os.fsync(f.fileno())
        except OSError as e:
            print(f"telemetry: flight-recorder dump failed: {e}")
            return None
        # last_dump is the watchdog-vs-excepthook-vs-atexit arbitration
        # state — same lock as configure()'s reset
        with self._lock:
            self.last_dump = reason
        return path


_FLIGHT = FlightRecorder()

_FLUSH_HOOKS: list = []  # weakref.WeakMethod of bound flush()es
_FLUSH_LOCK = threading.Lock()


def register_flush(bound_flush) -> None:
    """Register a bound ``flush()`` (e.g. a MetricsLogger's) to run on
    every flight-recorder dump — held weakly, so loggers die normally."""
    import weakref

    with _FLUSH_LOCK:
        _FLUSH_HOOKS.append(weakref.WeakMethod(bound_flush))


def _run_flush_hooks() -> None:
    with _FLUSH_LOCK:
        hooks = list(_FLUSH_HOOKS)
    for wm in hooks:
        fn = wm()
        if fn is None:
            with _FLUSH_LOCK:
                if wm in _FLUSH_HOOKS:
                    _FLUSH_HOOKS.remove(wm)
            continue
        try:
            fn()
        except Exception:  # a dead sink must not break the postmortem
            pass


def flight_recorder() -> FlightRecorder:
    return _FLIGHT


def record_scalars(step: int, values: dict) -> None:
    """MetricsLogger's tap: scalar emissions ride the flight ring so a
    postmortem shows the last metrics next to the last spans. Honors
    the --telemetry=false contract (disables recording entirely)."""
    if not _TRACER.enabled:
        return
    vals = {k: v for k, v in values.items()
            if isinstance(v, (int, float, str, bool)) or v is None}
    _FLIGHT.record("scalars", {"step": int(step), "values": vals})


def record_fault(point: str, mode: str, ctx: dict) -> None:
    """utils/faults.py calls this at every fired injection, BEFORE the
    mode's effect: the fault lands as an instant span, and crash/error
    modes dump the flight recorder immediately (``mode=crash`` is
    ``os._exit`` — no excepthook, no atexit, this is the only record
    that survives)."""
    _TRACER.record_instant(f"fault:{point}", mode=mode,
                           **{k: _json_safe(v) for k, v in ctx.items()})
    if mode in ("crash", "error", "refuse"):
        _FLIGHT.dump(f"fault:{point}:{mode}")


# -------------------------------------------------------- configuration


def host_tag(job_name: str = "", task_index: int = 0) -> str:
    return f"{job_name or 'worker'}-{int(task_index)}"


def configure(logdir: str | None = None, host: str | None = None,
              enabled: bool = True, watchdog_s: float = 0.0,
              watchdog_abort: bool = False,
              flight_events: int | None = None) -> Tracer:
    """Point the telemetry spine at a run: span sink + flight-recorder
    path under ``logdir`` (per-``host`` filenames so multi-process runs
    don't collide), optional watchdog. Loops and the serving stack call
    this via ``configure_from_flags``; calling again re-points the
    sinks (tests, multiple runs in one process)."""
    _TRACER.enabled = bool(enabled)
    host = host or host_tag()
    if enabled and logdir:
        os.makedirs(logdir, exist_ok=True)
        _TRACER.configure_sink(os.path.join(logdir,
                                            f"spans-{host}.jsonl"))
        _FLIGHT.configure(os.path.join(logdir,
                                       f"flightrec-{host}.jsonl"),
                          maxlen=flight_events)
    else:
        _TRACER.configure_sink(None)
        _FLIGHT.configure(None, maxlen=flight_events)
    if enabled and watchdog_s and watchdog_s > 0:
        set_watchdog(Watchdog(watchdog_s, abort=watchdog_abort))
    else:
        set_watchdog(None)
    return _TRACER


def configure_from_flags(FLAGS, job_name: str | None = None) -> Tracer:
    """The one flag->feature mapping for ``--telemetry`` /
    ``--watchdog_s`` / ``--watchdog_abort`` / ``--flightrec_events``,
    shared by every loop and the serving entry point. ``job_name``
    overrides the role in the per-host filenames — the serving replica
    passes "serve" so a replica pointed at the trainer's live logdir
    (the documented deployment) writes spans-serve-N.jsonl /
    flightrec-serve-N.jsonl instead of colliding with the trainer's
    worker-N files."""
    return configure(
        logdir=getattr(FLAGS, "logdir", None),
        host=host_tag(job_name or getattr(FLAGS, "job_name", "")
                      or "worker",
                      getattr(FLAGS, "task_index", 0) or 0),
        enabled=bool(getattr(FLAGS, "telemetry", True)),
        watchdog_s=float(getattr(FLAGS, "watchdog_s", 0.0) or 0.0),
        watchdog_abort=bool(getattr(FLAGS, "watchdog_abort", False)),
        flight_events=int(getattr(FLAGS, "flightrec_events", FLIGHT_EVENTS)
                          or FLIGHT_EVENTS),
    )
