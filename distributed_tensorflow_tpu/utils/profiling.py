"""Throughput metering + the collective in-flight cap.

The reference has no tracing or profiling at all (``import time`` at
MNISTDist.py:8 is dead — SURVEY.md §5). The build needs metering for the
BASELINE metric (images/sec/chip); jax.profiler tracing is driven directly
by the training loop via ``--profile_dir`` (training/loop.py).
"""

from __future__ import annotations

import functools
import threading
import time

import jax
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

from distributed_tensorflow_tpu.utils import telemetry
from distributed_tensorflow_tpu.utils.telemetry import SCOPES


def _catalogued(name: str) -> str:
    if name not in SCOPES:
        raise ValueError(f"scope {name!r} is not in the scope catalog "
                         f"{SCOPES} (utils/telemetry.py)")
    return name


def scope(name: str):
    """``jax.named_scope(name)`` for a name of the scope catalog
    (``telemetry.SCOPES``): every operation traced inside carries the
    name in its ``op_name`` path, through jvp, transpose and remat, and
    the profiler's trace gives each device operation that path. Only HLO
    metadata changes; the arithmetic and the programs do not."""
    return jax.named_scope(_catalogued(name))


def scoped(name: str):
    """Decorator form of ``scope``: the whole function runs under it.
    The scope is opened at every call (not once at decoration), which is
    what a custom-VJP rule that is traced long after its definition
    needs; a name outside the catalog fails at import, not at trace."""
    _catalogued(name)

    def decorate(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner

    return decorate


def _record(x, *, name, attrs):
    telemetry.get_tracer().record_instant(name, **dict(attrs))
    return x


_instant_p = Primitive("lowering_instant")
_instant_p.def_impl(_record)  # called eagerly: there is no program
_instant_p.def_abstract_eval(lambda x, **_: x)
mlir.register_lowering(
    _instant_p, lambda ctx, x, **params: [_record(x, **params)])
batching.defvectorized(_instant_p)


def lowering_instant(name: str, x, **attrs):
    """``x``, unchanged — and one telemetry instant ``name`` whenever the
    program that holds this call is LOWERED (no operation is emitted, the
    program does not change). A branch of ``lax.platform_dependent`` is
    traced for every platform and lowered for one, so a choice the
    lowering makes can only be recorded from there. Use the returned
    value, or the marker is dead code. ``attrs``: strings and numbers."""
    return _instant_p.bind(x, name=name, attrs=tuple(sorted(attrs.items())))


class Throughput:
    """images/sec (and per-chip) meter over a training window."""

    def __init__(self, batch_size: int, n_chips: int = 1):
        self.batch_size = batch_size
        self.n_chips = n_chips
        self.reset()

    def reset(self):
        self._start = time.perf_counter()
        self._images = 0

    def step(self, n: int | None = None):
        self._images += n if n is not None else self.batch_size

    @property
    def images_per_sec(self) -> float:
        dt = time.perf_counter() - self._start
        return self._images / dt if dt > 0 else 0.0

    @property
    def images_per_sec_per_chip(self) -> float:
        return self.images_per_sec / max(self.n_chips, 1)


class ServeTraceCapture:
    """``--serve_profile_batches N``: capture ONE jax.profiler trace
    window around N served microbatches and report the artifact path.

    Installed as the serving metrics hook's profiler: the first
    ``on_batch`` call starts the trace, the Nth stops it — so the window
    brackets real traffic (steady-state batching, reload blips included
    if one lands inside), not a synthetic loop. One-shot by design: a
    profile is an investigation artifact, not a steady-state cost.
    ``path`` (and the returned value of the closing ``on_batch``) is the
    trace directory for ``tensorboard --logdir`` / Perfetto."""

    def __init__(self, profile_dir: str, n_batches: int):
        if n_batches < 1:
            raise ValueError(f"n_batches must be >= 1, got {n_batches}")
        self.profile_dir = profile_dir
        self.n_batches = int(n_batches)
        self._seen = 0
        self._active = False
        self._done = False
        # shared across every batcher's worker thread: start/stop of the
        # singleton jax profiler must be check-then-act under one lock
        self._lock = threading.Lock()
        self.path: str | None = None

    def on_batch(self) -> str | None:
        """Call once per served microbatch (any worker thread). Returns
        the artifact path on the call that closes the window, else
        None."""
        with self._lock:
            if self._done:
                return None
            if not self._active:
                import os

                os.makedirs(self.profile_dir, exist_ok=True)
                jax.profiler.start_trace(self.profile_dir)
                self._active = True
            self._seen += 1
            if self._seen >= self.n_batches:
                jax.profiler.stop_trace()
                self._active = False
                self._done = True
                self.path = self.profile_dir
                print(f"serving profile: traced {self._seen} batches "
                      f"into {self.profile_dir}")
                return self.path
            return None

    def close(self) -> None:
        """Stop a still-open window (server shutdown before N batches)."""
        with self._lock:
            if self._active:
                jax.profiler.stop_trace()
                self._active = False
                self._done = True
                self.path = self.profile_dir


def collective_sync_cadence(multi_device: bool) -> int:
    """How often (in steps) a multi-device training loop must
    ``block_until_ready`` to bound in-flight collective programs; 0 = never.

    XLA:CPU runs each virtual device on a pool thread and collective
    programs rendezvous across all of them; dozens of concurrently enqueued
    mesh programs can interleave across device threads and deadlock the
    rendezvous (observed at ~60 deep on an 8-device host — PERF.md). TPU
    streams execute strictly in enqueue order per chip, so no cap there.

    MULTI-PROCESS CPU (the gloo test topology) is stricter still: two
    in-flight cross-host programs can interleave their gloo sends on one
    TCP pair and crash the transport with a preamble/size mismatch
    (``op.preamble.length <= op.nbytes`` abort, observed r8) — so at most
    ONE collective program may be in flight: cadence 1.
    """
    if not multi_device:
        return 0
    if jax.default_backend() == "cpu":
        return 1 if jax.process_count() > 1 else 16
    return 0
