"""Continuous batching: iteration-level scheduling over paged KV slots
(r21) — the serving answer to the long-generation adversary.

The whole-batch path (``DynamicBatcher`` + ``engine.generate``) commits
a microbatch for its ENTIRE generation: one 512-token request holds its
batch — and the worker — hostage while 8-token requests queue behind
it, and every batch member is billed a dense ``(B, seq_len, H, Dh)``
KV allocation regardless of its actual length. The Orca line of work
fixes the first problem (schedule between decode ITERATIONS, not
batches); vLLM's PagedAttention fixes the second (block-allocate the
cache so memory tracks live tokens). This module is both, on this
repo's bitwise-pinned decode:

- ``ContinuousScheduler`` owns a fixed set of batch SLOTS over one
  traced step (``decode.make_slot_step``). Every iteration it feeds
  each resident slot its next token at its own position; requests are
  admitted into free slots and retired out of finished ones BETWEEN
  iterations, so a long generation never blocks a short one behind it.
- Prefill is chunked maximally: a prompt enters the cache one token
  per iteration through the SAME step (prefill-as-decode), so a long
  prompt cannot stall in-flight decodes for more than one iteration —
  and the bitwise induction (see ``make_slot_step``) holds from
  position 0 with no separate prefill computation to pin.
- The KV cache is paged: ``kvpage.PageAllocator`` commits a request's
  worst-case footprint at admission (no-preemption guarantee) and hands
  out physical pages as generation crosses page boundaries, so
  ``pages_in_use`` tracks live tokens. Occupancy feeds the ``/metrics``
  ``hbm`` block and the ``--serve_hbm_headroom_pct`` drain floor.
- ``ContinuousBatcher`` is the drop-in sibling of ``DynamicBatcher``:
  same ``Future``/expiry/stats machinery (imported, not reimplemented),
  same admission contract (reject-never-hang, ``serve_admit`` fault
  point, request-plane dispositions on every exit), same
  close/drain/die story — so ``server.py`` and the loadgen drive
  either through one interface, selected by ``--serve_scheduler``.

Phase accounting under mid-batch admission: a request's slot residency
is bracketed by ``taken()``/``run_start()`` at slot admission and
``run_end()`` at retirement; every iteration's wall duration is noted
to every resident request (phase ``decode`` with one tick when that
slot sampled a token this iteration, ``prefill`` while its prompt is
still entering the cache) — each request WAITED the full iteration
whatever its share of the math was, exactly the whole-batch
convention. All notes land inside the request's own run window, so the
plane's ``sum(phases) == wall`` invariant survives admission and
retirement at any iteration, including rejections and expiries.

Greedy parity contract: with ``temperature == 0`` the per-request token
sequence is BITWISE identical to whole-batch ``generate()`` — asserted
per-request on mixed-length workloads by tests/test_continuous.py.
Temperature sampling is served (per-request stream seeded by the
request's ``seed``) but makes no cross-scheduler reproducibility
promise: the whole-batch path draws from one batch-shaped stream that
has no per-request decomposition.

Threads (dttsan registry): ``ContinuousBatcher`` starts a scheduler
thread (``_sched_loop`` — the iteration loop) and an expiry thread
(``_expiry_loop`` — deadline enforcement independent of iteration
progress). Queue and lifecycle state live under the batcher's
condition variable; counters under their own locks; the step dispatch
itself runs OUTSIDE every lock so admission never waits on the chip.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from distributed_tensorflow_tpu.serving import reqtrace
from distributed_tensorflow_tpu.serving.batcher import (
    BatcherStats,
    Future,
    RejectedError,
    _Request,
)
from distributed_tensorflow_tpu.serving.kvpage import PageAllocator
from distributed_tensorflow_tpu.utils import resources
from distributed_tensorflow_tpu.utils.faults import fault_point


class HostSlotBackend:
    """Chip-free slot stepper: deterministic logits from a tiny seeded
    embedding/head pair, no jax anywhere. The test and bench double for
    ``EngineSlotBackend`` — the scheduler state machine, the page
    ledger, the phase accounting, and the A/B throughput drill all run
    against it without a backend or a compile. ``step_cost`` (a
    callable) lets the bench charge a controlled amount of work per
    iteration so both arms of the A/B pay the same per-step price."""

    def __init__(self, *, n_slots: int = 4, capacity: int = 64,
                 page_size: int = 16, num_pages: int = 0,
                 vocab_size: int = 32, step_cost=None):
        if n_slots < 2:
            raise ValueError(f"n_slots must be >= 2, got {n_slots}")
        if page_size < 1 or capacity % page_size:
            raise ValueError(f"page_size ({page_size}) must divide the "
                             f"capacity ({capacity})")
        self.n_slots = int(n_slots)
        self.capacity = int(capacity)
        self.page_size = int(page_size)
        self.pages_per_slot = self.capacity // self.page_size
        self.num_pages = int(num_pages) or self.n_slots * self.pages_per_slot
        self.vocab_size = int(vocab_size)
        self._step_cost = step_cost
        rng = np.random.default_rng(0)
        self._emb = rng.standard_normal(
            (self.vocab_size, 16)).astype(np.float32)
        self._head = rng.standard_normal(
            (16, self.vocab_size)).astype(np.float32)

    def step(self, page_table, tok, t):
        if self._step_cost is not None:
            self._step_cost()
        # position-dependent so greedy sequences are non-trivial
        h = self._emb[tok] + np.asarray(t)[:, None].astype(np.float32)
        return h @ self._head

    def wants_refresh(self) -> bool:
        return False

    def refresh(self) -> None:
        pass

    def reset(self) -> None:
        pass


class EngineSlotBackend:
    """Device-backed slot stepper over the paged KV pools.

    Holds the engine's current (params, step) pinned for in-flight
    requests — the scheduler refreshes the pin (``refresh``) only when
    no slot is resident, so a hot-swap changes what FUTURE requests
    see, never one mid-generation (drain-to-swap; the whole-batch
    analogue reads ``engine.current()`` once per batch).

    Recompile sentry: slot count, page-table shape, and pool shapes are
    all static, so continuous mode contributes exactly ONE traced
    signature (``serve_continuous_step``) however requests arrive —
    noted per dispatch like the whole-batch sites.

    All mutable state (params pin, device pools) is guarded by one lock:
    the scheduler thread steps while tests and /metrics handlers may
    probe."""

    def __init__(self, engine, *, n_slots: int = 4, page_size: int = 16,
                 num_pages: int = 0):
        from distributed_tensorflow_tpu.serving import decode as dec

        dec.check_decodable(engine.model)
        if engine.mesh is not None:
            raise ValueError(
                "the continuous scheduler serves one replica per device; "
                "multi-device meshes / --serve_tp are whole-batch only")
        if n_slots < 2:
            # width >= 2 keeps every contraction on the GEMM kernel —
            # the same floor the whole-batch decode enforces for parity
            raise ValueError(f"n_slots must be >= 2, got {n_slots}")
        capacity = engine.model.seq_len
        if page_size < 1 or capacity % page_size:
            raise ValueError(f"page_size ({page_size}) must divide the "
                             f"cache capacity ({capacity})")
        pages_per_slot = capacity // page_size
        if num_pages <= 0:
            # full provisioning: every slot can hold a max-length request
            num_pages = n_slots * pages_per_slot
        if num_pages < pages_per_slot:
            raise ValueError(
                f"num_pages ({num_pages}) cannot hold one full-context "
                f"request ({pages_per_slot} pages)")
        self.engine = engine
        self.n_slots = int(n_slots)
        self.capacity = capacity
        self.page_size = int(page_size)
        self.pages_per_slot = pages_per_slot
        self.num_pages = int(num_pages)
        self.vocab_size = engine.model.vocab_size
        self._lock = threading.Lock()
        self._step_fn = dec.make_slot_step(engine.model, page_size,
                                           jit=engine.jit)
        self._pools = dec.make_slot_pools(engine.model, page_size,
                                          self.num_pages)
        self._params, self._params_step = engine.current()

    @property
    def params_step(self) -> int:
        with self._lock:
            return self._params_step

    def wants_refresh(self) -> bool:
        with self._lock:
            pinned = self._params_step
        return self.engine.step != pinned

    def refresh(self) -> None:
        """Re-pin the engine's current params. Only called by the
        scheduler with zero residents (drain-to-swap)."""
        with self._lock:
            self._params, self._params_step = self.engine.current()

    def reset(self) -> None:
        """Re-zero the device pools (scheduler abort path — donated
        buffers are in an unknown state after a failed dispatch)."""
        from distributed_tensorflow_tpu.serving import decode as dec

        with self._lock:
            self._pools = dec.make_slot_pools(
                self.engine.model, self.page_size, self.num_pages)

    def step(self, page_table, tok, t) -> np.ndarray:
        import jax.numpy as jnp

        resources.note_signature(
            "serve_continuous_step",
            (self.n_slots, self.capacity, self.page_size, self.num_pages))
        with self._lock:
            logits, self._pools = self._step_fn(
                self._params, self._pools,
                jnp.asarray(page_table), jnp.asarray(tok), jnp.asarray(t))
        return np.asarray(logits)


class _Slot:
    """One resident request's decode state: ``fed`` counts positions
    already written into the cache (prompt first, then generated
    tokens); the request retires when ``len(generated) == n``."""

    __slots__ = ("req", "prompt", "n", "fed", "generated", "reservation",
                 "temperature", "seed", "rng", "keep_logits", "logits")

    def __init__(self, req, prompt, n, reservation):
        self.req = req
        self.prompt = prompt
        self.n = n
        self.fed = 0
        self.generated: list[int] = []
        self.reservation = reservation
        self.temperature = float(req.opts.get("temperature", 0.0) or 0.0)
        self.seed = req.opts.get("seed")
        self.rng = None
        self.keep_logits = bool(req.opts.get("return_logits", False))
        self.logits: list[np.ndarray] = []


class ContinuousScheduler:
    """Slot/page state machine driven by the batcher's scheduler thread.

    State per slot: empty (``None`` — page-table row all zeros, feeds
    the scratch page) or resident (a ``_Slot``). One iteration
    (``_iterate``) feeds every resident its next token at its own
    position through ONE backend step, samples where a slot's prompt
    is already consumed, and retires slots whose generation completed.
    Underscored methods run on the scheduler thread only; ``snapshot``
    and ``allocator.occupancy()`` are the cross-thread read surface
    (lock-guarded counters, nothing else shared).

    Token feed schedule (the bitwise mirror of ``generate()``): a
    request with prompt length P and N new tokens feeds positions
    ``0 .. P+N-2`` — prompt tokens first, then its own samples; the
    sample drawn after feeding position ``P-1+k`` is output token
    ``k``, and the final token is sampled but never fed (whole-batch
    stops stepping there too). Cache footprint is therefore exactly
    ``P+N-1`` tokens = the page commitment."""

    def __init__(self, backend):
        self.backend = backend
        self.n_slots = backend.n_slots
        self.capacity = backend.capacity
        self.page_size = backend.page_size
        self.pages_per_slot = backend.pages_per_slot
        self.allocator = PageAllocator(backend.num_pages, backend.page_size)
        self._slots: list = [None] * self.n_slots
        self._free_slots = list(range(self.n_slots - 1, -1, -1))
        self._page_table = np.zeros((self.n_slots, self.pages_per_slot),
                                    np.int32)
        self._tok = np.zeros(self.n_slots, np.int32)
        self._t = np.zeros(self.n_slots, np.int32)
        # slot state (slots, free list, page table, feed buffers) is
        # touched by exactly one scheduler thread, but the failure path
        # (_abort_residents) can also run from close(); one uncontended
        # lock makes the ownership explicit. Order: batcher cv →
        # _slot_lock → {_lock, allocator._lock, backend._lock}
        self._slot_lock = threading.Lock()
        # counters: written by the scheduler thread, read by /metrics
        # and the bench via snapshot() — one lock guards them
        self._lock = threading.Lock()
        self._iterations = 0
        self._tokens_emitted = 0
        self._resident_iterations = 0
        self._live_tokens_high = 0
        self._ledger_ok = True

    # ------------------------------------------------- admission checks

    def _validate(self, prompt: np.ndarray, n: int) -> str | None:
        """Reject reasons mirroring ``decode.generate``'s loud
        ValueErrors (vocab range, capacity) plus the page-pool bound;
        None when servable."""
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            return f"prompt must be 1-D with >= 1 token; got shape " \
                   f"{tuple(prompt.shape)}"
        if n < 1:
            return f"max_new_tokens must be >= 1, got {n}"
        p = int(prompt.shape[0])
        if p + n > self.capacity:
            return (f"prompt ({p}) + max_new_tokens ({n}) exceeds the "
                    f"model's context window / cache capacity "
                    f"({self.capacity})")
        vocab = getattr(self.backend, "vocab_size", None)
        if vocab is not None and prompt.size and (
                int(prompt.min()) < 0 or int(prompt.max()) >= vocab):
            return (f"prompt ids must be in [0, {vocab}); got range "
                    f"[{prompt.min()}, {prompt.max()}]")
        if self.allocator.pages_for(p + n - 1) > self.allocator.num_pages:
            return (f"request footprint ({p + n - 1} tokens) exceeds the "
                    f"KV page pool ({self.allocator.num_pages} pages of "
                    f"{self.page_size})")
        return None

    def _can_admit(self, req) -> bool:
        with self._slot_lock:
            if not self._free_slots:
                return False
        p = int(np.asarray(req.payload).shape[-1])
        n = int(req.opts.get("max_new_tokens", 16))
        return self.allocator.can_admit(p + n - 1)

    def _has_residents(self) -> bool:
        with self._slot_lock:
            return len(self._free_slots) < self.n_slots

    def _wants_refresh(self) -> bool:
        return self.backend.wants_refresh()

    def _refresh(self) -> None:
        self.backend.refresh()

    # ---------------------------------------------------- slot lifecycle

    def _admit(self, req) -> None:
        """Move a validated, page-feasible request into a free slot.
        Caller guarantees ``_can_admit`` held; runs under the batcher cv
        (cheap: no device work here)."""
        prompt = np.asarray(req.payload, np.int32).reshape(-1)
        n = int(req.opts.get("max_new_tokens", 16))
        reservation = self.allocator.reserve(len(prompt) + n - 1)
        with self._slot_lock:
            i = self._free_slots.pop()
            self._slots[i] = _Slot(req, prompt, n, reservation)
        tr = req.trace
        if tr is not None:
            tr.taken()
            tr.run_start()
        with self._lock:
            it = self._iterations
        reqtrace.note_slot_admit(tr, iteration=it, slot=i)

    def _retire(self, i: int):
        """Free slot ``i`` (generation complete): release its pages,
        zero its page-table row back to scratch, hand back (request,
        result)."""
        s = self._slots[i]
        tr = s.req.trace
        if tr is not None:
            tr.run_end()
        with self._lock:
            it = self._iterations
        reqtrace.note_slot_retire(tr, iteration=it)
        self.allocator.release(s.reservation)
        self._page_table[i, :] = 0
        self._tok[i] = 0
        self._t[i] = 0
        self._slots[i] = None
        self._free_slots.append(i)
        tokens = np.concatenate(
            [s.prompt, np.asarray(s.generated, np.int32)])
        if s.keep_logits:
            return s.req, {"tokens": tokens, "logits": np.stack(s.logits)}
        return s.req, tokens

    def _abort_residents(self) -> list:
        """Failure path: evict every resident (pages released, slots
        zeroed, pools re-zeroed) and return their requests for the
        batcher to fail. The scheduler keeps serving afterwards."""
        failed = []
        with self._slot_lock:
            for i in range(self.n_slots):
                s = self._slots[i]
                if s is None:
                    continue
                if s.req.trace is not None:
                    s.req.trace.run_end()
                self.allocator.release(s.reservation)
                self._page_table[i, :] = 0
                self._tok[i] = 0
                self._t[i] = 0
                self._slots[i] = None
                self._free_slots.append(i)
                failed.append(s.req)
        self.backend.reset()
        return failed

    # -------------------------------------------------------- iteration

    def _sample(self, s: _Slot, row: np.ndarray) -> int:
        if s.temperature > 0.0:
            import jax
            import jax.numpy as jnp

            if s.rng is None:
                s.rng = jax.random.PRNGKey(
                    int(s.seed) if s.seed is not None else 0)
            key = jax.random.fold_in(s.rng, len(s.generated))
            return int(np.asarray(jax.random.categorical(
                key, jnp.asarray(row) / s.temperature)))
        return int(row.argmax())

    def _iterate(self):
        """One decode tick over the residents. Returns
        ``(finished, n_active)`` where ``finished`` is a list of
        (request, result) pairs retired this iteration."""
        with self._slot_lock:
            return self._iterate_locked()

    def _iterate_locked(self):
        t0 = time.perf_counter()
        active = [i for i in range(self.n_slots)
                  if self._slots[i] is not None]
        for i in active:
            s = self._slots[i]
            if s.fed % self.page_size == 0:
                # crossing into a fresh logical page: map a physical one
                # (the admission commitment guarantees availability)
                self._page_table[i, s.fed // self.page_size] = \
                    self.allocator.alloc(s.reservation)
            p = len(s.prompt)
            self._tok[i] = (s.prompt[s.fed] if s.fed < p
                            else s.generated[s.fed - p])
            self._t[i] = s.fed
        logits = self.backend.step(self._page_table, self._tok, self._t)
        d = time.perf_counter() - t0
        finished = []
        n_sampled = 0
        for i in active:
            s = self._slots[i]
            sampling = s.fed >= len(s.prompt) - 1
            tr = s.req.trace
            if tr is not None:
                # every resident waited the whole iteration — same
                # convention as whole-batch note_phase; noting BEFORE
                # any run_end keeps the note inside the run window, so
                # sum(phases) == wall survives mid-batch retirement
                tr.note("decode" if sampling else "prefill", d,
                        ticks=1 if sampling else None)
            s.fed += 1
            if sampling:
                n_sampled += 1
                tok = self._sample(s, logits[i])
                s.generated.append(tok)
                if s.keep_logits:
                    s.logits.append(np.array(logits[i], copy=True))
                if len(s.generated) >= s.n:
                    finished.append(self._retire(i))
        # analytic page ledger: in-use pages must equal the sum of every
        # resident's ceil(fed / page_size) — i.e. memory tracks LIVE
        # tokens, the paged-cache claim, checked every iteration
        expect = sum(
            -(-self._slots[i].fed // self.page_size)
            for i in range(self.n_slots) if self._slots[i] is not None)
        in_use = self.allocator.occupancy()["pages_in_use"]
        live_tokens = sum(
            self._slots[i].fed for i in range(self.n_slots)
            if self._slots[i] is not None)
        with self._lock:
            self._iterations += 1
            self._tokens_emitted += n_sampled
            self._resident_iterations += len(active)
            self._ledger_ok = self._ledger_ok and (in_use == expect)
            if live_tokens > self._live_tokens_high:
                self._live_tokens_high = live_tokens
        return finished, len(active)

    # ---------------------------------------------------------- reports

    def snapshot(self) -> dict:
        """The cross-thread read surface: scheduler counters + page
        occupancy, for /metrics' ``continuous`` block and the bench's
        analytic facts."""
        with self._lock:
            iterations = self._iterations
            tokens = self._tokens_emitted
            resident = self._resident_iterations
            live_high = self._live_tokens_high
            ledger_ok = self._ledger_ok
        return {
            "n_slots": self.n_slots,
            "iterations": iterations,
            "tokens_emitted": tokens,
            "tokens_per_iteration": round(tokens / iterations, 4)
            if iterations else 0.0,
            "slot_occupancy": round(
                resident / (iterations * self.n_slots), 4)
            if iterations else 0.0,
            "live_tokens_high_water": live_high,
            "page_ledger_ok": ledger_ok,
            "kv_pages": self.allocator.occupancy(),
        }


class ContinuousBatcher:
    """``DynamicBatcher``'s continuous-mode sibling: same bounded
    admission, Future, expiry, stats, and request-plane contract —
    but the worker is an iteration-level scheduler loop instead of a
    take-batch/run-batch loop. One "batch" in the stats is one
    scheduler ITERATION (``mean_batch_size`` therefore reads as mean
    slot occupancy).

    Admission is strict FIFO: the queue head is admitted as soon as a
    slot AND its full page commitment are free; nothing overtakes it
    (no starvation of long requests behind cheap ones). Validation
    failures (vocab, capacity, page-pool bound) raise ``ValueError`` at
    submit — the same loud-400 contract as the whole-batch runner —
    with a "failed" disposition.
    """

    def __init__(self, backend, *, queue_depth: int = 64,
                 default_timeout_ms: float = 1000.0,
                 latency=None, on_iteration=None, name: str = "generate"):
        if queue_depth < 1:
            raise ValueError(f"queue_depth must be >= 1, "
                             f"got {queue_depth}")
        self.queue_depth = int(queue_depth)
        self.default_timeout_s = float(default_timeout_ms) / 1000.0
        self.latency = latency
        self._on_iteration = on_iteration
        self._route = name
        self.scheduler = ContinuousScheduler(backend)
        self.max_batch = backend.n_slots  # interface parity (stats/UX)
        self.stats = BatcherStats()
        self._queue: list[_Request] = []
        self._cv = threading.Condition()
        self._closed = False
        self._sched = threading.Thread(
            target=self._sched_loop, name=f"{name}-sched", daemon=True)
        self._sched.start()
        # deadlines fire independently of iteration progress, exactly
        # like the whole-batch expiry thread
        self._expirer = threading.Thread(
            target=self._expiry_loop, name=f"{name}-expiry", daemon=True)
        self._expirer.start()

    # ------------------------------------------------------- admission

    def submit(self, payload, timeout_ms: float | None = None,
               request_id: str | None = None, **opts) -> Future:
        """Admit one request; returns its Future. Same contract as
        ``DynamicBatcher.submit`` (reject-never-hang, echoed
        request_id) plus submit-time validation against the decode
        capacity and page pool."""
        now = time.monotonic()
        rid = str(request_id) if request_id else reqtrace.new_request_id()
        plane = reqtrace.get_plane()
        tr = (plane.begin(rid, self._route, payload)
              if plane is not None else None)
        timeout_s = (self.default_timeout_s if timeout_ms is None
                     else float(timeout_ms) / 1000.0)
        prompt = np.asarray(payload)
        n = int(opts.get("max_new_tokens", 16))
        err = self.scheduler._validate(prompt, n)
        if err is not None:
            with self.stats.lock:
                self.stats.failed += 1
            reqtrace.finish(tr, "failed", reason=err)
            raise ValueError(err)
        req = _Request(payload=prompt, opts=opts, group=None,
                       future=Future(), t_submit=now,
                       deadline=now + timeout_s, request_id=rid,
                       trace=tr)
        req.future.request_id = rid
        with self._cv:
            if self._closed:
                with self.stats.lock:
                    self.stats.rejected_closed += 1
                reqtrace.finish(tr, "rejected_closed",
                                reason="batcher closed")
                raise RejectedError("batcher closed", request_id=rid)
            if len(self._queue) >= self.queue_depth:
                with self.stats.lock:
                    self.stats.rejected_full += 1
                reason = (f"queue full (depth={self.queue_depth}); "
                          f"retry later")
                reqtrace.finish(tr, "rejected_full", reason=reason)
                raise RejectedError(reason, request_id=rid)
            with self.stats.lock:
                admit_count = self.stats.admitted + 1
            try:
                fault_point("serve_admit", count=admit_count)
            except Exception as e:
                with self.stats.lock:
                    self.stats.rejected_fault += 1
                reqtrace.finish(tr, "rejected_fault",
                                reason=f"admission fault: {e}")
                raise RejectedError(f"admission fault: {e}",
                                    request_id=rid) from e
            self._queue.append(req)
            if tr is not None:
                tr.admitted()
            with self.stats.lock:
                self.stats.admitted += 1
                self.stats.queue_depth = len(self._queue)
            self._cv.notify_all()
        return req.future

    # ------------------------------------------------- scheduler thread

    def _expire_locked(self) -> None:
        now = time.monotonic()
        keep = []
        for r in self._queue:
            if r.deadline <= now:
                with self.stats.lock:
                    self.stats.rejected_deadline += 1
                r.future.meta = reqtrace.finish(
                    r.trace, "expired",
                    reason="deadline exceeded before execution")
                r.future.set_error(RejectedError(
                    "deadline exceeded before execution",
                    request_id=r.request_id))
            else:
                keep.append(r)
        if len(keep) != len(self._queue):
            self._queue = keep
            with self.stats.lock:
                self.stats.queue_depth = len(self._queue)

    def _admit_locked(self) -> None:
        """Strict-FIFO slot admission from the queue head; stops at the
        first request that doesn't fit (slot or pages)."""
        sched = self.scheduler
        admitted = False
        while self._queue and sched._can_admit(self._queue[0]):
            r = self._queue.pop(0)
            sched._admit(r)
            admitted = True
        if admitted:
            with self.stats.lock:
                self.stats.queue_depth = len(self._queue)

    def _sched_loop(self) -> None:
        sched = self.scheduler
        while True:
            with self._cv:
                while True:
                    self._expire_locked()
                    draining = sched._wants_refresh()
                    if not draining:
                        self._admit_locked()
                    if sched._has_residents():
                        break
                    if self._closed and not self._queue:
                        return
                    if draining:
                        # drain-to-swap: zero residents is the moment a
                        # params hot-swap is safe (nothing mid-flight)
                        sched._refresh()
                        continue
                    self._cv.wait(0.05)
            # the step dispatch runs OUTSIDE the cv: admission (submit)
            # must never wait on the chip
            try:
                with self.stats.lock:
                    self.stats.batches += 1
                    n_iter = self.stats.batches
                fault_point("serve_batch", count=n_iter)
                finished, n_active = sched._iterate()
                with self.stats.lock:
                    self.stats.batched_requests += n_active
                now = time.monotonic()
                for r, res in finished:
                    if self.latency is not None:
                        self.latency.record((now - r.t_submit) * 1e3)
                    if r.trace is not None:
                        # every resident decodes on the backend's pinned
                        # params (drain-to-swap), so the step at retire
                        # is the step that served the whole request
                        r.trace.served_step = getattr(
                            sched.backend, "params_step", None)
                    # meta BEFORE the result, like the whole-batch path
                    r.future.meta = reqtrace.finish(r.trace, "ok")
                    r.future.set_result(res)
                if finished:
                    with self.stats.lock:
                        self.stats.completed += len(finished)
                if self._on_iteration is not None:
                    try:
                        self._on_iteration(self)
                    except Exception as e:  # hooks never kill serving
                        print(f"serving on_iteration hook failed: {e}")
            except Exception as e:
                # one bad iteration (including an injected serve_batch
                # fault): fail the RESIDENTS, reset the slots, keep
                # serving the queue
                self._fail_residents(e, died=False)
            except BaseException as e:
                self._fail_residents(e, died=True)
                self._die(e)
                return

    def _fail_residents(self, error: BaseException, died: bool) -> None:
        requests = self.scheduler._abort_residents()
        if not requests:
            return
        with self.stats.lock:
            self.stats.failed += len(requests)
        what = "scheduler died" if died else f"{type(error).__name__}"
        for r in requests:
            if not r.future.done():
                r.future.meta = reqtrace.finish(
                    r.trace, "failed", reason=f"{what}: {error}")
                r.future.set_error(error)

    def _die(self, error: BaseException) -> None:
        with self._cv:
            self._closed = True
            pending, self._queue = self._queue, []
            with self.stats.lock:
                self.stats.queue_depth = 0
                self.stats.failed += len(pending)
            self._cv.notify_all()
        for r in pending:
            if not r.future.done():
                r.future.meta = reqtrace.finish(
                    r.trace, "failed",
                    reason=f"scheduler died: {error}")
                r.future.set_error(RejectedError(
                    f"scheduler died: {error}",
                    request_id=r.request_id))
        print(f"serving scheduler died: {type(error).__name__}: {error}")

    def _expiry_loop(self) -> None:
        while True:
            with self._cv:
                if self._closed and not self._queue:
                    return
                self._expire_locked()
                if self._queue:
                    wake = min(r.deadline for r in self._queue)
                    self._cv.wait(
                        max(wake - time.monotonic(), 0.0) + 1e-3)
                else:
                    self._cv.wait(0.05)

    # ----------------------------------------------------------- admin

    @property
    def closed(self) -> bool:
        with self._cv:
            return self._closed

    def close(self, drain: bool = True) -> None:
        """Stop the scheduler. ``drain=True`` finishes the residents
        AND the queue first; False rejects the queue (residents still
        finish — there is no preemption to cut them short)."""
        with self._cv:
            self._closed = True
            if not drain:
                pending, self._queue = self._queue, []
                for r in pending:
                    r.future.meta = reqtrace.finish(
                        r.trace, "rejected_closed",
                        reason="batcher closed")
                    r.future.set_error(RejectedError(
                        "batcher closed", request_id=r.request_id))
                with self.stats.lock:
                    self.stats.queue_depth = 0
            self._cv.notify_all()
        self._sched.join(timeout=30)
