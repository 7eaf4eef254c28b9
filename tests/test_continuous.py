"""Continuous batching (r21, serving/continuous.py + kvpage.py): the
free-list page allocator's ledger, the iteration-level scheduler's
state machine on the host backend, per-request bitwise parity with
whole-batch ``generate()`` on mixed-length workloads, ``sum(phases) ==
wall`` under mid-batch admission/retirement (including rejections and
expiries), the recompile-sentry budget, /metrics' ``hbm.kv_pages``
block and the /healthz page drain floor, drain-to-swap refresh, the
bench phase's analytic facts, and the loadgen long-tail/knee helpers."""

import threading
import time

import numpy as np
import pytest

from distributed_tensorflow_tpu.checkpoint import save_checkpoint
from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher,
    EngineSlotBackend,
    HostSlotBackend,
    InferenceEngine,
    InferenceServer,
    InProcessClient,
    PageAllocator,
    RejectedError,
    pages_needed,
)
from distributed_tensorflow_tpu.serving import reqtrace
from distributed_tensorflow_tpu.training import create_train_state, sgd
from distributed_tensorflow_tpu.utils import faults, resources, telemetry

VOCAB, SEQ, DM, HEADS, BLOCKS = 32, 64, 16, 2, 1


@pytest.fixture(autouse=True)
def _clean_plane_and_faults():
    """Same hygiene as test_reqtrace: no plane, no faults, no active
    sentry leaks across tests (all three are process-global)."""
    faults.reset()
    prev_plane = reqtrace.get_plane()
    tracer = telemetry.get_tracer()
    prev_enabled = tracer.enabled
    prev_meter = resources.active_meter()
    prev_sentry = resources.active_sentry()
    yield
    faults.reset()
    reqtrace._PLANE = prev_plane
    tracer.enabled = prev_enabled
    telemetry.configure(logdir=None, enabled=prev_enabled)
    resources.activate(meter=prev_meter, sentry=prev_sentry)


@pytest.fixture
def plane():
    return reqtrace.configure(enabled=True, slo_p99_ms=60_000.0)


def _batcher(backend, **kw):
    cfg = dict(queue_depth=64, default_timeout_ms=30_000.0)
    cfg.update(kw)
    return ContinuousBatcher(backend, **cfg)


def _host_reference(backend: HostSlotBackend, prompt, n: int):
    """Single-request greedy decode against the host backend's math —
    the whole-batch analogue the scheduler must reproduce bitwise."""
    seq = [int(t) for t in prompt]
    p = len(seq)
    for pos in range(p + n - 1):
        logits = (backend._emb[seq[pos]]
                  + np.float32(pos)) @ backend._head
        if pos >= p - 1:
            seq.append(int(logits.argmax()))
    return np.asarray(seq, np.int32)


# ------------------------------------------------------ page allocator


def test_pages_needed():
    assert pages_needed(0, 16) == 0
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2
    with pytest.raises(ValueError):
        pages_needed(-1, 16)
    with pytest.raises(ValueError):
        pages_needed(4, 0)


def test_allocator_commit_then_alloc_ledger():
    a = PageAllocator(num_pages=4, page_size=16)
    assert a.can_admit(33)            # 3 pages
    res = a.reserve(33)
    occ = a.occupancy()
    assert occ["pages_committed"] == 3 and occ["pages_in_use"] == 0
    assert occ["free_pct"] == 25.0    # committed, not in-use, drains
    assert not a.can_admit(17)        # 2 more pages won't fit
    assert a.can_admit(16)
    pages = [a.alloc(res), a.alloc(res), a.alloc(res)]
    assert 0 not in pages             # page 0 is the scratch page
    assert len(set(pages)) == 3
    with pytest.raises(RuntimeError):  # budget exhausted
        a.alloc(res)
    occ = a.occupancy()
    assert occ["pages_in_use"] == 3 and occ["pages_high_water"] == 3
    a.release(res)
    a.release(res)                    # idempotent
    occ = a.occupancy()
    assert occ["pages_in_use"] == 0 and occ["pages_committed"] == 0
    assert occ["free_pct"] == 100.0
    assert occ["pages_high_water"] == 3   # high water survives release


def test_allocator_overcommit_is_a_loud_bug():
    a = PageAllocator(num_pages=2, page_size=8)
    a.reserve(16)
    with pytest.raises(RuntimeError, match="can_admit"):
        a.reserve(1)


# ------------------------------------------- scheduler on the host double


def test_host_mixed_lengths_bitwise_and_ledger(plane):
    backend = HostSlotBackend(n_slots=3, capacity=64, page_size=8)
    b = _batcher(backend)
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, VOCAB, rng.integers(1, 20)).astype(np.int32),
             int(rng.integers(1, 24))) for _ in range(10)]
    try:
        futs = [b.submit(p, max_new_tokens=n) for p, n in reqs]
        for f, (p, n) in zip(futs, reqs):
            got = f.result(timeout=30)
            np.testing.assert_array_equal(
                got, _host_reference(backend, p, n))
    finally:
        b.close()
    snap = b.scheduler.snapshot()
    assert snap["page_ledger_ok"]
    assert snap["tokens_emitted"] == sum(n for _, n in reqs)
    assert 0 < snap["slot_occupancy"] <= 1.0
    kv = snap["kv_pages"]
    # paged-cache claim: the pool's high water tracks live tokens —
    # each resident wastes at most one partial page
    assert (kv["pages_high_water"] * kv["page_size"]
            < snap["live_tokens_high_water"]
            + backend.n_slots * kv["page_size"])
    assert kv["pages_in_use"] == 0 and kv["pages_committed"] == 0


def test_sum_phases_equals_wall_under_mid_batch_admission(plane):
    backend = HostSlotBackend(n_slots=2, capacity=64, page_size=8,
                              step_cost=lambda: time.sleep(0.002))
    b = _batcher(backend)
    try:
        f_long = b.submit(np.array([1, 2, 3], np.int32),
                          max_new_tokens=30)
        time.sleep(0.02)  # the long request is mid-decode...
        f_short = b.submit(np.array([4, 5], np.int32), max_new_tokens=3)
        long_toks = f_long.result(timeout=30)
        short_toks = f_short.result(timeout=30)
    finally:
        b.close()
    assert len(long_toks) == 33 and len(short_toks) == 5
    # the short request admitted mid-batch and retired first; both
    # timelines stay exhaustive
    assert f_short.meta["slot"] != f_long.meta["slot"]
    assert f_short.meta["iter_admit"] > f_long.meta["iter_admit"]
    assert f_short.meta["iter_retire"] < f_long.meta["iter_retire"]
    assert len(plane.audit) == 2
    for s in plane.audit:
        assert s["disposition"] == "ok"
        assert {"admit", "queue_wait", "prefill", "decode",
                "respond"} <= set(s["phases_ms"])
        assert sum(s["phases_ms"].values()) == pytest.approx(
            s["total_ms"], abs=0.05)
        assert s["iter_retire"] >= s["iter_admit"] >= 0


def test_rejection_expiry_and_fault_timelines_complete(plane):
    # 2 slots pinned by long generations + queue_depth 1: the third
    # request queues and expires, the fourth is shed
    backend = HostSlotBackend(n_slots=2, capacity=64, page_size=8,
                              step_cost=lambda: time.sleep(0.002))
    b = _batcher(backend, queue_depth=1)
    try:
        futs = []
        for _ in range(2):
            futs.append(b.submit(np.array([1, 2], np.int32),
                                 max_new_tokens=40))
            deadline = time.monotonic() + 5
            while (b.stats.as_dict()["queue_depth"]
                   and time.monotonic() < deadline):
                time.sleep(0.002)   # wait for slot admission
        f_exp = b.submit(np.array([3], np.int32), max_new_tokens=2,
                         timeout_ms=20)
        with pytest.raises(RejectedError, match="queue full"):
            b.submit(np.array([4], np.int32), max_new_tokens=2)
        with pytest.raises(RejectedError):
            f_exp.result(timeout=10)
        assert f_exp.meta["disposition"] == "expired"
        faults.configure("serve_admit:mode=error:times=1")
        with pytest.raises(RejectedError, match="admission fault"):
            b.submit(np.array([5], np.int32), max_new_tokens=2)
        for f in futs:
            f.result(timeout=30)
    finally:
        faults.reset()
        b.close()
    by_disp = {s["disposition"]: s for s in plane.audit}
    assert {"ok", "expired", "rejected_full",
            "rejected_fault"} <= set(by_disp)
    for s in plane.audit:   # EVERY exit keeps the exhaustive-sum pin
        assert sum(s["phases_ms"].values()) == pytest.approx(
            s["total_ms"], abs=0.05)
    assert "queue_wait" in by_disp["expired"]["phases_ms"]


def test_validation_rejects_loudly_at_submit(plane):
    b = _batcher(HostSlotBackend(n_slots=2, capacity=32, page_size=8))
    try:
        with pytest.raises(ValueError, match="exceeds"):
            b.submit(np.arange(30, dtype=np.int32) % VOCAB,
                     max_new_tokens=10)
        with pytest.raises(ValueError, match="max_new_tokens"):
            b.submit(np.array([1], np.int32), max_new_tokens=0)
        with pytest.raises(ValueError, match="ids must be"):
            b.submit(np.array([99], np.int32), max_new_tokens=2)
    finally:
        b.close()
    assert [s["disposition"] for s in plane.audit] == ["failed"] * 3


def test_close_paths():
    backend = HostSlotBackend(n_slots=2, capacity=64, page_size=8,
                              step_cost=lambda: time.sleep(0.002))
    b = _batcher(backend, queue_depth=8)
    futs = [b.submit(np.array([1, 2], np.int32), max_new_tokens=12)
            for _ in range(5)]
    b.close(drain=True)   # drain finishes residents AND queue
    assert all(len(f.result(timeout=5)) == 14 for f in futs)
    assert b.closed
    with pytest.raises(RejectedError, match="closed"):
        b.submit(np.array([1], np.int32), max_new_tokens=2)

    b2 = _batcher(HostSlotBackend(
        n_slots=2, capacity=64, page_size=8,
        step_cost=lambda: time.sleep(0.005)), queue_depth=8)
    futs2 = [b2.submit(np.array([1, 2], np.int32), max_new_tokens=40)
             for _ in range(4)]
    deadline = time.monotonic() + 5
    while (b2.stats.as_dict()["queue_depth"] == 4
           and time.monotonic() < deadline):
        time.sleep(0.002)   # wait until the slots fill
    b2.close(drain=False)  # rejects the QUEUE; residents still finish
    results = []
    for f in futs2:
        try:
            results.append(("ok", len(f.result(timeout=30))))
        except RejectedError:
            results.append(("rejected", None))
    assert ("ok", 42) in results and ("rejected", None) in results


def test_drain_to_swap_refreshes_only_with_zero_residents():
    class SwapBackend(HostSlotBackend):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.pending_swap = False
            self.refreshes = []

        def wants_refresh(self):
            return self.pending_swap

        def refresh(self):
            self.refreshes.append(self.sched._has_residents())
            self.pending_swap = False

    backend = SwapBackend(n_slots=2, capacity=64, page_size=8,
                          step_cost=lambda: time.sleep(0.002))
    b = _batcher(backend)
    backend.sched = b.scheduler
    try:
        f1 = b.submit(np.array([1, 2], np.int32), max_new_tokens=20)
        time.sleep(0.01)
        backend.pending_swap = True   # hot-swap lands mid-generation
        f2 = b.submit(np.array([3], np.int32), max_new_tokens=4)
        assert len(f1.result(timeout=30)) == 22
        assert len(f2.result(timeout=30)) == 5   # admitted post-swap
        deadline = time.monotonic() + 5
        while backend.pending_swap and time.monotonic() < deadline:
            time.sleep(0.005)
        assert backend.refreshes == [False]   # swapped while empty
    finally:
        b.close()


# ------------------------------------------------- server integration


class _HostModel:
    @staticmethod
    def apply(params, x):
        return np.asarray(x) @ params["w"] + params["b"]


def test_metrics_hbm_kv_block_and_healthz_drain_floor(tmp_path):
    rng = np.random.default_rng(0)
    params = {"w": rng.standard_normal((8, 4)).astype(np.float32),
              "b": np.zeros(4, np.float32)}
    save_checkpoint(str(tmp_path), {"params": params}, 10)
    eng = InferenceEngine(_HostModel(), str(tmp_path), jit=False,
                          params_template=params, max_batch=4)
    backend = HostSlotBackend(n_slots=2, capacity=32, page_size=8,
                              num_pages=8,
                              step_cost=lambda: time.sleep(0.005))
    gb = _batcher(backend)
    srv = InferenceServer(eng, InProcessClient(None, gb), port=0,
                          hbm_headroom_floor_pct=70.0
                          ).start_background()
    try:
        # a 24-token footprint commits 3/8 pages: free_pct 62.5 < 70
        f = gb.submit(np.array([1, 2], np.int32), max_new_tokens=23)
        deadline = time.monotonic() + 5
        h = srv.healthz()
        while (h["kv_page_free_pct"] in (None, 100.0)
               and time.monotonic() < deadline):
            time.sleep(0.002)
            h = srv.healthz()
        assert h["kv_page_free_pct"] == 62.5
        assert h["kv_low_pages"] and not h["ok"]
        m = srv.metrics()
        kv = m["hbm"]["kv_pages"]
        assert kv["num_pages"] == 8 and kv["pages_committed"] == 3
        assert len(f.result(timeout=30)) == 25
        h = srv.healthz()
        assert h["ok"] and h["kv_page_free_pct"] == 100.0
        assert not h["kv_low_pages"]
    finally:
        gb.close()
        srv.close()


# --------------------------------------------- engine parity (bitwise)


def test_engine_parity_bitwise_mixed_lengths_one_signature(tmp_path):
    """THE acceptance pin: per-request greedy tokens from the
    continuous scheduler are bitwise identical to whole-batch
    ``generate()`` on a mixed-length workload — and the whole subsystem
    traces exactly one new signature however requests arrive."""
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
                          num_heads=HEADS, num_blocks=BLOCKS)
    state = create_train_state(model, sgd(0.1), seed=0)
    save_checkpoint(str(tmp_path), state, 10)
    eng = InferenceEngine(model, str(tmp_path), max_batch=4)
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, VOCAB, rng.integers(1, 14)).astype(np.int32),
             int(rng.integers(1, 18))) for _ in range(5)]
    refs = [np.asarray(eng.generate([p], max_new_tokens=n,
                                    temperature=0.0)["tokens"][0])
            for p, n in reqs]
    cs = resources.CompileSentry()
    resources.activate(sentry=cs)
    backend = EngineSlotBackend(eng, n_slots=3, page_size=8)
    b = _batcher(backend)
    try:
        futs = [b.submit(p, max_new_tokens=n) for p, n in reqs]
        for f, ref in zip(futs, refs):
            np.testing.assert_array_equal(f.result(timeout=120), ref)
    finally:
        b.close()
    assert b.scheduler.snapshot()["page_ledger_ok"]
    # recompile sentry: slot count/pool shapes are static — ONE traced
    # signature for any arrival order, occupancy, or prompt length
    assert cs.site_signatures("serve_continuous_step") == 1


def test_served_step_names_the_params_under_continuous(tmp_path, plane):
    """The wire meta names the checkpoint step that served a request
    under the continuous scheduler too (chip_smoke.py holds every
    /v1/generate answer to the trainer's last step; the whole-batch path
    stamps it through the engine, this one at retire)."""
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
                          num_heads=HEADS, num_blocks=BLOCKS)
    save_checkpoint(str(tmp_path),
                    create_train_state(model, sgd(0.1), seed=0), 10)
    eng = InferenceEngine(model, str(tmp_path), max_batch=4)
    b = _batcher(EngineSlotBackend(eng, n_slots=2, page_size=8))
    try:
        f = b.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
        assert len(f.result(timeout=120)) == 7
        assert f.meta["served_step"] == 10
    finally:
        b.close()


# ------------------------------------------------- bench + loadgen glue


def test_bench_continuous_phase_fields_non_null(monkeypatch):
    import bench

    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_STEP_S", 0.0005)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_SHORT_TOKENS", 3)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_LONG_TOKENS", 9)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_LONG_EVERY", 5)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_SLOTS", 4)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_WB_BATCH", 2)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_CAPACITY", 24)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_PAGE", 4)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_PAGES", 12)
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_RATES", (80.0, 160.0))
    monkeypatch.setattr(bench, "CONTINUOUS_BENCH_DURATION_S", 0.25)
    out = bench.continuous_batching_phase()
    assert set(out) == set(bench._CONTINUOUS_NULLS)
    # the contract the degraded-record test rides on: analytic facts
    # never null, measured facts present (null only on an A/B error,
    # which would surface as continuous_ab_error here)
    assert "continuous_error" not in out
    for key in ("kv_pages_allocated", "kv_pages_high_water",
                "kv_page_ledger_ok", "slot_occupancy",
                "tokens_per_iteration", "continuous_knee_rps",
                "whole_batch_knee_rps", "continuous_knee_ratio",
                "continuous_drops_below_knee"):
        assert out[key] is not None, key
    assert out["kv_page_ledger_ok"] is True


def test_loadgen_long_tail_mix_is_exact():
    from tools.serve_loadgen import long_tail_fn

    calls = []
    mixed = long_tail_fn(lambda: calls.append("s"),
                         lambda: calls.append("l"), long_every=10)
    for _ in range(30):
        mixed()
    assert calls.count("l") == 3
    assert [i for i, c in enumerate(calls) if c == "l"] == [9, 19, 29]
    with pytest.raises(ValueError):
        long_tail_fn(lambda: None, lambda: None, long_every=1)


def test_loadgen_knee_picks_last_sustained_rate(monkeypatch):
    from tools import serve_loadgen as slg

    seen = []

    def fake_open_loop(request_fn, *, rate_rps, duration_s,
                       max_inflight=256, slo_p99_ms=None):
        seen.append(rate_rps)
        saturated = rate_rps > 200
        return {"achieved_rps": rate_rps if not saturated else 90.0,
                "ok": int(rate_rps * duration_s),
                "rejected": 5 if saturated else 0, "errors": 0,
                "latency_ms_p99": 4.0,
                "phase_ms": {"queue_wait": {"p99": 1.5}}}

    monkeypatch.setattr(slg, "run_open_loop", fake_open_loop)
    rep = slg.knee_throughput(lambda: None, [400, 100, 200],
                              duration_s=0.5)
    assert rep["knee_rps"] == 200.0
    assert seen == [100.0, 200.0, 400.0]  # ascending, stop past failure
    assert [r["sustained"] for r in rep["sweep"]] == [True, True, False]
    assert rep["sweep"][0]["queue_wait_p99_ms"] == 1.5


@pytest.mark.slow
def test_continuous_beats_whole_batch_at_the_knee(monkeypatch):
    """The headline A/B (timing-sensitive — slow tier): at the
    adversary-scale config (CONTINUOUS_BENCH_FULL — 32-token longs,
    12 slots vs 4 dense rows, the full rate sweep) the continuous
    scheduler's knee is >= 2x whole-batch with p99 queue_wait reduced
    >= 5x and zero drops below its knee."""
    import bench

    for name, value in bench.CONTINUOUS_BENCH_FULL.items():
        monkeypatch.setattr(bench, name, value)
    out = bench.continuous_batching_phase()
    assert "continuous_error" not in out and "continuous_ab_error" not in out
    assert out["continuous_knee_ratio"] >= 2.0
    assert out["continuous_queue_wait_reduction"] >= 5.0
    assert out["continuous_drops_below_knee"] == 0
