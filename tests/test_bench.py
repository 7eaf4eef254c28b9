"""bench.py phases exercised on the 8-device virtual mesh (weak spot from
round 1: the multi-chip branch only ran when real hardware had >1 chip).
Constants are shrunk via monkeypatch; the point is that every branch —
mesh build, sharded prefetch staging, dp eval on the device-resident test
set, the feed-dict baseline — compiles and executes, not the numbers."""

import time

import jax
import numpy as np
import pytest

_PRNG_BEFORE_BENCH_IMPORT = jax.config.jax_default_prng_impl

import bench  # noqa: E402 — the capture above must precede this import
from distributed_tensorflow_tpu.data import read_data_sets


@pytest.fixture(scope="module")
def ds(tmp_path_factory):
    # synthetic (no IDX files in the tmp dir); 2000-example test split is
    # divisible by 8 so convergence_phase takes the dp-eval branch
    return read_data_sets(str(tmp_path_factory.mktemp("no-data")), one_hot=True)


# the 8-mesh arm compiles the full host-fed wire path over the virtual
# mesh — 341s on the r23 tier-1 audit, the single largest line in the
# kill window, for a link-bound rate DTP001 exempts from banding; the
# 1-chip arm keeps the phase's tier-1 coverage
@pytest.mark.parametrize(
    "n_chips", [1, pytest.param(8, marks=pytest.mark.slow)])
def test_throughput_phase_runs(monkeypatch, ds, n_chips):
    monkeypatch.setattr(bench, "PER_CHIP_BATCH", 16)
    monkeypatch.setattr(bench, "WIRE_TIMED_STEPS", 4)
    rate = bench.throughput_phase(ds, n_chips)
    assert rate > 0 and np.isfinite(rate)


@pytest.mark.parametrize("n_chips", [1, 8])
def test_device_resident_phase_runs(monkeypatch, ds, n_chips):
    monkeypatch.setattr(bench, "PER_CHIP_BATCH", 16)
    monkeypatch.setattr(bench, "CHUNK", 3)
    monkeypatch.setattr(bench, "TIMED_CHUNKS", 2)
    rate = bench.device_resident_phase(ds, n_chips)
    assert rate > 0 and np.isfinite(rate)


@pytest.mark.parametrize("n_chips", [1, 8])
def test_convergence_phase_runs(monkeypatch, ds, n_chips):
    monkeypatch.setattr(bench, "CONVERGE_BATCH", 16)
    monkeypatch.setattr(bench, "CONVERGE_MAX_STEPS", 12)
    monkeypatch.setattr(bench, "CONVERGE_EVAL_EVERY", 6)
    out = bench.convergence_phase(ds, n_chips)
    assert 0.0 <= out["test_accuracy"] <= 1.0
    assert out["target_accuracy"] == bench.TARGET_ACC
    # 12 tiny steps will not reach 99%; the fields must say so honestly
    if out["seconds_to_target"] is None:
        assert out["steps_to_target"] is None


def test_resnet_phase_runs(monkeypatch, tmp_path):
    monkeypatch.setattr(bench, "RESNET_PER_CHIP_BATCH", 4)
    monkeypatch.setattr(bench, "RESNET_TIMED_CHUNKS", 1)
    monkeypatch.setattr(bench, "RESNET_CHUNK", 2)
    # hermetic: an empty data_dir pins the synthetic CIFAR fallback
    rate, source = bench.resnet_phase(8, data_dir=str(tmp_path / "no-cifar"))
    assert rate > 0 and np.isfinite(rate)
    assert source == "synthetic"


def test_ps_emulation_phase_runs(monkeypatch, ds):
    monkeypatch.setattr(bench, "PS_BATCH", 16)
    monkeypatch.setattr(bench, "PS_STEPS", 3)
    rate = bench.ps_emulation_phase(ds)
    assert rate > 0 and np.isfinite(rate)


def test_feeddict_baseline_runs(monkeypatch, ds):
    monkeypatch.setattr(bench, "FEEDDICT_BATCH", 16)
    monkeypatch.setattr(bench, "FEEDDICT_STEPS", 3)
    rate = bench.feeddict_baseline_phase(ds, 8)
    assert rate > 0 and np.isfinite(rate)


def test_sync_every_matches_backend():
    assert bench._sync_every(1) == 0
    expected = 16 if jax.default_backend() == "cpu" else 0
    assert bench._sync_every(8) == expected


def test_bench_import_does_not_flip_global_prng():
    """Regression: bench.py selects the rbg PRNG inside main() (scoped),
    not at import time — this module imports bench, and a module-level
    config flip leaked rbg into every test module collected afterwards
    (changing init distributions under other tests' seeds). Assert the
    import left the impl exactly as it found it."""
    assert jax.config.jax_default_prng_impl == _PRNG_BEFORE_BENCH_IMPORT


def test_convergence_phase_fashion_target(monkeypatch, ds):
    """The fashion phase reuses convergence_phase with its own target and
    budget; the reported target_accuracy must follow the parameter.
    CONVERGE_BATCH shrinks like its siblings above — at the default 128
    this single test paid minutes of bf16-emulated CPU chunks (the r23
    tier-1 audit's worst offender) for an assertion about parameter
    plumbing."""
    monkeypatch.setattr(bench, "CONVERGE_BATCH", 16)
    monkeypatch.setattr(bench, "CONVERGE_EVAL_EVERY", 5)
    out = bench.convergence_phase(ds, 1, target_acc=0.5, max_steps=20)
    assert out["target_accuracy"] == 0.5
    assert out["steps_to_target"] is None or out["steps_to_target"] <= 20


def test_lm_longctx_phase_runs(monkeypatch):
    monkeypatch.setattr(bench, "LM_SEQ_LEN", 64)
    monkeypatch.setattr(bench, "LM_BATCH", 4)
    monkeypatch.setattr(bench, "LM_D_MODEL", 32)
    monkeypatch.setattr(bench, "LM_ATTN_BLOCK", 16)
    monkeypatch.setattr(bench, "LM_TIMED_STEPS", 2)
    out = bench.lm_longctx_phase()
    assert out["lm_4k_tokens_per_sec_per_chip"] > 0
    assert out["lm_seq_len"] == 64


# ---- no record without the chip: main() fails off the TPU platform and
# when a phase raises; nothing is printed in place of a result ----

def test_degraded_record_shape():
    """Pin the host-only record's shape: headline keys present (null),
    the tpu_unavailable flag and the error — and the whole thing must
    survive a json round-trip as one line."""
    import json

    rec = bench.degraded_record(
        "jax.errors.JaxRuntimeError: UNAVAILABLE: no device")
    line = json.dumps(rec)
    assert "\n" not in line
    back = json.loads(line)
    assert back["tpu_unavailable"] is True
    assert back["metric"] == "mnist_images_per_sec_per_chip"
    assert back["value"] is None and back["vs_baseline"] is None
    assert back["unit"] == "images/sec/chip"
    assert "UNAVAILABLE" in back["error"]


def test_degraded_record_keeps_partial_results():
    """Partial fields override the nulls."""
    rec = bench.degraded_record(
        "RuntimeError: phase failed",
        partial={"value": 747600.0, "n_chips": 1, "data_source": "synthetic"})
    assert rec["tpu_unavailable"] is True
    assert rec["value"] == 747600.0
    assert rec["n_chips"] == 1


def _json_lines(text: str) -> list:
    return [l for l in text.splitlines() if l.lstrip().startswith("{")]


def test_main_exits_nonzero_off_the_chip(monkeypatch, capsys):
    """The tests' platform is the CPU: main() names it on its first
    line, runs no phase, prints no record and exits non-zero."""
    ran = []
    monkeypatch.setattr(bench, "_run_phases", ran.append)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code) and "'tpu'" in str(exc.value.code)
    out = capsys.readouterr().out
    assert out.splitlines()[0].startswith("bench: platform=cpu ")
    assert ran == [] and _json_lines(out) == []


_V5E = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_chips": 1}


def test_main_phase_failure_propagates_with_no_record(monkeypatch, capsys):
    """A phase that raises ends the run non-zero with its own
    exception; the fields finished before it are not printed as a
    record with a null headline."""
    monkeypatch.setattr(bench, "_require_tpu", lambda: dict(_V5E))

    def exploding_phases(out):
        out["value"] = 123.4
        raise RuntimeError("UNAVAILABLE: socket closed")

    monkeypatch.setattr(bench, "_run_phases", exploding_phases)
    with pytest.raises(RuntimeError, match="socket closed"):
        bench.main()
    assert _json_lines(capsys.readouterr().out) == []


def test_main_record_carries_the_device(monkeypatch, capsys):
    """Past the gate the record starts from what JAX reported —
    platform and device_kind beside n_chips — so no record can be
    mistaken for a run on another device."""
    import json

    monkeypatch.setattr(bench, "_require_tpu", lambda: dict(_V5E))
    monkeypatch.setattr(bench, "_run_phases",
                        lambda out: print(json.dumps(out)))
    bench.main()
    rec = json.loads(_json_lines(capsys.readouterr().out)[-1])
    assert rec == _V5E


def _shrink_ppep(monkeypatch):
    monkeypatch.setattr(bench, "PP_EP_SEQ_LEN", 32)
    monkeypatch.setattr(bench, "PP_EP_VOCAB", 16)
    monkeypatch.setattr(bench, "PP_EP_D_MODEL", 32)
    monkeypatch.setattr(bench, "PP_EP_SPLIT", 64)
    monkeypatch.setattr(bench, "PP_EP_BATCH_PER_DATA_WAY", 4)
    monkeypatch.setattr(bench, "PP_EP_CHUNK", 2)
    monkeypatch.setattr(bench, "PP_EP_TIMED_CHUNKS", 1)


@pytest.mark.slow  # the compile-heavy phase bodies; the mesh paths they
                   # drive are tier-1-covered by tests/test_device_pp_ep.py
def test_pp_device_phase_runs(monkeypatch):
    _shrink_ppep(monkeypatch)
    out = bench.pp_device_phase(8)
    assert out["pp_images_per_sec_per_chip"] > 0
    assert out["pp_device_stages"] == 4
    # r7: same-session schedule A/B + analytic facts ride along
    assert out["pp_gpipe_images_per_sec_per_chip"] > 0
    assert out["pp_schedule"] == "interleaved"
    assert out["pp_virtual_stages"] == 2
    assert out["pp_interleave_speedup"] is not None


@pytest.mark.slow
def test_ep_device_phase_runs(monkeypatch):
    _shrink_ppep(monkeypatch)
    out = bench.ep_device_phase(8)
    assert out["ep_tokens_per_sec_per_chip"] > 0
    assert out["ep_device_experts"] == bench.PP_EP_EXPERTS


def test_ppep_phases_skip_on_one_chip():
    """1 chip has no model axis: the phases must report null metrics
    with a reason, not crash (the r5 hardened-artifact pattern)."""
    pp, ep = bench.pp_device_phase(1), bench.ep_device_phase(1)
    assert pp["pp_images_per_sec_per_chip"] is None
    assert ep["ep_tokens_per_sec_per_chip"] is None
    assert "pp_device_skipped" in pp and "ep_device_skipped" in ep


def test_degraded_record_nulls_ppep_keys():
    """Outage artifacts carry the PP/EP headline keys as nulls so the
    driver's schema stays stable across outages."""
    rec = bench.degraded_record("UNAVAILABLE")
    assert rec["pp_images_per_sec_per_chip"] is None
    assert rec["ep_tokens_per_sec_per_chip"] is None


def test_pp_schedule_facts_match_analytic_formula():
    """The BENCH schedule facts must equal the analytic bubble formula
    M*V/(M*V + K - 1) for the phase's (K, M=K, V) config — the
    acceptance pin that the recorded fraction is the real cost model,
    not a hand-typed constant."""
    for ways in (2, 4):
        facts = bench._pp_schedule_facts(ways)
        v = facts["pp_virtual_stages"]
        m = ways  # the phase runs microbatches = stage count
        assert facts["pp_useful_tick_fraction"] == round(
            m * v / (m * v + ways - 1), 4)
        assert facts["pp_schedule"] == ("interleaved" if v > 1
                                        else "gpipe")
        # PP_NUM_BLOCKS=8 gives both the 2- and 4-way axes a V=2 run
        assert v == 2


def test_degraded_record_keeps_schedule_facts_non_null():
    """The host-only record nulls the rates, and the ANALYTIC schedule
    facts stay in it."""
    rec = bench.degraded_record("UNAVAILABLE: no device")
    assert rec["pp_images_per_sec_per_chip"] is None
    assert rec["pp_schedule"] == "interleaved"
    assert rec["pp_virtual_stages"] == 2
    # 2-way fallback config: K=2, M=2, V=2 -> 4/5
    assert rec["pp_useful_tick_fraction"] == 0.8
    # r16: the static-analysis facts ride the degraded record too
    # (dttlint is pure ast, no backend at all) — asserted here instead
    # of paying a second full degraded_record build
    assert rec["lint_findings_total"] == 0
    assert rec["lint_rules"] == 11
    assert rec["lint_baselined_total"] is not None
    assert rec["lint_time_s"] is not None
    # r20: the concurrency-proof facts ride the degraded record too
    # (dttsan is pure ast like dttlint — no backend at all)
    assert rec["consan_findings_total"] == 0
    assert rec["consan_threads_total"] > 0
    assert rec["consan_locks_total"] > 0
    assert rec["consan_time_s"] is not None
    # r18: the jaxpr-proof facts ride the degraded record too (the
    # dttcheck drill runs in its own CPU-mesh subprocess, no backend
    # dependence; per-process cache makes this ride-along free here)
    assert rec["jaxprcheck_findings_total"] == 0
    assert rec["jaxprcheck_modes_proven"] == 8
    assert rec["jaxprcheck_collectives_total"] > 0
    assert rec["jaxprcheck_time_s"] is not None
    # r23: the performance-contract facts ride the degraded record too
    # (dttperf is pure Python + eval_shape; per-process cache makes
    # this ride-along free here — DTP002 enforces the wiring statically)
    assert rec["perfcheck_findings_total"] == 0
    assert rec["perfcheck_scenarios_proven"] >= 13
    assert rec["perfcheck_band_pct"] is not None
    assert rec["perfcheck_time_s"] is not None


def test_degraded_record_keeps_router_facts_non_null():
    """r22: the fleet-router drill is host-only (LocalTransport, no
    chip), so its facts must survive outages — non-null in EVERY
    record, degraded included."""
    rec = bench.degraded_record("UNAVAILABLE: no device")
    assert rec["router_replicas"] == 2
    assert rec["router_healthy"] is not None
    assert rec["router_ejections"] >= 1  # the breaker drill tripped
    assert rec["router_retries"] is not None
    assert rec["router_hedges"] >= 1  # the hedge drill fired
    assert rec["router_overhead_ms"] is not None
    assert "router_error" not in rec


def test_pp_skip_record_carries_schedule_facts():
    """Even the 1-chip skip record reports the (analytic) schedule
    facts alongside its null rates."""
    pp = bench.pp_device_phase(1)
    assert pp["pp_images_per_sec_per_chip"] is None
    assert pp["pp_gpipe_images_per_sec_per_chip"] is None
    assert pp["pp_schedule"] == "interleaved"
    assert pp["pp_useful_tick_fraction"] == 0.8


def test_lm_largevocab_phase_runs(monkeypatch):
    monkeypatch.setattr(bench, "LM_BIGV_VOCAB", 512)
    monkeypatch.setattr(bench, "LM_BIGV_SEQ_LEN", 64)
    monkeypatch.setattr(bench, "LM_BIGV_BATCH", 2)
    monkeypatch.setattr(bench, "LM_BIGV_CE_BLOCK", 16)
    monkeypatch.setattr(bench, "LM_BIGV_TIMED_STEPS", 2)
    monkeypatch.setattr(bench, "LM_D_MODEL", 32)
    monkeypatch.setattr(bench, "LM_ATTN_BLOCK", 16)
    out = bench.lm_largevocab_phase()
    assert out["lm_bigvocab_tokens_per_sec_per_chip"] > 0
    assert out["lm_bigvocab_vocab"] == 512
    assert out["lm_bigvocab_seq_len"] == 64


# ---- r10: the dp_zero phase (replicated vs --zero 1 A/B + analytic
# memory facts; the facts must survive outages and 1-chip skips) ----


_ZERO_ANALYTIC_KEYS = (
    "zero_data_ways", "zero_opt_bytes_per_chip",
    "zero_opt_bytes_per_chip_replicated", "zero_opt_reduction",
    "zero3_param_bytes_per_chip", "zero_param_reduction",
    "zero_comm_bytes_allreduce", "zero_comm_bytes_reduce_scatter_gather",
    "zero_live_bytes_per_chip", "dp_live_bytes_per_chip",
    "zero_live_bytes_source",
)


@pytest.mark.slow
def test_dp_zero_phase_runs(monkeypatch, ds):
    monkeypatch.setattr(bench, "PER_CHIP_BATCH", 8)
    monkeypatch.setattr(bench, "CHUNK", 2)
    monkeypatch.setattr(bench, "ZERO_TIMED_CHUNKS", 2)
    out = bench.dp_zero_phase(ds, 8)
    assert out["zero_images_per_sec_per_chip"] > 0
    assert out["dp_ab_images_per_sec_per_chip"] > 0
    assert out["zero_data_ways"] == 8
    assert out["zero_opt_reduction"] >= 7.9
    for k in _ZERO_ANALYTIC_KEYS:
        assert out[k] is not None, k
    # CPU backend has no memory_stats -> the analytic totals stand in
    assert out["zero_live_bytes_source"] in ("analytic", "memory_stats")


def test_dp_zero_phase_skips_on_one_chip(ds):
    """1 chip = nothing to shard over: null rates with a reason, the
    analytic facts (2-way fallback config) still present."""
    out = bench.dp_zero_phase(ds, 1)
    assert out["zero_images_per_sec_per_chip"] is None
    assert out["dp_ab_images_per_sec_per_chip"] is None
    assert "zero_skipped" in out
    assert out["zero_data_ways"] == 2
    assert out["zero_opt_reduction"] >= 1.9


def test_degraded_record_keeps_zero_facts_non_null():
    """Outage artifacts null the measured A/B rates but carry every
    analytic ZeRO memory/comm fact (the r8-r9 hardened-artifact
    convention)."""
    rec = bench.degraded_record("UNAVAILABLE: no device")
    assert rec["zero_images_per_sec_per_chip"] is None
    assert rec["dp_ab_images_per_sec_per_chip"] is None
    for k in _ZERO_ANALYTIC_KEYS:
        assert rec[k] is not None, k
    # r14: the overlap phase's analytic facts ride the same record —
    # measured A/B rates null, schedule fractions/exposure non-null
    for key in bench._OVERLAP_RATE_KEYS:
        assert rec[key] is None, key
    for k in _OVERLAP_ANALYTIC_KEYS:
        assert rec[k] is not None, k
    assert rec["pp_zb_useful_tick_fraction"] > \
        rec["pp_interleaved_useful_tick_fraction"]
    assert rec["zero_live_bytes_source"] == "analytic"
    assert rec["zero_data_ways"] == 2


# ---- r14: the overlap phase (pipeline-schedule A/B + ZeRO comm
# overlap; the analytic fractions/exposure must survive outages) ----


_OVERLAP_ANALYTIC_KEYS = (
    "pp_gpipe_useful_tick_fraction",
    "pp_interleaved_useful_tick_fraction",
    "pp_zb_useful_tick_fraction", "pp_zb_ticks",
    "zero_overlap_bucket_mb", "zero_overlap_buckets",
    "zero1_exposed_comm_bytes_serial", "zero1_exposed_comm_bytes_overlap",
    "zero3_exposed_comm_bytes_serial", "zero3_exposed_comm_bytes_overlap",
)


def test_overlap_analytic_facts_pin_the_acceptance():
    """The chip-free half of the r14 acceptance: zb's useful-tick
    fraction strictly exceeds interleaved at the SAME (K, M, V), and
    the overlapped exposure is strictly below the serial exposure at
    both ZeRO levels."""
    out = bench._overlap_analytic_facts(2, 8)
    for k in _OVERLAP_ANALYTIC_KEYS:
        assert out[k] is not None, k
    assert out["pp_zb_useful_tick_fraction"] > \
        out["pp_interleaved_useful_tick_fraction"] > \
        out["pp_gpipe_useful_tick_fraction"]
    for lv in (1, 3):
        assert out[f"zero{lv}_exposed_comm_bytes_overlap"] < \
            out[f"zero{lv}_exposed_comm_bytes_serial"]


@pytest.mark.slow
def test_overlap_phase_runs(monkeypatch, ds):
    monkeypatch.setattr(bench, "PER_CHIP_BATCH", 8)
    monkeypatch.setattr(bench, "CHUNK", 2)
    monkeypatch.setattr(bench, "OVERLAP_TIMED_CHUNKS", 1)
    _shrink_ppep(monkeypatch)
    monkeypatch.setattr(bench, "PP_NUM_BLOCKS", 8)
    out = bench.overlap_phase(ds, 8)
    for key in bench._OVERLAP_RATE_KEYS:
        assert out[key] is not None and out[key] > 0, key
    for k in _OVERLAP_ANALYTIC_KEYS:
        assert out[k] is not None, k


def test_overlap_phase_skips_on_one_chip(ds):
    out = bench.overlap_phase(ds, 1)
    for key in bench._OVERLAP_RATE_KEYS:
        assert out[key] is None, key
    assert "overlap_skipped" in out
    assert out["pp_zb_useful_tick_fraction"] > \
        out["pp_interleaved_useful_tick_fraction"]


# (the degraded-record assertions for the overlap keys ride the
# existing test_degraded_record_keeps_zero_facts_non_null record build
# — one degraded-record construction, not two)


def test_lint_phase_runs_clean_and_fast():
    """r16: the dttlint drill — zero non-baselined findings with the
    checked-in baseline, all eleven rules (DTT009 since r18, DTT010
    since r20, DTT011 since r23), inside the <10s acceptance budget
    (pure ast, no chip)."""
    out = bench.lint_phase()
    assert out["lint_findings_total"] == 0, out
    assert out["lint_stale_suppressions"] == 0
    assert out["lint_rules"] == 11
    assert out["lint_baselined_total"] >= 0
    assert out["lint_time_s"] < 10.0
    assert "lint_error" not in out
    # the degraded-record ride-along is asserted in
    # test_degraded_record_keeps_schedule_facts_non_null (one shared
    # degraded_record build instead of two)


def test_consan_phase_runs_clean_and_fast():
    """r20: the dttsan drill — zero non-baselined findings (stale
    suppressions count as findings here: either way the gate is dirty)
    with the checked-in baseline + thread registry, inside the <15s
    acceptance budget (pure ast, no chip), with the thread/lock census
    non-null."""
    out = bench.consan_phase()
    assert out["consan_findings_total"] == 0, out
    assert out["consan_threads_total"] > 0
    assert out["consan_locks_total"] > 0
    assert out["consan_shared_attrs"] > 0
    assert out["consan_baselined_total"] >= 0
    assert out["consan_time_s"] < 15.0
    assert "consan_error" not in out
    # the degraded-record ride-along is asserted in
    # test_degraded_record_keeps_schedule_facts_non_null (one shared
    # degraded_record build instead of two)


def test_jaxprcheck_phase_proves_the_full_matrix():
    """r18: the dttcheck drill — the comm ledgers and SPMD safety
    machine-proven against the lowered computation for ALL EIGHT modes
    in the phase's own CPU-mesh subprocess, zero findings. Cached per
    process (the degraded record re-emits the same facts free)."""
    out = bench.jaxprcheck_phase()
    assert out["jaxprcheck_findings_total"] == 0, out
    assert out["jaxprcheck_modes_proven"] == 8
    assert out["jaxprcheck_collectives_total"] > 0
    assert out["jaxprcheck_time_s"] is not None
    assert "jaxprcheck_error" not in out
    # the per-process cache: a second call must not pay the subprocess
    t0 = time.perf_counter()
    again = bench.jaxprcheck_phase()
    assert time.perf_counter() - t0 < 1.0
    assert again == out


def test_perfcheck_phase_proves_the_contract():
    """r23: the dttperf drill — the full (mode x model) prediction
    matrix priced and banded against the checked-in records with zero
    non-baselined findings, and the facts non-null (host-only: pure
    Python + eval_shape, no chip). Cached per process like jaxprcheck;
    the degraded record re-emits the same facts free — asserted here
    to spare a full degraded_record build."""
    out = bench.perfcheck_phase()
    assert out["perfcheck_findings_total"] == 0, out
    assert out["perfcheck_scenarios_proven"] >= 13
    assert out["perfcheck_band_pct"] is not None
    assert out["perfcheck_time_s"] is not None
    assert "perfcheck_error" not in out
    # the per-process cache: a second call must not re-pay the matrix
    t0 = time.perf_counter()
    again = bench.perfcheck_phase()
    assert time.perf_counter() - t0 < 1.0
    assert again == out
    # the degraded-record ride-along is asserted in
    # test_degraded_record_keeps_schedule_facts_non_null (one shared
    # degraded_record build instead of two)
