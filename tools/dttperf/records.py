"""The measured side of the performance contract: bench-record
discovery plus the three DATA tables the passes check against.

- ``RATE_CHECKS`` (DTP001) — which measured record rates are BANDED
  against the predictor, at which (phase, mode, model) identity, and
  which are structurally EXEMPT because they are link-bound: a rate
  the host input path dominates has no term in the step-time model,
  so no honest band exists for it — exemption with the reason spelled
  out beats a band wide enough to be meaningless.
- ``PHASE_FACTS`` (DTP002) — for every host-only bench phase, the
  fact keys that must be NON-NULL in every record the phase appears
  in, the host-only record included (the established bench
  contract, now machine-enforced), plus the phase's error key: a
  record may carry null facts ONLY alongside the error key (the phase
  failed loudly and named why).
- ``PHASE_EXEMPT`` — bench phases with no dttperf-resolvable facts,
  each with the reason. dttlint DTT011 closes the loop: every public
  ``*_phase`` in bench.py must appear in exactly one of these two
  tables, so a new phase cannot ship outside the contract.

``MODEL_CONSUMES`` names the bench facts each predictor term has a
measured dual in — DTP002 proves the closure (every term's fact is
emitted by a covered phase), so the step-time model can never quietly
consume an analytic no record carries.
"""

from __future__ import annotations

import glob
import json
import os

from tools._analysis_common import REPO_ROOT


def load_records(root: str = REPO_ROOT) -> list[dict]:
    """Every ``BENCH_r*.json`` wrapper in ``root``, oldest first — none
    is a clean (empty) corpus: the tree keeps no record from another
    installation, and the benchmark PR's records will land here.
    ``parsed`` is normalized to a dict — a failed run's wrapper
    carries ``parsed: null`` and must not crash the scan."""
    out = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        try:
            raw = json.load(open(path, encoding="utf-8"))
        except (OSError, ValueError):
            continue  # an unreadable wrapper has nothing to check
        if not isinstance(raw, dict):
            continue
        out.append({
            "stem": stem,
            "path": os.path.relpath(path, root),
            "rc": raw.get("rc"),
            "parsed": raw.get("parsed") or {},
        })
    return out


#: DTP001: one row per measured rate key. ``band`` is the allowed
#: measured/predicted ratio interval (the prediction is an efficiency-
#: 1.0 ceiling, so bands sit well below 1; the 1.05 roof catches a
#: measured rate beating the analytic ceiling — an accounting bug, not
#: a miracle). ``link_bound`` rows are exempt, with the reason.
#: Calibration (earlier code, another installation — to be redone by
#: the benchmark PR): the device-resident headline sat at 0.31/0.30 of
#: ceiling; resnet20 at 0.105/0.089 (bf16 convs fuse worse than the
#: dense stack). Band floors sit ~20% under the worst calibrated
#: point, so a >20% regression becomes a named finding.
RATE_CHECKS: tuple = (
    dict(key="value", metric="mnist_images_per_sec_per_chip",
         phase="device_resident", mode="dp", model="deep_cnn",
         per_chip_batch=2048, band=(0.25, 1.05)),
    dict(key="resnet20_cifar10_images_per_sec_per_chip",
         phase="resnet", mode="dp", model="resnet20",
         per_chip_batch=512, band=(0.07, 1.05)),
    dict(key="wire_images_per_sec_per_chip",
         phase="throughput", mode="dp", model="deep_cnn",
         link_bound="host-fed wire rate: bound by the host-to-device "
                    "input path, which the step-time model has no "
                    "term for — no honest band exists"),
    dict(key="feeddict_images_per_sec_per_chip",
         phase="feeddict_baseline", mode="dp", model="deep_cnn",
         link_bound="per-step synchronous host feed (the "
                    "reference-parity baseline) — link-bound like the "
                    "wire rate"),
    dict(key="ps_emulation_images_per_sec",
         phase="ps_emulation", mode="ps", model="deep_cnn",
         link_bound="the PS pull/push cycle rides host TCP and a "
                    "full parameter transfer — link-bound by design"),
    dict(key="ps_emulation_bf16_images_per_sec",
         phase="ps_emulation", mode="ps", model="deep_cnn",
         link_bound="bf16 wire variant of the PS cycle — link-bound "
                    "like its f32 twin"),
)


#: DTP002: host-only phases and the facts that stay non-null in EVERY
#: record the phase appears in (the host-only record included). A phase
#: "appears" in a record when any of its keys or its error key is
#: present — records that predate a phase are out of scope.
PHASE_FACTS: dict = {
    "lint_phase": dict(
        keys=("lint_findings_total", "lint_baselined_total",
              "lint_stale_suppressions", "lint_rules", "lint_time_s"),
        error_key="lint_error"),
    "consan_phase": dict(
        keys=("consan_findings_total", "consan_baselined_total",
              "consan_threads_total", "consan_locks_total",
              "consan_shared_attrs", "consan_time_s"),
        error_key="consan_error"),
    "jaxprcheck_phase": dict(
        keys=("jaxprcheck_findings_total", "jaxprcheck_modes_proven",
              "jaxprcheck_collectives_total", "jaxprcheck_time_s"),
        error_key="jaxprcheck_error"),
    "perfcheck_phase": dict(
        keys=("perfcheck_findings_total", "perfcheck_scenarios_proven",
              "perfcheck_band_pct", "perfcheck_time_s"),
        error_key="perfcheck_error"),
    "efficiency_phase": dict(
        keys=("mfu", "flops_per_step", "goodput", "model_flops_per_sec",
              "mfu_peak_flops_per_sec", "mfu_peak_source",
              "efficiency_images_per_sec"),
        error_key="efficiency_error"),
    "resources_phase": dict(
        keys=("resources_hbm_live_bytes", "resources_hbm_source",
              "resources_hbm_analytic_state_bytes",
              "resources_live_vs_analytic",
              "resources_compiles_distinct_shapes",
              "resources_recompiles", "resources_compile_time_s",
              "resources_comm_bytes_dp", "resources_comm_bytes_zero1"),
        error_key="resources_error"),
    "telemetry_phase": dict(
        # telemetry_overhead_pct needs the chip A/B and is legitimately
        # null in host-only/degraded records — it is DTP003's budget
        # when measured, not a coverage fact here.
        # telemetry_span_overhead_ns: CPU time of the measuring thread
        # per span, best of 3 passes (PR 21; the wall before)
        keys=("telemetry_span_overhead_ns", "telemetry_span_budget_ns",
              "telemetry_step_host_wait_s", "telemetry_step_dispatch_s",
              "telemetry_step_device_s", "telemetry_breakdown_source"),
        error_key="telemetry_error"),
    "reqtrace_phase": dict(
        # reqtrace_record_cost_ms: CPU time of the measuring thread per
        # request record, best of 3 passes (PR 21; one pass on the wall
        # before); reqtrace_overhead_pct is that cost over the drill's
        # mean request latency on the wall
        keys=("reqtrace_requests_total", "reqtrace_complete_pct",
              "reqtrace_p99_phase", "reqtrace_slo_compliant_pct",
              "reqtrace_record_cost_ms", "reqtrace_overhead_pct"),
        error_key="reqtrace_error"),
    "recovery_phase": dict(
        keys=("recovery_restore_step", "recovery_fallback_depth",
              "recovery_quarantined", "recovery_time_s"),
        error_key="recovery_error"),
    "serving_phase": dict(
        keys=("serving_throughput_rps", "serving_p50_ms",
              "serving_p99_ms", "serving_reload_blip_ms",
              "serving_dropped"),
        error_key="serving_error"),
    "router_phase": dict(
        keys=("router_replicas", "router_healthy", "router_retries",
              "router_hedges", "router_ejections", "router_overhead_ms"),
        error_key="router_error"),
    "continuous_batching_phase": dict(
        # the knee A/B rates need wall-clock sweeps and stay null in
        # degraded records; the page-ledger facts are analytic
        keys=("kv_pages_allocated", "kv_pages_high_water",
              "kv_page_ledger_ok", "slot_occupancy",
              "tokens_per_iteration"),
        error_key="continuous_error"),
    "elastic_phase": dict(
        keys=("elastic_world", "elastic_drain_steps", "elastic_resize_s",
              "elastic_restore_step", "elastic_restore_fallback_depth",
              "elastic_epoch"),
        error_key="elastic_error"),
}


#: bench phases with nothing for dttperf to resolve — each with the
#: reason (DTT011 rejects a bare name; an unexplained exemption is an
#: unexplained hole in the contract).
PHASE_EXEMPT: dict = {
    "device_resident_phase":
        "the headline measured rate — DTP001 bands it against the "
        "predictor; it emits a rate, not analytic facts",
    "throughput_phase":
        "host-fed wire rate: link-bound (the host input path), "
        "RATE_CHECKS exempts it explicitly",
    "resnet_phase":
        "chip-gated measured rate — DTP001 bands it via RATE_CHECKS",
    "convergence_phase":
        "accuracy trajectory (seconds/steps-to-target), not a step "
        "rate — no analytic dual in the step-time model",
    "feeddict_baseline_phase":
        "reference-parity baseline over the host link — link-bound, "
        "RATE_CHECKS exempts it explicitly",
    "ps_emulation_phase":
        "host-TCP PS cycle — link-bound, RATE_CHECKS exempts it",
    "lm_longctx_phase":
        "chip-gated LM sweep; its analytic duals (FLOPs, ledger "
        "bytes) ride efficiency_phase/resources_phase facts",
    "lm_largevocab_phase":
        "chip-gated LM sweep — see lm_longctx_phase",
    "pp_device_phase":
        "chip-gated PP A/B; the analytic schedule facts "
        "(pp_useful_tick_fraction) ride _pp_schedule_facts into every "
        "record including degraded ones",
    "ep_device_phase":
        "chip-gated EP A/B — rates need >=2 chips and stay null off",
    "dp_zero_phase":
        "chip-gated ZeRO A/B; the analytic memory facts ride "
        "_zero_mem_facts into every record",
    "overlap_phase":
        "chip-gated overlap A/B; the analytic fractions ride "
        "_overlap_analytic_facts into every record",
    "telemetry_ab_phase":
        "the chip half of the telemetry A/B — its product "
        "(telemetry_overhead_pct) is DTP003's budget when measured",
}


#: the closure DTP002 proves: every term of the step-time model names
#: the bench fact that carries its measured/analytic dual. ``phase``
#: None = the fact is emitted at record level by an analytic helper
#: (checked against bench.py source), else the fact must sit in that
#: phase's PHASE_FACTS row.
MODEL_CONSUMES: tuple = (
    ("compute", "efficiency_phase", "flops_per_step"),
    ("compute", "efficiency_phase", "mfu_peak_flops_per_sec"),
    ("exposed_comm", "resources_phase", "resources_comm_bytes_dp"),
    ("exposed_comm", "resources_phase", "resources_comm_bytes_zero1"),
    ("pp_useful_fraction", None, "pp_useful_tick_fraction"),
)
