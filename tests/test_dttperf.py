"""dttperf — the performance-contract analyzer (tools/dttperf/).

Four layers: (1) the step-time predictor's term composition,
hand-pinned for the flagship CNN and LM across dp/zero/pp/tp against
the HARDWARE table; (2) the passes on SYNTHETIC corpora — a slowed
record trips DTP001 at the band edge, silent nulls trip DTP002, blown
and unmeasured budgets trip DTP003; (3) the REPO-WIDE gate: the full
matrix prices clean against the checked-in records/budgets inside the
<15s acceptance, stale suppressions fail loudly; (4) the CLI surface
(--json, --mode filtering, exit codes)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.dttperf import predict_step_time, run_perf  # noqa: E402
from tools.dttperf.model import HARDWARE  # noqa: E402
from tools.dttperf.passes import (  # noqa: E402
    pass_budgets,
    pass_conformance,
    pass_fact_coverage,
)
from tools.dttperf.records import (  # noqa: E402
    MODEL_CONSUMES,
    PHASE_EXEMPT,
    PHASE_FACTS,
    RATE_CHECKS,
)
from tools.dttperf.scenarios import flagship_model  # noqa: E402

HW = HARDWARE["v5lite"]

#: the flagship DeepCNN's analytic train FLOPs/example — the
#: hand-computed pin (utils.efficiency.flops_budget, 3x fwd) every
#: composition below rests on. If the model or the accounting changes,
#: this NUMBER must be re-derived by hand, not copied from the code.
CNN_TRAIN_FLOPS_PER_EXAMPLE = 83_303_424


def _empty_baseline(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"version": 1, "entries": []}))
    return str(p)


def _rec(stem="SYNTH", **parsed):
    return {"stem": stem, "path": f"{stem}.json", "rc": 0,
            "parsed": parsed}


# ------------------------------------------- the step-time composition


def test_predict_cnn_dp_composition_hand_pinned():
    """The flagship CNN, 8-way DP at the bench per-chip batch: every
    term re-derived by hand from the HARDWARE row and the analytic
    FLOPs pin — compute-bound, so the step IS the FLOPs term plus the
    fixed host share."""
    model = flagship_model("deep_cnn")
    pred = predict_step_time(dict(mode="dp", data_ways=8), model, 8,
                             global_batch=16384)
    assert pred["train_flops_per_example"] == CNN_TRAIN_FLOPS_PER_EXAMPLE
    assert pred["flops_per_step"] == CNN_TRAIN_FLOPS_PER_EXAMPLE * 16384
    compute = (CNN_TRAIN_FLOPS_PER_EXAMPLE * 16384
               / (HW["peak_flops_per_chip"] * 8))
    assert pred["compute_s"] == pytest.approx(compute)
    assert pred["comm_s"] == pytest.approx(
        pred["comm_exposed_bytes_per_step"] / HW["ici_bytes_per_sec"])
    assert pred["bound"] == "compute"
    assert pred["useful_fraction"] == 1.0
    assert pred["step_time_s"] == pytest.approx(
        compute + HW["host_fixed_s"])
    # the implied DTP001 ceiling, end to end: ~2.31M images/s/chip
    assert pred["examples_per_sec_per_chip"] == pytest.approx(
        16384 / (compute + HW["host_fixed_s"]) / 8)
    assert pred["examples_per_sec_per_chip"] == pytest.approx(
        2_311_467, rel=1e-3)


def test_predict_cnn_zero_shares_compute_changes_wire():
    """ZeRO-1 re-prices the WIRE (reduce-scatter+all-gather vs
    all-reduce), never the FLOPs: same compute term as DP, different
    ledger bytes."""
    model = flagship_model("deep_cnn")
    dp = predict_step_time(dict(mode="dp", data_ways=8), model, 8,
                           global_batch=16384)
    z1 = predict_step_time(dict(mode="zero1", data_ways=8,
                                zero_level=1), model, 8,
                           global_batch=16384)
    assert z1["compute_s"] == pytest.approx(dp["compute_s"])
    assert z1["comm_bytes_per_step"] != dp["comm_bytes_per_step"]
    assert z1["step_time_s"] == pytest.approx(
        max(z1["compute_s"], z1["comm_s"]) + HW["host_fixed_s"])


def test_predict_lm_pp_stretches_compute_by_the_bubble():
    """The LM pipelined 4 stages x 8 microbatches under GPipe: the
    useful-tick fraction is the hand-computed M/(M+K-1) = 8/11, and
    the compute term is the flat-DP term divided by exactly that —
    bubbles stretch compute, they add no wire bytes."""
    model = flagship_model("lm")
    flat = predict_step_time(dict(mode="dp", data_ways=2), model, 8,
                             global_batch=64)
    pp = predict_step_time(
        dict(mode="pp", data_ways=2, model_axis=4, microbatches=8,
             pp_schedule="gpipe"), model, 8, global_batch=64)
    assert pp["useful_fraction"] == pytest.approx(8 / 11)
    assert pp["flops_per_step"] == flat["flops_per_step"]
    # flat compute uses the same 8 chips, so the bubble is the ONLY
    # difference between the two compute terms
    assert pp["compute_s"] == pytest.approx(
        flat["compute_s"] / (8 / 11))
    assert pp["step_time_s"] == pytest.approx(
        max(pp["compute_s"], pp["comm_s"]) + HW["host_fixed_s"])


def test_predict_lm_tp_composition():
    """The LM tensor-parallel 4 x 2: activation psums on the wire,
    the full max(compute, comm) + host composition, and a nonzero
    exposed-comm term."""
    model = flagship_model("lm")
    pred = predict_step_time(
        dict(mode="tp", data_ways=4, model_axis=2), model, 8,
        global_batch=128)
    assert pred["comm_exposed_bytes_per_step"] > 0
    assert pred["comm_s"] == pytest.approx(
        pred["comm_exposed_bytes_per_step"] / HW["ici_bytes_per_sec"])
    assert pred["step_time_s"] == pytest.approx(
        max(pred["compute_s"], pred["comm_s"]) + HW["host_fixed_s"])
    assert pred["examples_per_sec_per_chip"] == pytest.approx(
        128 / pred["step_time_s"] / 8)


def test_predict_ps_prices_the_host_wire():
    """The PS-emulation topology pays the HOST wire, not ICI — the
    comm term divides by the host-wire figure and dominates (the
    reference's own bottleneck, predicted)."""
    model = flagship_model("deep_cnn")
    pred = predict_step_time(dict(mode="ps", data_ways=1), model, 1,
                             global_batch=2048)
    assert pred["comm_s"] == pytest.approx(
        pred["comm_exposed_bytes_per_step"]
        / HW["host_wire_bytes_per_sec"])
    assert pred["bound"] == "comm"


# --------------------------------------------- DTP001 on synthetic data


def test_band_edge_findings_on_slowed_record():
    """A record whose headline rate sits below the band floor is a
    DTP001 finding keyed (record, phase, mode, model); the same record
    at an in-band rate is clean. The ceiling is re-derived by hand
    from the FLOPs pin (1 chip, batch 2048, no collectives)."""
    step = (CNN_TRAIN_FLOPS_PER_EXAMPLE * 2048
            / HW["peak_flops_per_chip"] + HW["host_fixed_s"])
    ceiling = 2048 / step
    lo, hi = next(c["band"] for c in RATE_CHECKS
                  if c["phase"] == "device_resident")
    slowed = _rec(metric="mnist_images_per_sec_per_chip",
                  value=round(0.5 * lo * ceiling, 1), n_chips=1)
    f, rows = pass_conformance([slowed])
    assert [x.key for x in f] == [
        "band:SYNTH:device_resident:dp:deep_cnn"]
    assert f[0].rule == "DTP001"
    assert "regression" in f[0].message
    assert rows[0]["status"] == "OUT"
    healthy = _rec(metric="mnist_images_per_sec_per_chip",
                   value=round(0.5 * (lo + hi) * ceiling, 1), n_chips=1)
    f2, rows2 = pass_conformance([healthy])
    assert f2 == []
    assert rows2[0]["status"] == "in_band"


def test_faster_than_the_roof_is_also_a_finding():
    """A measured rate ABOVE the analytic ceiling band is an
    accounting bug, not a win — DTP001 names it as such."""
    fast = _rec(metric="mnist_images_per_sec_per_chip",
                value=9e9, n_chips=1)
    f, _ = pass_conformance([fast])
    assert len(f) == 1 and "accounting bug" in f[0].message


def test_link_bound_rates_are_exempt_not_banded():
    """The host-path rates (host-fed wire, feed_dict, PS cycle) are
    structurally exempt — reported, never banded (the step-time model
    has no term for the host input path)."""
    rec = _rec(metric="mnist_images_per_sec_per_chip",
               wire_images_per_sec_per_chip=123.4,
               feeddict_images_per_sec_per_chip=56.7, n_chips=1)
    f, rows = pass_conformance([rec])
    assert f == []
    assert {r["status"] for r in rows} == {"exempt"}


# --------------------------------------------- DTP002 on synthetic data


def test_fact_coverage_flags_silent_nulls():
    """A record carrying a phase's facts with one silently null (no
    error key) is a DTP002 finding; the same null WITH the phase's
    error key is excused (the phase failed loudly)."""
    silent = _rec(lint_findings_total=None, lint_baselined_total=1,
                  lint_stale_suppressions=0, lint_rules=11,
                  lint_time_s=0.5)
    f, rows = pass_fact_coverage([silent])
    keys = [x.key for x in f]
    assert "facts:SYNTH:lint_phase:lint_findings_total" in keys
    assert any(r["phase"] == "lint_phase" and r["status"] == "VIOLATION"
               for r in rows)
    excused = _rec(lint_findings_total=None, lint_baselined_total=None,
                   lint_stale_suppressions=None, lint_rules=None,
                   lint_time_s=None, lint_error="RuntimeError: boom")
    f2, rows2 = pass_fact_coverage([excused])
    assert [x for x in f2 if x.key.startswith("facts:")] == []
    assert any(r["phase"] == "lint_phase" and r["status"] == "errored"
               for r in rows2)


def test_fact_coverage_catches_unwired_phase(tmp_path):
    """A bench.py that defines a covered phase but never calls it from
    _run_phases/degraded_record is a DTP002 finding for EACH missing
    wiring — the degraded-record contract is enforced statically."""
    stub = tmp_path / "bench.py"
    stub.write_text(
        "def lint_phase():\n"
        "    return {'lint_findings_total': 0}\n"
        "def _run_phases(out):\n"
        "    out.update(lint_phase())\n"
        "def degraded_record(e, i):\n"
        "    return {}\n")
    f, _ = pass_fact_coverage([], bench_path=str(stub))
    keys = {x.key for x in f}
    assert "phase:lint_phase:unwired:degraded_record" in keys
    assert "phase:lint_phase:unwired:_run_phases" not in keys
    # every OTHER covered phase is missing from this stub entirely
    assert "phase:perfcheck_phase:missing" in keys


# --------------------------------------------- DTP003 on synthetic data


def test_budgets_blown_unmeasured_and_record_sourced():
    """The three measurement sources: a pinned budget over its limit
    is BLOWN, a pinned budget with no measurement is unmeasured (both
    findings), a record-sourced budget reads the newest record
    carrying the key — and one no record carries yet is a note, not a
    failure (the fact was born after the last chip run)."""
    budgets = [
        {"name": "a_wall_s", "limit": 10.0, "source": "pinned",
         "measured": 12.0},
        {"name": "b_wall_s", "limit": 10.0, "source": "pinned",
         "measured": None},
        {"name": "c_pct", "limit": 2.0, "source": "record:ov_pct"},
        {"name": "d_pct", "limit": 2.0, "source": "record:unborn"},
        {"name": "e_wall_s", "limit": 5.0, "source": "live:e"},
    ]
    recs = [_rec("OLD", ov_pct=0.5), _rec("NEW", ov_pct=1.5)]
    f, rows = pass_budgets(budgets, recs, {"live:e": 1.0})
    by = {r["budget"]: r for r in rows}
    assert by["a_wall_s"]["status"] == "BLOWN"
    assert by["b_wall_s"]["status"] == "unmeasured"
    assert by["c_pct"]["status"] == "ok"
    assert by["c_pct"]["measured"] == 1.5 and "NEW" in by["c_pct"]["note"]
    assert by["d_pct"]["status"] == "unmeasured"
    assert by["e_wall_s"]["status"] == "ok"
    keys = {x.key for x in f}
    assert keys == {"budget:a_wall_s", "budget:b_wall_s:unmeasured"}


# ------------------------------------------------------- repo-wide gate


@pytest.fixture(scope="module")
def gate():
    return run_perf()


def test_repo_gate_prices_clean_inside_the_budget(gate):
    """THE gate: the full (mode x model) matrix prices chip-free with
    zero non-baselined findings, zero stale suppressions, every mode
    covered, inside the <15s matrix acceptance — and the suppressed
    set is exactly the checked-in baseline (which can only shrink)."""
    assert gate.findings == [], \
        "new findings:\n" + "\n".join(f.format() for f in gate.findings)
    assert gate.stale == [], gate.stale
    rep = gate.report
    assert rep["scenarios_proven"] == 13
    assert rep["modes_priced"] == ["dp", "ep", "pp", "ps", "sp", "tp",
                                   "zero1", "zero3"]
    assert rep["matrix_time_s"] < 15.0, rep["matrix_time_s"]
    assert rep["in_band_pct"] >= 50.0
    from tools.dttperf import load_baseline

    assert {(f.rule, f.key) for f in gate.baselined} == \
        {(e["rule"], e["key"]) for e in load_baseline()}


def test_repo_gate_covers_the_fact_and_budget_closures(gate):
    """The unfiltered run exercises all four passes: conformance rows
    for whatever records are checked in (none since PR 21 took out the
    records of another installation — an empty corpus is a clean run,
    not an error; the static closure — phases wired and emitting —
    carries the proof here and the synthetic tests above exercise the
    row side), fact-coverage rows for every covered phase the corpus
    carries, and a status for every declared budget."""
    from tools.dttperf.records import load_records

    rep = gate.report
    assert {r["status"] for r in rep["rate_checks"]} <= {"in_band",
                                                         "exempt"}
    assert bool(rep["rate_checks"]) == bool(load_records())
    covered = {r["phase"] for r in rep["fact_coverage"]}
    assert covered <= set(PHASE_FACTS)
    assert not any(r["status"] == "VIOLATION"
                   for r in rep["fact_coverage"])
    # every pinned budget carries a real measurement (a BLOWN one is
    # allowed only because the gate fixture already proved it
    # baselined with a reason — findings == [])
    assert all(b["status"] != "unmeasured" for b in rep["budgets"]
               if b["source"] == "pinned"), rep["budgets"]


def test_stale_suppression_fails_loudly(tmp_path):
    """A baseline entry whose finding no longer exists FAILS the run
    (the baseline only shrinks) — exercised with synthetic records so
    the dead DTP001 key is provably dead."""
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "DTP001", "key": "band:GONE:device_resident:dp:deep_cnn",
         "reason": "left over from a deleted record"},
    ]}))
    res = run_perf(str(base), records=[])
    assert not res.ok
    assert any("GONE" in s for s in res.stale)


def test_model_consumes_closure_holds():
    """Every predictor term's measured dual is really declared: the
    MODEL_CONSUMES rows with a phase point at keys that phase's
    PHASE_FACTS row owns (the repo gate then proves bench.py emits
    them)."""
    for term, phase, key in MODEL_CONSUMES:
        if phase is not None:
            assert key in PHASE_FACTS[phase]["keys"], (term, phase, key)


def test_rate_checks_and_exemptions_are_well_formed():
    """Table sanity the passes rest on: every banded check declares a
    real band and a full identity; every exemption states a reason;
    no phase sits in both PHASE_FACTS and PHASE_EXEMPT."""
    for chk in RATE_CHECKS:
        if chk.get("link_bound"):
            assert isinstance(chk["link_bound"], str) and chk["link_bound"]
        else:
            lo, hi = chk["band"]
            assert 0 < lo < hi
            assert chk["phase"] and chk["mode"] and chk["model"]
            assert chk["per_chip_batch"] > 0
    assert not set(PHASE_FACTS) & set(PHASE_EXEMPT)
    for phase, why in PHASE_EXEMPT.items():
        assert isinstance(why, str) and why.strip(), phase


# ------------------------------------------------------------------ CLI


def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.dttperf", *args],
        capture_output=True, text=True, cwd=cwd)


def test_cli_json_exits_zero_and_carries_the_report():
    p = _cli("--json")
    assert p.returncode == 0, p.stdout + p.stderr
    out = json.loads(p.stdout)
    assert out["ok"] and out["findings"] == []
    assert out["report"]["scenarios_proven"] == 13
    assert out["report"]["budgets"]


def test_cli_filtered_run_prices_the_subset():
    """--mode dp prices only the dp cells (bring-up ergonomics) and
    must not charge the whole-corpus passes' stale entries."""
    p = _cli("--mode", "dp", "--json")
    assert p.returncode == 0, p.stdout + p.stderr
    out = json.loads(p.stdout)
    assert out["report"]["modes_priced"] == ["dp"]
    assert out["report"]["rate_checks"] == []


def test_cli_exits_nonzero_on_stale_entry(tmp_path):
    """A dead suppression flips the exit code — scoped to a filtered
    run so the check stays cheap: the DTP000 entry names a cell that
    RAN clean, so the entry is provably stale."""
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "DTP000", "key": "build:dp/deep_cnn",
         "reason": "pretend this cell cannot price"},
    ]}))
    p = _cli("--mode", "dp", "--baseline", str(base))
    assert p.returncode == 1
    assert "STALE" in p.stdout
