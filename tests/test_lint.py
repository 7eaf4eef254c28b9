"""dttlint — the AST invariant linter (tools/dttlint/).

Three layers: (1) per-rule fixture pairs — one minimal violating
snippet, one conforming — under tests/lint_fixtures/; (2) the
REPO-WIDE run: zero non-baselined findings with the checked-in
baseline, and stale suppressions fail loudly; (3) the CLI surface
(--json, exit codes, the DTT001 --fix rewrite)."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.dttlint import run_lint  # noqa: E402
from tools.dttlint.__main__ import apply_dtt001_fixes  # noqa: E402
from tools.dttlint.rules import (  # noqa: E402
    ALL_RULES,
    rule_collective_axis,
    rule_donation_safety,
    rule_fault_registry,
    rule_flag_validator,
    rule_inventory_coverage,
    rule_ledger_coverage,
    rule_perf_coverage,
    rule_scalar_contract,
    rule_span_catalog,
    rule_trace_purity,
    rule_traced_coverage,
)

FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")


_EMPTY_BASELINE = os.path.join(FIXTURES, "empty_baseline.json")


def _lint(rule, root, *targets):
    return run_lint(os.path.join(FIXTURES, root) if root else FIXTURES,
                    baseline_path=_EMPTY_BASELINE, rules=[rule],
                    targets=targets)


@pytest.fixture(scope="module", autouse=True)
def empty_baseline():
    with open(_EMPTY_BASELINE, "w") as f:
        json.dump({"version": 1, "entries": []}, f)
    yield
    os.remove(_EMPTY_BASELINE)


# ---------------------------------------------------- per-rule fixtures

# (rule, fixture root under lint_fixtures/ or "" for flat, bad targets,
#  good targets, expected rule id, minimum bad findings)
FIXTURE_MATRIX = [
    (rule_collective_axis, "", ("dtt001_bad.py",), ("dtt001_good.py",),
     "DTT001", 4),
    (rule_ledger_coverage, "dtt002", ("parallel/bad_mod.py",),
     ("parallel/good_mod.py",), "DTT002", 1),
    (rule_scalar_contract, "", ("dtt003_bad.py",), ("dtt003_good.py",),
     "DTT003", 3),
    (rule_fault_registry, "", ("dtt004_bad.py",), ("dtt004_good.py",),
     "DTT004", 2),
    (rule_span_catalog, "dtt005_bad", ("code.py",), None, "DTT005", 2),
    (rule_flag_validator, "dtt006_bad", ("flags.py",), None, "DTT006", 1),
    (rule_trace_purity, "", ("dtt007_bad.py",), ("dtt007_good.py",),
     "DTT007", 5),
    (rule_donation_safety, "", ("dtt008_bad.py",), ("dtt008_good.py",),
     "DTT008", 1),
    (rule_traced_coverage, "dtt009_bad",
     ("parallel/mod.py", "tools/dttcheck/refs.py"), None, "DTT009", 1),
    (rule_inventory_coverage, "dtt010_bad",
     ("code.py", "tools/dttsan/stub.py"), None, "DTT010", 2),
    (rule_perf_coverage, "dtt011_bad",
     ("bench.py", "tools/dttperf/records.py"), None, "DTT011", 2),
]


@pytest.mark.parametrize(
    "rule,root,bad,good,rule_id,min_bad",
    FIXTURE_MATRIX, ids=[m[4] for m in FIXTURE_MATRIX])
def test_rule_fixture_pair(rule, root, bad, good, rule_id, min_bad):
    res = _lint(rule, root, *bad)
    assert len(res.findings) >= min_bad, \
        f"{rule_id} bad fixture: {[f.format() for f in res.findings]}"
    assert all(f.rule == rule_id for f in res.findings)
    if good is None:  # table-paired rules carry their own good dir
        root = root.replace("_bad", "_good")
        good = bad
    res_good = _lint(rule, root, *good)
    assert res_good.findings == [], \
        f"{rule_id} good fixture not clean: " \
        f"{[f.format() for f in res_good.findings]}"


def test_dtt001_flags_every_literal_kind():
    """The bad fixture exercises all three literal shapes: collective
    axis arg, axis_name kwarg, PartitionSpec/Mesh tuples."""
    res = _lint(rule_collective_axis, "", "dtt001_bad.py")
    msgs = "\n".join(f.message for f in res.findings)
    assert "psum()" in msgs and "psum_scatter()" in msgs
    assert "P()" in msgs and "Mesh()" in msgs


def test_dtt004_names_both_directions():
    res = _lint(rule_fault_registry, "", "dtt004_bad.py")
    msgs = "\n".join(f.message for f in res.findings)
    assert "unknown_point" in msgs and "UNREGISTERED" in msgs
    assert "orphan" in msgs and "never fired" in msgs


def test_dtt005_flags_both_directions():
    res = _lint(rule_span_catalog, "dtt005_bad", "code.py")
    msgs = "\n".join(f.message for f in res.findings)
    assert "rogue_span" in msgs  # code -> docs drift
    assert "ghost_span" in msgs  # docs -> code drift


def test_dtt007_names_each_impurity():
    res = _lint(rule_trace_purity, "", "dtt007_bad.py")
    msgs = "\n".join(f.message for f in res.findings)
    for needle in ("print", "time.time", "np.random.rand",
                   "branches on traced argument 'x'"):
        assert needle in msgs, f"missing {needle!r} in:\n{msgs}"


# ------------------------------------------------------- repo-wide run


def test_repo_lints_clean_with_checked_in_baseline():
    """THE gate: the whole walk set (package + tools + bench +
    entry points) has zero non-baselined findings and zero stale
    suppressions, inside the <10s acceptance budget — and every
    baseline entry still matches a real finding (the suppressed set
    is exactly the baseline, which can only shrink)."""
    t0 = time.perf_counter()
    res = run_lint()
    dt = time.perf_counter() - t0
    assert res.findings == [], \
        "new findings:\n" + "\n".join(f.format() for f in res.findings)
    assert res.stale == [], res.stale
    assert len(res.rules) == 11
    assert dt < 10.0, f"lint took {dt:.1f}s (>10s acceptance budget)"
    assert res.baselined, "baseline is empty — update this test if " \
                          "the tree went fully clean"
    keys = {(f.rule, f.key) for f in res.baselined}
    from tools.dttlint import load_baseline

    assert keys == {(e["rule"], e["key"]) for e in load_baseline()}


def test_stale_suppression_fails_loudly(tmp_path):
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "DTT001", "key": "no/such/file.py::gone::psum:data",
         "reason": "left over from deleted code"},
    ]}))
    res = run_lint(baseline_path=str(base))
    assert not res.ok
    assert any("no/such/file.py" in s for s in res.stale)


def test_finding_keys_are_line_number_free():
    """Baseline stability: keys must survive unrelated edits, so no
    key may embed a line number."""
    import re

    res = _lint(rule_collective_axis, "", "dtt001_bad.py")
    for f in res.findings:
        assert not re.search(r":\d+$", f.key.replace(":2", "")), f.key


# ------------------------------------------------------------ CLI + fix


def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.dttlint", *args],
        capture_output=True, text=True, cwd=cwd)


def test_cli_exits_zero_and_emits_json():
    p = _cli("--json")
    assert p.returncode == 0, p.stdout + p.stderr
    out = json.loads(p.stdout)
    assert out["ok"] and out["findings"] == []
    assert len(out["rules"]) == 11


def test_cli_exits_nonzero_on_new_violation(tmp_path):
    """Introduce a fixture violation into a scratch tree — the exit
    code must flip (the tier-1 hook's contract)."""
    shutil.copy(os.path.join(FIXTURES, "dtt001_bad.py"),
                tmp_path / "bench.py")  # bench.py is in the walk set
    (tmp_path / "docs").mkdir()
    base = tmp_path / "empty.json"
    base.write_text(json.dumps({"version": 1, "entries": []}))
    p = _cli("--root", str(tmp_path), "--baseline", str(base))
    assert p.returncode == 1
    assert "DTT001" in p.stdout


def test_fix_rewrites_axis_literals(tmp_path):
    """The --fix stub: DTT001 "data"/"model" literals become the mesh
    constants (import added), and the rewritten file lints clean."""
    target = tmp_path / "code.py"
    shutil.copy(os.path.join(FIXTURES, "dtt001_bad.py"), target)
    res = run_lint(str(tmp_path), baseline_path=_EMPTY_BASELINE,
                   rules=[rule_collective_axis], targets=("code.py",))
    assert res.findings
    n = apply_dtt001_fixes(res.findings, str(tmp_path))
    assert n >= 4
    src = target.read_text()
    assert '"data"' not in src and '"model"' not in src
    assert "from distributed_tensorflow_tpu.parallel.mesh import" in src
    res2 = run_lint(str(tmp_path), baseline_path=_EMPTY_BASELINE,
                    rules=[rule_collective_axis], targets=("code.py",))
    assert res2.findings == []


# ------------------------------------------- the rules watch the tree


def test_scalar_contract_sees_all_loop_variants():
    """The DTT003 surface: the four _train_* loops of loop.py are in scope
    (the device-resident driver and the three host-fed loops; a new one
    automatically joins)."""
    from tools.dttlint import RepoIndex
    import ast

    index = RepoIndex()
    tree = index.trees["distributed_tensorflow_tpu/training/loop.py"]
    variants = [n.name for n in tree.body
                if isinstance(n, ast.FunctionDef)
                and n.name.startswith("_train_")]
    assert sorted(variants) == ["_train_device", "_train_once",
                                "_train_pipeline", "_train_zero"], variants
    assert rule_scalar_contract(index) == []


def test_all_rules_registered():
    assert [r.rule_id for r in ALL_RULES] == [
        f"DTT00{i}" for i in range(1, 10)] + ["DTT010", "DTT011"]


def test_dtt009_names_the_orphan_and_guards_self_disable():
    """The orphan site is NAMED; and a walk set with parallel/
    collectives but no tools/dttcheck sources is itself a finding
    (the rule must not silently self-disable)."""
    res = _lint(rule_traced_coverage, "dtt009_bad",
                "parallel/mod.py", "tools/dttcheck/refs.py")
    assert [f.key for f in res.findings] == [
        "parallel/mod.py::orphan_collective_path"]
    assert "machine-unproven" in res.findings[0].message
    res2 = _lint(rule_traced_coverage, "dtt009_bad", "parallel/mod.py")
    assert [f.rule for f in res2.findings] == ["DTT009"]
    assert "self-disable" in res2.findings[0].message


def test_dtt010_names_the_unresolvable_and_guards_self_disable():
    """DTT010 (r20): the Thread/Timer whose target is an arbitrary
    callable value is NAMED (the self-method one is inventory-covered
    and stays quiet); a walk set with Thread sites but no tools/dttsan
    sources is itself a finding."""
    res = _lint(rule_inventory_coverage, "dtt010_bad",
                "code.py", "tools/dttsan/stub.py")
    assert [f.key for f in res.findings] == [
        "code.py::launch:Thread", "code.py::launch:Timer"]
    assert all("inventory" in f.message for f in res.findings)
    res2 = _lint(rule_inventory_coverage, "dtt010_bad", "code.py")
    assert [f.rule for f in res2.findings] == ["DTT010"]
    assert "self-disable" in res2.findings[0].message


def test_dtt011_names_the_hole_and_guards_self_disable():
    """DTT011 (r23): the phase in neither table is NAMED, the
    bare-reason exemption is rejected with its own message, the
    fact-covered phase stays quiet; a walk set with bench phases but
    no tools/dttperf sources is itself a finding."""
    res = _lint(rule_perf_coverage, "dtt011_bad",
                "bench.py", "tools/dttperf/records.py")
    assert sorted(f.key for f in res.findings) == [
        "bench.py::bare_exempt_phase", "bench.py::uncovered_phase"]
    by_key = {f.key: f.message for f in res.findings}
    assert "unexplained exemption" in by_key["bench.py::bare_exempt_phase"]
    assert "neither PHASE_FACTS nor PHASE_EXEMPT" in \
        by_key["bench.py::uncovered_phase"]
    res2 = _lint(rule_perf_coverage, "dtt011_bad", "bench.py")
    assert [f.rule for f in res2.findings] == ["DTT011"]
    assert "self-disable" in res2.findings[0].message
