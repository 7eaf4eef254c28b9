"""dttcheck (r18): the jaxpr-level ledger/SPMD verifier.

Fixture jaxprs drive each pass through its good/bad pair — an unpriced
collective, a phantom ledger row, divergent cond branches, a bad axis
name, a broken donation, replication drift — then the repo-wide
zero-findings gate proves the full (mode x model) scenario matrix
clean inside a <15s chip-free budget (the conftest's 8-device virtual
CPU mesh; tracing is Python time, no chip anywhere).

Fixture step functions mirror the builders' idiom: ``jax.shard_map``
(the package shim) with ``check_vma=False`` and a ``jax.jit`` wrapper,
so the fixtures exercise the same jit/shard_map jaxpr shapes the real
scenarios produce.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import distributed_tensorflow_tpu  # noqa: F401,E402 — install the shim

import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from distributed_tensorflow_tpu.parallel.mesh import (  # noqa: E402
    DATA_AXIS,
    MeshSpec,
    make_mesh,
)
from tools._analysis_common import load_baseline  # noqa: E402
from tools.dttcheck import ALL_PASSES, run_check, verify_ledger  # noqa: E402
from tools.dttcheck import passes as dtc_passes  # noqa: E402
from tools.dttcheck.inventory import (  # noqa: E402
    Inventory,
    trace_inventory,
    walk_jaxpr,
)
from tools.dttcheck.scenarios import Scenario, TraceTarget  # noqa: E402


def _mesh8():
    return make_mesh(MeshSpec(8, 1))


def _target(step_fn, args, mesh, **kw) -> TraceTarget:
    """A minimal pass-level target (the passes read only these fields)."""
    defaults = dict(name="fixture", mode="dp", model_name="fixture",
                    model=None, optimizer=None, batch_size=8)
    defaults.update(kw)
    return TraceTarget(step_fn=step_fn, args=args, mesh=mesh, **defaults)


def _psum_step(mesh):
    """One priced psum (2 x 16 B = 32 B wire) + one scalar control psum."""

    def body(v):
        return jax.lax.psum(v, DATA_AXIS), jax.lax.psum(v.sum(), DATA_AXIS)

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=P(DATA_AXIS),
                               out_specs=(P(), P()), check_vma=False))
    return fn, (np.ones((8, 4), np.float32),)


# ------------------------------------------------------------ inventory


def test_inventory_prices_psum_and_exempts_scalar_control():
    fn, args = _psum_step(_mesh8())
    _, inv = trace_inventory(fn, args)
    priced, control = inv.priced(), inv.control()
    assert [(e.family, e.axes, e.wire_bytes) for e in priced] == [
        ("psum", ("data",), 32)]  # 2 x (1,4) f32, all-reduce convention
    assert len(control) == 1 and control[0].payload_bytes == 4
    assert inv.total_bytes() == 32  # control traffic never priced


def test_inventory_multiplies_scan_trips():
    mesh = _mesh8()
    ring = [(i, (i + 1) % 8) for i in range(8)]

    def step(x):
        def tick(c, _):
            return jax.shard_map(
                lambda v: jax.lax.ppermute(v, DATA_AXIS, ring),
                mesh=mesh, in_specs=P(DATA_AXIS),
                out_specs=P(DATA_AXIS), check_vma=False)(c), None
        out, _ = jax.lax.scan(tick, x, None, length=5)
        return out

    _, inv = trace_inventory(jax.jit(step), (np.ones((8, 4), np.float32),))
    assert [(e.family, e.trips, e.wire_bytes) for e in inv.priced()] == [
        ("ppermute", 5, 5 * 16)]


def test_inventory_sees_checked_shard_map_psum():
    """A check_vma=True caller's psum stages as ``psum_invariant`` — the
    walker maps it to the psum family instead of going blind."""
    mesh = _mesh8()
    fn = jax.shard_map(lambda v: jax.lax.psum(v, DATA_AXIS), mesh=mesh,
                       in_specs=P(DATA_AXIS), out_specs=P())
    _, inv = trace_inventory(fn, (np.ones((8, 4), np.float32),))
    assert [(e.family, e.wire_bytes) for e in inv.priced()] == [
        ("psum", 32)]


# --------------------------------------------- DTC001 ledger proof pair


def test_unpriced_collective_is_exactly_one_named_finding():
    mesh = _mesh8()
    fn, args = _psum_step(mesh)
    _, inv = trace_inventory(fn, args)
    found = dtc_passes.pass_ledger(_target(fn, args, mesh), inv,
                                   {"rows": []})
    assert len(found) == 1
    f = found[0]
    assert f.rule == "DTC001" and f.key == "ledger:fixture:psum:data"
    assert "UNPRICED" in f.message and "32 B" in f.message


def test_phantom_row_is_exactly_one_named_finding():
    mesh = _mesh8()
    fn, args = _psum_step(mesh)
    _, inv = trace_inventory(fn, args)
    ledger = {"rows": [
        {"collective": "all_reduce(grads)", "axis": "data", "bytes": 32},
        {"collective": "all_gather(params)", "axis": "data",
         "bytes": 4096},
    ]}
    found = dtc_passes.pass_ledger(_target(fn, args, mesh), inv, ledger)
    assert len(found) == 1
    assert found[0].rule == "DTC001"
    assert "PHANTOM" in found[0].message
    assert "all_gather(params)" in found[0].message


def test_exact_ledger_proves_clean_and_drift_names_both_sides():
    mesh = _mesh8()
    fn, args = _psum_step(mesh)
    _, inv = trace_inventory(fn, args)
    good = {"rows": [{"collective": "all_reduce(grads)", "axis": "data",
                      "bytes": 32}]}
    assert dtc_passes.pass_ledger(_target(fn, args, mesh), inv, good) == []
    drift = {"rows": [{"collective": "all_reduce(grads)", "axis": "data",
                       "bytes": 48}]}
    found = dtc_passes.pass_ledger(_target(fn, args, mesh), inv, drift)
    assert len(found) == 1
    assert "48 B" in found[0].message and "32 B" in found[0].message


# ------------------------------------------ DTC002 spmd deadlock pair


def _cond_step(mesh, divergent: bool):
    def body(v):
        def collective(u):
            return jax.lax.psum(u, DATA_AXIS)

        def other(u):
            return u * 2.0 if divergent else jax.lax.psum(2.0 * u,
                                                          DATA_AXIS)
        return jax.lax.cond(v.sum() > 0, collective, other, v)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(DATA_AXIS),
                                 out_specs=P(), check_vma=False))


def test_divergent_cond_is_exactly_one_named_finding():
    mesh = _mesh8()
    args = (np.ones((8, 4), np.float32),)
    fn = _cond_step(mesh, divergent=True)
    _, inv = trace_inventory(fn, args)
    found = dtc_passes.pass_deadlock(_target(fn, args, mesh), inv, None)
    assert len(found) == 1
    f = found[0]
    assert f.rule == "DTC002" and f.key.startswith("cond:fixture:")
    assert "divergent" in f.message and "deadlock" in f.message
    # the good twin: both branches carry the same collective signature
    gn = _cond_step(mesh, divergent=False)
    _, ginv = trace_inventory(gn, args)
    assert ginv.cond_mismatches == []
    assert dtc_passes.pass_deadlock(_target(gn, args, mesh), ginv,
                                    None) == []


def test_bad_axis_name_and_bad_ledger_axis_are_findings():
    # a collective naming an axis the enclosing env does not bind
    closed = jax.make_jaxpr(lambda v: jax.lax.psum(v, "model"),
                            axis_env=[("model", 8)])(
        np.ones((4,), np.float32))
    inv = Inventory()
    walk_jaxpr(closed.jaxpr, inv, env=("data",))
    assert inv.bad_axes  # detected at walk time...
    mesh = _mesh8()
    found = dtc_passes.pass_deadlock(_target(None, (), mesh), inv, None)
    assert [f.rule for f in found] == ["DTC002"]
    assert "not bound" in found[0].message
    # ...and the same walk under the right env is clean
    good = Inventory()
    walk_jaxpr(closed.jaxpr, good, env=("data", "model"))
    assert good.bad_axes == []
    # a ledger row claiming an axis the mesh does not carry
    row_led = {"rows": [{"collective": "all_reduce(x)", "axis": "expert",
                         "bytes": 4}]}
    found = dtc_passes.pass_deadlock(_target(None, (), mesh),
                                     Inventory(), row_led)
    assert [f.rule for f in found] == ["DTC002"]
    assert "'expert'" in found[0].message


def test_collective_under_while_is_unprovable_finding():
    mesh = _mesh8()

    def step(x):
        def body(v):
            def w_body(c):
                return jax.lax.psum(c, DATA_AXIS) * 0.5

            return jax.lax.while_loop(lambda c: c.sum() > 1.0, w_body, v)
        return jax.shard_map(body, mesh=mesh, in_specs=P(DATA_AXIS),
                             out_specs=P(DATA_AXIS), check_vma=False)(x)

    fn = jax.jit(step)
    args = (np.ones((8, 4), np.float32),)
    _, inv = trace_inventory(fn, args)
    found = dtc_passes.pass_deadlock(_target(fn, args, mesh), inv, None)
    assert any(f.key.startswith("while:") and "unprovable"
               in f.message for f in found)
    # the unknowable-trip entry must NOT enter the byte proof: a
    # 1-trip guess would fabricate a drift (or prove a guessed ledger)
    assert inv.priced() == [] and inv.total_bytes() == 0
    assert any(not e.provable for e in inv.entries)


def test_unparseable_hlo_collective_fails_loudly():
    """A collective line the HLO parser cannot read (a variadic
    all-gather, an async -start form) must become a finding, never a
    silent skip — uncounted traffic breaks the whole proof."""
    from tools.dttcheck.inventory import hlo_inventory

    mesh = _mesh8()
    hlo = ('  %ag = (f32[80]{0}, f32[128]{0}) all-gather(%a, %b), '
           'replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n')
    inv = hlo_inventory(hlo, mesh)
    assert inv.entries == []
    assert [op for op, _ in inv.unparsed] == ["all-gather"]
    found = dtc_passes.pass_deadlock(_target(None, (), mesh), inv, None)
    assert [f.rule for f in found] == ["DTC002"]
    assert "could not read" in found[0].message
    # a parseable line never lands in unparsed
    ok = ('  %ag = f32[64,4]{1,0} all-gather(f32[8,4]{1,0} %p), '
          'replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n')
    inv2 = hlo_inventory(ok, mesh)
    assert inv2.unparsed == [] and len(inv2.entries) == 1


def test_variadic_all_reduce_is_priced_per_element():
    """The collective combiner's merged all-reduce (tuple result,
    operands by name) is one entry per element: arrays priced at 2x
    their bytes, rank-0 elements exempt as control."""
    from tools.dttcheck.inventory import hlo_inventory

    mesh = _mesh8()
    hlo = ('  %all-reduce.12 = (f32[32]{0}, f32[10,64]{1,0}, '
           '/*index=2*/f32[]) all-reduce(%a, %b, /*index=2*/%c), '
           'channel_id=3, replica_groups={{0,1,2,3,4,5,6,7}}, '
           'use_global_device_ids=true, to_apply=%add\n')
    inv = hlo_inventory(hlo, mesh)
    assert inv.unparsed == []
    assert [(e.family, e.wire_bytes, e.control) for e in inv.entries] == [
        ("psum", 2 * 32 * 4, False), ("psum", 2 * 640 * 4, False),
        ("psum", 2 * 4, True)]


# -------------------------------------------- DTC003 donation audit pair


def test_broken_donation_names_the_arg_and_good_twin_is_clean():
    mesh = _mesh8()
    x = np.ones((8, 4), np.float32)
    # bad: donated (8,4) input, only a scalar output — nothing to alias
    bad = jax.jit(lambda v: v.sum(), donate_argnums=0)
    closed, _ = trace_inventory(bad, (x,))
    found = dtc_passes.pass_donation(
        _target(bad, (x,), mesh, donate=True), closed)
    assert len(found) == 1
    f = found[0]
    assert f.rule == "DTC003" and "arg0" in f.key
    assert "no same-shape/dtype output" in f.message
    # good: same-shape output exists, the alias is real
    good = jax.jit(lambda v: v + 1.0, donate_argnums=0)
    closed, _ = trace_inventory(good, (x,))
    assert dtc_passes.pass_donation(
        _target(good, (x,), mesh, donate=True), closed) == []


def test_promised_donation_that_lowers_none_is_a_finding():
    mesh = _mesh8()
    x = np.ones((8, 4), np.float32)
    fn = jax.jit(lambda v: v + 1.0)  # no donate_argnums
    closed, _ = trace_inventory(fn, (x,))
    found = dtc_passes.pass_donation(
        _target(fn, (x,), mesh, donate=True), closed)
    assert [f.key for f in found] == ["donate:fixture:none"]
    assert "silently lost" in found[0].message
    # donate=False targets skip the audit entirely
    assert dtc_passes.pass_donation(
        _target(fn, (x,), mesh, donate=False), closed) == []


# --------------------------------------- DTC004 replication drift pair


def _sm_step(mesh):
    def body(sv, bv):
        return sv * 1.0, jax.lax.psum(bv.sum(), DATA_AXIS)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(DATA_AXIS)),
        out_specs=(P(), P()), check_vma=False))


def test_replication_drift_both_directions_and_good_twin():
    mesh = _mesh8()
    args = (np.ones((4,), np.float32), np.ones((8, 4), np.float32))
    fn = _sm_step(mesh)
    closed, _ = trace_inventory(fn, args)
    # plan claims leaf 0 sharded; the lowered shard_map replicates it
    found = dtc_passes.pass_replication(
        _target(fn, args, mesh, plan=[("data",), ("data",)]), closed)
    assert len(found) == 1
    assert found[0].rule == "DTC004"
    assert "replicates it" in found[0].message
    # plan claims leaf 1 replicated; the lowered shard_map splits it
    found = dtc_passes.pass_replication(
        _target(fn, args, mesh, plan=[(), ()]), closed)
    assert len(found) == 1
    assert "splits it" in found[0].message
    # the good twin: plan matches the lowered layout
    assert dtc_passes.pass_replication(
        _target(fn, args, mesh, plan=[(), ("data",)]), closed) == []


# --------------------------------------------- runner / baseline / gate


def _fixture_scenario(name="fix/psum"):
    mesh = _mesh8()
    fn, args = _psum_step(mesh)
    return Scenario(name, "dp", "fixture", lambda: _target(
        fn, args, mesh, name=name, plan=None, donate=False))


def test_broken_scenario_build_is_a_dtc000_finding():
    from tools.dttcheck.scenarios import SCENARIOS

    good = next(s for s in SCENARIOS if s.name == "dp/mlp")
    res = run_check(scenarios=[
        good, Scenario("boom/x", "dp", "x", lambda: 1 / 0)])
    assert [f.rule for f in res.findings] == ["DTC000"]
    assert res.findings[0].key == "build:boom/x"
    assert "failed to BUILD" in res.findings[0].message
    assert not res.ok
    # a mode with ANY untraceable scenario must not read as proven,
    # even though the broken build never reaches a report row
    assert res.report["modes_proven"] == []


def test_stale_suppression_fails_loudly(tmp_path):
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "DTC001", "key": "ledger:fix/psum:psum:data",
         "reason": "finding no longer produced by this scenario"},
        {"rule": "DTC002", "key": "cond:other/scenario:site",
         "reason": "belongs to a scenario this filtered run skips"}]}))
    res = run_check(str(base), scenarios=[_fixture_scenario()])
    assert res.findings == []
    # the fix/psum entry's scenario RAN and produced no finding: stale;
    # the other/scenario entry is NOT charged — its scenario was
    # filtered out (the __main__ bring-up contract)
    assert res.stale == ["DTC001:ledger:fix/psum:psum:data"]
    assert not res.ok


def test_baseline_entry_without_reason_is_rejected(tmp_path):
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "DTC001", "key": "ledger:x:psum:data"}]}))
    with pytest.raises(ValueError, match="reason"):
        load_baseline(str(base), str(base))


def test_repo_wide_zero_findings_gate_under_budget():
    """THE gate: the full (mode x model) matrix traces clean — every
    ledger byte-proven against the lowered computation — chip-free
    inside the 15s budget (conftest mesh, jax already warm)."""
    t0 = time.perf_counter()
    res = run_check()
    dt = time.perf_counter() - t0
    assert res.findings == [], "new findings:\n" + "\n".join(
        f.format() for f in res.findings)
    assert res.stale == []
    assert res.rules == ALL_PASSES
    assert res.report["modes_proven"] == [
        "dp", "ep", "pp", "ps", "sp", "tp", "zero1", "zero3"]
    assert len(res.report["scenarios"]) == 20
    assert res.report["collectives_total"] > 0
    assert dt < 15.0, f"dttcheck took {dt:.1f}s (>15s chip-free budget)"


# ------------------------------------- comm_ledger(verify=True) hook


def test_comm_ledger_verify_hook_proves_and_rejects():
    from distributed_tensorflow_tpu.models.mlp import MLP
    from distributed_tensorflow_tpu.training.train_state import (
        get_optimizer,
    )
    from distributed_tensorflow_tpu.utils import resources

    model = MLP(image_size=8, channels=1, num_classes=10,
                hidden_units=64)
    led = resources.comm_ledger(model, None, 64, mode="dp", data_ways=8,
                                verify=True)
    assert led["verified"] is True
    # tamper one row: the proof names the drifted group
    led["rows"][0]["bytes"] += 1024
    found = verify_ledger(model, get_optimizer("sgd", 0.01), 64, led,
                          mode="dp", data_ways=8)
    assert found and found[0].rule == "DTC001"
    assert "drift" in found[0].message
    # ...and the comm_ledger hook surfaces it as a loud ValueError
    with pytest.raises(ValueError, match="do not match"):
        resources._verify_ledger(model, None, 64, led, mode="dp",
                                 data_ways=8)


# ----------------------------------------------------------------- CLI


def test_cli_json_filtered_run_exits_zero():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    p = subprocess.run(
        [sys.executable, "-m", "tools.dttcheck", "--json",
         "--mode", "dp", "--model", "mlp"],
        capture_output=True, text=True, timeout=240, cwd=REPO, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["findings"] == []
    assert out["rules"] == list(ALL_PASSES)
    assert out["report"]["modes_proven"] == ["dp"]


def test_cli_exits_nonzero_on_stale_baseline(tmp_path):
    base = tmp_path / "baseline.json"
    # dp/mlp RUNS under this filter and donates cleanly — the entry's
    # finding does not exist, so it is stale even in a filtered run
    base.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "DTC003", "key": "donate:dp/mlp:none",
         "reason": "finding no longer produced"}]}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    p = subprocess.run(
        [sys.executable, "-m", "tools.dttcheck", "--mode", "dp",
         "--model", "mlp", "--baseline", str(base)],
        capture_output=True, text=True, timeout=240, cwd=REPO, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "STALE suppression" in p.stdout
