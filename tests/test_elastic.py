"""Elastic, preemption-tolerant training (r15).

The tentpole under test: a membership change — a spot preemption
modeled by the ``preempt`` fault point — becomes a planned, accounted,
bitwise-safe resize. The rescale matrix pins post-resize trajectories
BITWISE against a fresh run restored at the target shape (resize IS a
cross-topology restore); the chaos test kills and re-adds a worker
mid-run and pins final params against an un-preempted reference; the
accounting tests pin the ``resize_s`` goodput charge and the
``membership_change``/``resize`` spans end to end.
"""

import glob
import json
import os
import sys

import jax
import numpy as np
import pytest

from distributed_tensorflow_tpu import cluster, flags
from distributed_tensorflow_tpu.checkpoint import (
    latest_checkpoint,
    restore_latest,
)
from distributed_tensorflow_tpu.checkpoint.checkpoint import save_checkpoint
from distributed_tensorflow_tpu.models import get_model
from distributed_tensorflow_tpu.training import (
    create_train_state,
    get_optimizer,
)
from distributed_tensorflow_tpu.training import elastic
from distributed_tensorflow_tpu.training.loop import train
from distributed_tensorflow_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_slate():
    """Every test starts and ends with no fault rules, the full world
    at epoch 0, and no pending elastic state — nothing leaks between
    tests (or into other files' tests)."""
    faults.reset()
    cluster.reset_membership()
    elastic._PENDING["resize"] = None
    elastic._PENDING["joins"] = []
    elastic._PENDING["handled"] = set()
    yield
    faults.reset()
    cluster.reset_membership()
    elastic._PENDING["resize"] = None
    elastic._PENDING["joins"] = []
    elastic._PENDING["handled"] = set()
    flags.FLAGS._reset()


# --------------------------------------------------- preempt fault point


def test_preempt_spec_parses_the_documented_forms():
    rules = faults.parse_fault_spec(
        "preempt:at_step=60:mode=notice:notice_s=30:host=3,"
        "preempt:mode=immediate:host=2:rejoin_steps=40")
    assert rules[0].mode == "notice" and rules[0].at_step == 60
    assert rules[0].notice_s == 30.0 and rules[0].host == 3
    assert rules[1].mode == "immediate" and rules[1].rejoin_steps == 40


@pytest.mark.parametrize("bad,match", [
    ("restore:notice_s=3", "only applies to the preempt point"),
    ("ckpt_write:host=1", "only applies to the preempt point"),
    ("restore:mode=notice", "only applies to the preempt point"),
    ("preempt:mode=torn_file", "names no file"),
    ("preempt:notice_s=-1", "must be >= 0"),
    ("preempt:rejoin_steps=-2", "must be >= 0"),
    ("preempt:host=-1", "must be >= 0"),
])
def test_preempt_grammar_mistakes_are_named(bad, match):
    with pytest.raises(faults.FaultSpecError, match=match):
        faults.parse_fault_spec(bad)


def test_preempt_point_registered_and_described():
    assert "preempt" in faults.INJECTION_POINTS
    text = faults.describe_points()
    assert "preempt" in text and "rejoin_steps" in text


def test_preempt_mode_raises_typed_signal():
    faults.configure("preempt:at_step=5:mode=notice:notice_s=7:host=2")
    faults.fault_point("preempt", step=4)  # filter: no fire
    with pytest.raises(faults.Preempted) as ei:
        faults.fault_point("preempt", step=5)
    assert ei.value.host == 2 and ei.value.notice_s == 7.0
    assert not ei.value.immediate


def test_armed_points_sees_env_rules(monkeypatch):
    monkeypatch.setenv("DTT_FAULT_SPEC", "preempt:mode=immediate")
    faults.reset()
    assert "preempt" in faults.armed_points()


# ------------------------------------------------------ flag validation


@pytest.mark.parametrize("argv,match", [
    (["--world_size=-1"], "--world_size"),
    (["--elastic", "--ps_hosts=a:1,b:2"], "ps"),
    (["--fault_spec=preempt:mode=notice", "--mode=ps"], "ps"),
    (["--fault_spec=preempt:frequency=2"], "--fault_spec"),
])
def test_elastic_flag_validation(argv, match):
    flags.define_reference_flags()
    flags.FLAGS._reset()
    with pytest.raises(ValueError, match=match):
        flags.FLAGS._parse(argv)


def test_elastic_flag_surface_parses_clean():
    flags.define_reference_flags()
    for ok in (["--elastic"], ["--world_size=4"],
               ["--fault_spec=preempt:at_step=9:mode=notice:host=1"
                ":rejoin_steps=5"]):
        flags.FLAGS._reset()
        flags.FLAGS._parse(ok)


# -------------------------------------------------- cluster membership


def test_set_world_filters_active_devices():
    assert len(cluster.active_devices()) == 8  # full world by default
    cluster.set_world((0, 2, 5), epoch=0)
    devs = cluster.active_devices()
    assert [d.id for d in devs] == [0, 2, 5]
    cluster.reset_membership()
    assert len(cluster.active_devices()) == 8


def test_world_size_beyond_host_is_loud():
    cluster.set_world(range(16), epoch=0)
    with pytest.raises(ValueError, match="exceed"):
        cluster.active_devices()


def test_empty_world_refused():
    with pytest.raises(ValueError, match="empty the world"):
        cluster.set_world(())


def test_epoch_advances_by_default():
    cluster.set_world((0, 1), epoch=0)
    assert cluster.membership_epoch() == 0
    assert cluster.set_world((0,)) == 1
    assert cluster.membership_epoch() == 1


def test_make_mesh_covers_the_elastic_world():
    from distributed_tensorflow_tpu.parallel import make_mesh

    cluster.set_world((0, 1, 2, 3), epoch=0)
    mesh = make_mesh()
    assert mesh.devices.size == 4
    cluster.reset_membership()
    assert make_mesh().devices.size == 8


def test_epoch_coordinator_namespaces_the_port():
    assert cluster._epoch_coordinator("10.0.0.1:1234", 0) == \
        "10.0.0.1:1234"
    assert cluster._epoch_coordinator("10.0.0.1:1234", 3) == \
        "10.0.0.1:1237"


def test_init_retry_messages_name_the_epoch(capsys):
    """The satellite: re-initialization after a resize cannot race a
    stale peer (the coordinator is epoch-namespaced) and the retry/
    backoff lines name the epoch."""
    from distributed_tensorflow_tpu.cluster import (
        ClusterSpec,
        maybe_initialize_distributed,
    )

    faults.configure("init:mode=refuse:times=0")  # never let it connect
    spec = ClusterSpec({"ps": [], "worker": ["127.0.0.1:3000",
                                             "127.0.0.1:3001"]})
    with pytest.raises(faults.InjectedFault):
        maybe_initialize_distributed(spec, 0, init_retries=1,
                                     init_backoff_s=0.0,
                                     membership_epoch=2)
    out = capsys.readouterr().out
    assert "[membership epoch 2]" in out
    assert "127.0.0.1:3002" in out  # port 3000 + epoch 2


# ------------------------------------------------- drain via managed()


def _tiny_state():
    model = get_model("mlp", image_size=28, channels=1, num_classes=10,
                      hidden_units=16)
    return create_train_state(model, get_optimizer("sgd", 0.01), seed=0)


def _change(lost=False):
    return elastic.MembershipChange(kind="depart", hosts=(1,), step=5,
                                    epoch=1, lost_step=lost)


def test_resize_drain_is_the_managed_exit_save(tmp_path):
    """A ResizeRequired unwinding through managed() is a CLEAN exit:
    the final save IS the drain checkpoint, at the agreed step."""
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    sv = Supervisor(is_chief=True, logdir=str(tmp_path),
                    save_model_secs=10**6)
    state = _tiny_state()
    with pytest.raises(elastic.ResizeRequired):
        with sv.managed(state) as box:
            box.update(state, 5)
            raise elastic.ResizeRequired(_change(), (0, 1), (0,), 5)
    found = latest_checkpoint(str(tmp_path))
    assert found is not None and found[1] == 5


def test_lost_step_preemption_skips_the_drain_save(tmp_path):
    """mode=immediate: the step died with the capacity — NO drain save;
    the re-form restores the newest cadenced checkpoint instead."""
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    sv = Supervisor(is_chief=True, logdir=str(tmp_path),
                    save_model_secs=10**6)
    state = _tiny_state()
    with pytest.raises(elastic.ResizeRequired):
        with sv.managed(state) as box:
            box.update(state, 5)
            raise elastic.ResizeRequired(_change(lost=True), (0, 1),
                                         (0,), 5)
    assert latest_checkpoint(str(tmp_path)) is None


def test_adopt_sentinel_snapshot(tmp_path):
    d = str(tmp_path)
    state = {"params": {"w": np.arange(8.0, dtype=np.float32)},
             "step": np.int64(0)}
    # nothing to adopt without a sentinel dir
    assert elastic.adopt_sentinel_snapshot(d) is None
    save_checkpoint(d, dict(state, step=np.int64(8)), 8)
    save_checkpoint(os.path.join(d, "sentinel"),
                    dict(state, step=np.int64(10)), 10)
    assert elastic.adopt_sentinel_snapshot(d) == 10
    assert latest_checkpoint(d)[1] == 10
    # idempotent: the main dir is now at least as new
    assert elastic.adopt_sentinel_snapshot(d) is None
    # an OLDER sentinel is never adopted
    save_checkpoint(d, dict(state, step=np.int64(20)), 20)
    assert elastic.adopt_sentinel_snapshot(d) is None


# -------------------------------------------------- goodput accounting


def test_resize_s_scalar_always_present():
    from distributed_tensorflow_tpu.utils.efficiency import GoodputMeter

    g = GoodputMeter()
    assert g.scalars()["resize_s"] == 0.0
    g.charge(2.5, "resize")
    g.charge(0.5, "ckpt")
    assert g.scalars()["resize_s"] == 2.5


# ------------------------------------------- multi-host vote agreement


def _mh_supervisor(proc, n=2):
    es = elastic.ElasticSupervisor()
    es._n_procs = n
    es._proc = proc
    es._default_world = n
    return es


def test_vote_departure_bit_and_agreement():
    """The departing process announces via its bit; on_vote installs
    the SAME change on every process — the survivor resizes, the
    departed process leaves."""
    faults.configure("preempt:mode=notice")
    dep = _mh_supervisor(1)
    assert dep.poll(10) is False  # announced, not yet agreed
    assert dep.local_departure_bit() == 1
    srv = _mh_supervisor(0)
    assert srv.local_departure_bit() == 0
    bits = [0, 1]  # the gathered column, identical everywhere
    for es in (srv, dep):
        es.on_vote(bits, 10)
        assert es.poll(10) is True
    with pytest.raises(elastic.ResizeRequired) as ei:
        srv.maybe_resize(10)
    assert ei.value.new_world == (0,)
    assert ei.value.change.epoch == 1
    with pytest.raises(elastic.Departed):
        dep.maybe_resize(10)


def test_vote_code_carries_lost_step_and_rejoin():
    """An immediate preemption with a re-join schedule survives the
    vote: the departure code encodes both, so every survivor installs
    the change with the detecting process's full semantics."""
    faults.configure("preempt:mode=immediate:rejoin_steps=5")
    dep = _mh_supervisor(1)
    assert dep.poll(10) is False
    code = dep.local_departure_bit()
    assert code & 1 and code & 2 and code >> 2 == 5
    srv = _mh_supervisor(0)
    srv.on_vote([0, code], 10)
    with pytest.raises(elastic.ResizeRequired) as ei:
        srv.maybe_resize(10)
    ch = ei.value.change
    assert ch.lost_step is True
    assert ch.rejoins == ((1, 5),)


def test_vote_ranks_map_to_member_ids_after_a_resize():
    """Vote rows are CURRENT process ranks; after a resize they must
    map through the sorted world to stable member ids — rank 1 of a
    (0, 2) world is member 2, not member 1."""
    cluster.set_world((0, 2), epoch=1)
    srv = _mh_supervisor(0, n=2)
    srv.on_vote([0, 1], 20)
    with pytest.raises(elastic.ResizeRequired) as ei:
        srv.maybe_resize(20)
    assert ei.value.change.hosts == (2,)
    assert ei.value.new_world == (0,)
    assert ei.value.change.epoch == 2


def test_each_preempt_rule_departs_once_per_run():
    """Loop re-entry re-arms the fault rules (their fired counters
    reset); the handled-departure registry keeps a no-at_step rule
    with rejoin_steps from re-firing after its host re-joins — one
    kill-and-re-add cycle, not endless churn."""
    cluster.set_world((0, 1, 2, 3), epoch=0)
    spec = "preempt:mode=notice:host=2:rejoin_steps=4"
    faults.configure(spec)
    es = elastic.ElasticSupervisor()
    assert es.poll(5) is True
    with pytest.raises(elastic.ResizeRequired) as ei:
        es.maybe_resize(5)
    cluster.set_world(ei.value.new_world, epoch=1)
    elastic._PENDING["joins"] = [(9, 2)]
    faults.configure(spec)  # the resize re-entry re-arms the rule
    es = elastic.ElasticSupervisor()
    assert es.poll(9) is True  # the scheduled re-join, NOT a re-fire
    with pytest.raises(elastic.ResizeRequired) as ei:
        es.maybe_resize(9)
    assert ei.value.change.kind == "join"
    cluster.set_world(ei.value.new_world, epoch=2)
    faults.configure(spec)  # the join re-entry re-arms it again
    es = elastic.ElasticSupervisor()
    # host 2 is back in the world, but this rule identity already ran
    assert es.poll(10) is False
    assert cluster.world_hosts(4) == (0, 1, 2, 3)


def test_departed_is_a_clean_managed_exit(tmp_path):
    """The preempted process leaves at the AGREED boundary: its exit
    must count as clean (chief-side: the final save still lands), or
    cross-host-sharded survivors would vote the drain save away."""
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    sv = Supervisor(is_chief=True, logdir=str(tmp_path),
                    save_model_secs=10**6)
    state = _tiny_state()
    with pytest.raises(elastic.Departed):
        with sv.managed(state) as box:
            box.update(state, 7)
            raise elastic.Departed(7)
    found = latest_checkpoint(str(tmp_path))
    assert found is not None and found[1] == 7


# --------------------------------------------------- the rescale matrix


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _parse(args):
    flags.define_reference_flags()
    flags.FLAGS._reset()
    flags.FLAGS._parse(args)
    return flags.FLAGS


def _args(tmp, logdir, iters, world, zero, extra=()):
    return [f"--logdir={logdir}", f"--data_dir={tmp}/none",
            "--model=mlp", "--batch_size=24", f"--training_iter={iters}",
            "--display_step=3", "--device_data", "--device_chunk=3",
            "--test_eval=false", f"--world_size={world}",
            f"--zero={zero}", "--save_model_secs=100000",
            "--optimizer=adam", *extra]


def _final_state(logdir, step_want):
    model = get_model("mlp", image_size=28, channels=1, num_classes=10,
                      hidden_units=100)
    tmpl = create_train_state(model, get_optimizer("adam", 0.001), seed=0)
    got, step = restore_latest(logdir, tmpl)
    assert step == step_want
    return got


# tier-1 time budget: the suite is killed at 870 s, so only ONE matrix
# cell runs fast (zero=1 — it exercises the ZeRO loop AND the elastic
# path; the plain-DP loop is covered fast by the immediate test below);
# the other cells and the grow/chaos scenarios ride the slow lane
@pytest.mark.parametrize("zero", [
    pytest.param(0, marks=pytest.mark.slow),
    1,
    pytest.param(3, marks=pytest.mark.slow),
])
def test_rescale_matrix_shrink_bitwise(tmp_path, zero):
    """D=4 -> 2 at a drained boundary, zero in {0,1,3}: the post-resize
    trajectory is BITWISE the one a fresh run restored at the target
    shape takes — resize is a cross-topology restore, not a migration.
    (--device_data makes the trajectory a pure function of the
    checkpointed state, so bitwise equality is well-defined.)"""
    tmp = str(tmp_path)
    spec = ("preempt:at_step=6:mode=notice:notice_s=5:host=3,"
            "preempt:at_step=6:mode=notice:host=2")
    res = train(_parse(_args(tmp, f"{tmp}/a", 12, 4, zero,
                             (f"--fault_spec={spec}",))), mode="sync")
    assert res.final_step == 12 and res.n_chips == 2
    faults.reset()
    # the un-preempted reference: a clean run at D=4 to the drain step,
    # then a clean run RESTORED at the target shape to the end
    res = train(_parse(_args(tmp, f"{tmp}/b", 6, 4, zero)), mode="sync")
    assert res.final_step == 6 and res.n_chips == 4
    res = train(_parse(_args(tmp, f"{tmp}/b", 12, 2, zero)), mode="sync")
    assert res.final_step == 12 and res.n_chips == 2

    got_a = _final_state(f"{tmp}/a", 12)
    got_b = _final_state(f"{tmp}/b", 12)
    _assert_trees_equal(got_b.params, got_a.params)
    _assert_trees_equal(got_b.opt_state, got_a.opt_state)


def test_join_change_grows_the_world_unit():
    """The join half of poll/maybe_resize without a training run: a
    scheduled re-join becomes a due change at its step and the resize
    grows the world (the trained twin is the slow grow test below)."""
    cluster.set_world((0, 1), epoch=1)
    elastic._PENDING["joins"] = [(9, 2), (9, 3), (20, 4)]
    es = elastic.ElasticSupervisor()
    assert es.poll(8) is False
    assert es.poll(9) is True
    with pytest.raises(elastic.ResizeRequired) as ei:
        es.maybe_resize(9)
    assert ei.value.change.kind == "join"
    assert ei.value.new_world == (0, 1, 2, 3)
    assert ei.value.change.epoch == 2
    assert elastic._PENDING["joins"] == [(20, 4)]  # not yet due


@pytest.mark.slow
def test_rescale_grow_via_rejoin_bitwise(tmp_path):
    """D=2 -> 4: the re-add direction. The world starts at 2 members
    of a 4-slot launch, two preempted hosts re-join mid-run, and the
    grown trajectory pins bitwise against a fresh run restored at 4."""
    tmp = str(tmp_path)
    # depart hosts 2,3 at step 3, both re-join 3 steps after the drain:
    # world 4 (0..3), 2 (3..6), 4 (6..12)
    spec = ("preempt:at_step=3:mode=notice:host=3:rejoin_steps=3,"
            "preempt:at_step=3:mode=notice:host=2:rejoin_steps=3")
    res = train(_parse(_args(tmp, f"{tmp}/a", 12, 4, 0,
                             (f"--fault_spec={spec}",))), mode="sync")
    assert res.final_step == 12 and res.n_chips == 4
    faults.reset()
    res = train(_parse(_args(tmp, f"{tmp}/b", 3, 4, 0)), mode="sync")
    assert res.final_step == 3
    res = train(_parse(_args(tmp, f"{tmp}/b", 6, 2, 0)), mode="sync")
    assert res.final_step == 6
    res = train(_parse(_args(tmp, f"{tmp}/b", 12, 4, 0)), mode="sync")
    assert res.final_step == 12

    got_a = _final_state(f"{tmp}/a", 12)
    got_b = _final_state(f"{tmp}/b", 12)
    _assert_trees_equal(got_b.params, got_a.params)
    _assert_trees_equal(got_b.opt_state, got_a.opt_state)


def test_immediate_preemption_loses_the_step_and_recovers(tmp_path):
    """mode=immediate with no checkpoint on disk: the in-flight
    progress is genuinely lost — the re-formed world starts from
    scratch at the new size and lands bitwise on a clean run at that
    shape (the honest lost-step semantics, end to end)."""
    tmp = str(tmp_path)
    spec = "preempt:at_step=6:mode=immediate:host=1"
    res = train(_parse(_args(tmp, f"{tmp}/a", 9, 2, 0,
                             (f"--fault_spec={spec}",))), mode="sync")
    assert res.final_step == 9 and res.n_chips == 1
    faults.reset()
    res = train(_parse(_args(tmp, f"{tmp}/b", 9, 1, 0)), mode="sync")
    assert res.final_step == 9

    got_a = _final_state(f"{tmp}/a", 9)
    got_b = _final_state(f"{tmp}/b", 9)
    _assert_trees_equal(got_b.params, got_a.params)


# ----------------------------------------------------- the chaos test


@pytest.mark.slow
def test_chaos_kill_and_readd_worker_bitwise_with_accounting(tmp_path):
    """THE acceptance scenario: a run preempted at D=4 drains at the
    next boundary, re-forms at D=2, later re-adds the lost capacity
    back to D=4, and its final params are bitwise equal to an
    un-preempted reference; the resize downtime lands as a named
    resize_s charge in the goodput ledger, and membership_change/
    resize spans ride the span sink AND the flight recorder."""
    from distributed_tensorflow_tpu.utils import telemetry

    tmp = str(tmp_path)
    spec = ("preempt:at_step=4:mode=notice:notice_s=30:host=3"
            ":rejoin_steps=4,"
            "preempt:at_step=4:mode=notice:host=2:rejoin_steps=4")
    extra = (f"--fault_spec={spec}", "--display_step=2",
             "--device_chunk=2")
    res = train(_parse(_args(tmp, f"{tmp}/a", 16, 4, 0, extra)),
                mode="sync")
    assert res.final_step == 16 and res.n_chips == 4
    # the flight recorder's ring holds the membership story; a dump
    # (what any crash/watchdog/atexit path writes) must surface it
    fr_path = telemetry.flight_recorder().dump("chaos-test")
    faults.reset()

    # un-preempted reference: the same world schedule as three clean
    # runs (4 to the drain, 2 to the re-join, 4 to the end)
    res = train(_parse(_args(tmp, f"{tmp}/b", 4, 4, 0,
                             ("--display_step=2", "--device_chunk=2"))),
                mode="sync")
    assert res.final_step == 4
    res = train(_parse(_args(tmp, f"{tmp}/b", 8, 2, 0,
                             ("--display_step=2", "--device_chunk=2"))),
                mode="sync")
    assert res.final_step == 8
    res = train(_parse(_args(tmp, f"{tmp}/b", 16, 4, 0,
                             ("--display_step=2", "--device_chunk=2"))),
                mode="sync")
    assert res.final_step == 16

    got_a = _final_state(f"{tmp}/a", 16)
    got_b = _final_state(f"{tmp}/b", 16)
    _assert_trees_equal(got_b.params, got_a.params)
    _assert_trees_equal(got_b.opt_state, got_a.opt_state)

    # --- accounting: the named resize_s charge in the goodput ledger
    lines = [json.loads(l) for l in open(f"{tmp}/a/metrics.jsonl")]
    resize_vals = [l["resize_s"] for l in lines if "resize_s" in l]
    assert resize_vals and max(resize_vals) > 0.0
    epochs = [l["membership_epoch"] for l in lines
              if "membership_epoch" in l]
    assert epochs and max(epochs) == 2.0  # depart epoch 1, re-join 2

    # --- the spans: membership_change at each change, resize on each
    # re-formed loop's first boundary
    span_file = glob.glob(f"{tmp}/a/spans-*.jsonl")[0]
    recs = [json.loads(l) for l in open(span_file)]
    changes = [r for r in recs if r.get("name") == "membership_change"]
    assert {c["change"] for c in changes} == {"depart", "join"}
    resizes = [r for r in recs if r.get("name") == "resize"]
    assert len(resizes) == 2
    assert all(r["resize_s"] > 0 for r in resizes)

    # --- the flight recorder holds the membership_change span too
    assert fr_path is not None
    fr = open(fr_path).read()
    assert "membership_change" in fr


# ------------------------------------------------------- fleet report


def test_fleet_report_surfaces_resize_column(tmp_path):
    sys.path.insert(0, REPO)
    from tools.fleet_report import analyze, print_report

    p = tmp_path / "spans-worker-0.jsonl"
    recs = [
        {"name": "train_step", "ts": 1.0, "dur_s": 0.01, "step": 1,
         "host": "worker-0"},
        {"name": "membership_change", "ts": 2.0, "dur_s": 0.0,
         "change": "depart", "epoch": 1, "host": "worker-0"},
        {"name": "resize", "ts": 3.0, "dur_s": 0.0, "resize_s": 1.25,
         "epoch": 1, "host": "worker-0"},
        {"name": "resize", "ts": 9.0, "dur_s": 0.0, "resize_s": 0.75,
         "epoch": 2, "host": "worker-0"},
    ]
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    report = analyze([str(p)])
    h = report["hosts"]["worker-0"]
    assert h["resize_s"] == 2.0
    assert h["membership_changes"] == 1
    import io

    buf = io.StringIO()
    print_report(report, out=buf)
    assert "resize_s" in buf.getvalue()
    assert "2.00" in buf.getvalue()


# ------------------------------------------------------- bench fields


def test_bench_elastic_phase_nonnull():
    import bench

    out = bench.elastic_phase()
    assert out.get("elastic_error") is None, out
    assert out["elastic_world"] == "2->1"
    assert out["elastic_epoch"] == 1
    assert out["elastic_drain_steps"] == 2
    # the adopted sentinel snapshot (step 10) landed torn, so the
    # ladder walked back to the last cadenced checkpoint (step 8)
    assert out["elastic_restore_step"] == 8
    assert out["elastic_restore_fallback_depth"] == 1
    assert out["elastic_resize_s"] is not None


def test_bench_degraded_record_keeps_elastic_fields():
    import bench

    rec = bench.degraded_record("forced outage")
    assert rec["elastic_world"] == "2->1"
    assert rec["elastic_restore_fallback_depth"] == 1
    assert rec["elastic_resize_s"] is not None
