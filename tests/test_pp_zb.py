"""Zero-bubble pipeline schedule (--pp_schedule zb:
parallel/pp_schedule.build_zb_schedule + the explicit F/B/W tick scan
in parallel/pipeline_parallel._pp_zb_grads). Pins:

- the combined table's structural invariants: every unit of the F/B/W
  inventory scheduled exactly once on its stage, every consumption
  strictly after its ring arrival, W strictly after B for the same
  unit, everything inside ONE step's tick range (a deferred W can
  never cross an optimizer update — the fold runs before it);
- the acceptance fact: zb's useful-tick fraction STRICTLY exceeds the
  interleaved schedule's at the same (K, M, V);
- matching trajectories: zb follows gpipe AND interleaved on the
  8-device mesh to within ``ZB_RTOL`` of each leaf's scale,
  --clip_norm set and dropout on — host-fed and device-resident
  chunked steps both;
- cross-SCHEDULE checkpoint portability (save under zb -> restore
  under gpipe and the reverse) and mid-chunk --device_data CLI resume
  under --pp_schedule zb;
- parse-time flag validation (whitelist, parent-mode gating, the
  gpipe x V contradiction, the >= 2 blocks/group zb constraint);
- tools/trace_ops.py --schedule ... zb prints B/W ticks distinguished.
"""

import subprocess
import sys

import jax
import numpy as np
import pytest

from distributed_tensorflow_tpu.data.lm import LMDataSet
from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh
from distributed_tensorflow_tpu.parallel.pipeline_parallel import (
    fetch_state_pp,
    make_pp_train_step,
    pp_clip_transform,
    pp_comm_rows,
    shard_state_pp,
    stage_batch_pp,
)
from distributed_tensorflow_tpu.parallel.pp_schedule import (
    ZB_B,
    ZB_F,
    ZB_NONE,
    ZB_W,
    build_zb_schedule,
    normalize_pp_schedule,
    schedule_useful_fraction,
    validate_zb_layout,
)
from distributed_tensorflow_tpu.training import (
    create_train_state,
    get_optimizer,
)

KW8 = dict(vocab_size=16, seq_len=32, d_model=32, num_heads=2,
           num_blocks=8)


# ------------------------------------------------------ schedule table


def _units_of(sched):
    """{(kind, m, j): tick} from the table, asserting uniqueness and
    stage placement on the way."""
    k, v = sched.k_stages, sched.virtual_stages
    seen = {}
    for t in range(sched.num_ticks):
        for s in range(k):
            kind = int(sched.kind[t, s])
            if kind == ZB_NONE:
                continue
            mm = int(sched.micro_index[t, s])
            j = int(sched.chunk_index[t, s]) * k + s
            key = (kind, mm, j)
            assert key not in seen, f"unit {key} scheduled twice"
            assert j % k == s  # owned by its stage
            seen[key] = t
    return seen


@pytest.mark.parametrize("k,m,v", [(2, 2, 1), (2, 8, 1), (4, 8, 1),
                                   (2, 4, 2), (4, 4, 2), (2, 6, 3)])
def test_zb_table_invariants(k, m, v):
    """The unit inventory (first group: F+W, last group: B+W, middle:
    F+B+W) runs exactly once each, and every dependency holds with the
    one-tick ring-arrival latency. All ticks live inside one step —
    W-tick deferral can never cross an optimizer update."""
    sched = build_zb_schedule(k, m, v)
    n_groups = k * v
    units = _units_of(sched)
    expect = set()
    for mm in range(m):
        for j in range(n_groups):
            if j < n_groups - 1:
                expect.add((ZB_F, mm, j))
            if j > 0:
                expect.add((ZB_B, mm, j))
            expect.add((ZB_W, mm, j))
    assert set(units) == expect
    for (kind, mm, j), t in units.items():
        assert 0 <= t < sched.num_ticks
        if kind == ZB_F and j > 0:
            # input activation arrived (producer tick + 1 ring hop)
            assert t >= units[(ZB_F, mm, j - 1)] + 1
        if kind == ZB_B:
            if j < n_groups - 1:
                assert t >= units[(ZB_B, mm, j + 1)] + 1  # cot arrival
            assert t >= units[(ZB_F, mm, j - 1)] + 1      # h arrival
        if kind == ZB_W:
            if j == 0:
                assert t >= units[(ZB_B, mm, 1)] + 1      # cot arrival
            else:
                assert t > units[(ZB_B, mm, j)]           # after own B


@pytest.mark.parametrize("k,m,v", [(2, 2, 1), (2, 8, 1), (4, 8, 1),
                                   (2, 4, 2), (4, 4, 2)])
def test_zb_fraction_strictly_exceeds_interleaved(k, m, v):
    """THE acceptance fact: the zb table's useful-tick fraction is
    strictly above the interleaved schedule's M*V/(M*V+K-1) at the
    same (K, M, V) — the deferred W ticks fill the cooldown."""
    zb = build_zb_schedule(k, m, v).useful_tick_fraction
    inter = schedule_useful_fraction("interleaved", k, m, v)
    assert zb > inter
    assert zb == schedule_useful_fraction("zb", k, m, v)


def test_zb_arrival_tables_route_consistently():
    """Every arrival cell points at a unit whose producer ran on the
    right neighbor the tick before — the stash routing the compiled
    scan trusts blindly."""
    sched = build_zb_schedule(4, 4, 2)
    k = sched.k_stages
    units = _units_of(sched)
    for t in range(sched.num_ticks):
        for s in range(k):
            if sched.fwd_in_valid[t, s]:
                mm = int(sched.fwd_in_micro[t, s])
                j = int(sched.fwd_in_chunk[t, s]) * k + s
                assert units[(ZB_F, mm, j - 1)] == t - 1
            if sched.bwd_in_valid[t, s]:
                mm = int(sched.bwd_in_micro[t, s])
                j = int(sched.bwd_in_chunk[t, s]) * k + s
                assert units[(ZB_B, mm, j + 1)] == t - 1


def test_zb_layout_validation():
    with pytest.raises(ValueError, match="k_stages >= 2"):
        build_zb_schedule(1, 4, 1)
    with pytest.raises(ValueError, match="rounds"):
        build_zb_schedule(2, 3, 2)  # M % K under V > 1
    with pytest.raises(ValueError, match="2 blocks per virtual"):
        validate_zb_layout(8, 4, 2)  # 1 block per group
    validate_zb_layout(8, 2, 2)  # 2 per group: fine
    with pytest.raises(ValueError, match="gpipe"):
        normalize_pp_schedule("gpipe", 2)
    with pytest.raises(ValueError, match="must be one of"):
        normalize_pp_schedule("1f1b", 1)
    assert normalize_pp_schedule("auto", 1) == "gpipe"
    assert normalize_pp_schedule("auto", 2) == "interleaved"
    assert normalize_pp_schedule("zb", 1) == "zb"


def test_pp_comm_rows_zb_exposure():
    """The ledger prices zb's backward ring as overlapped (the
    deferred-W slack) and the AD schedules as fully exposed. Byte
    volume is TICK-exact per schedule (r18, dttcheck-proven): the ring
    fires every tick of ITS OWN table, so zb — whose combined F/B/W
    table runs more ticks — moves more ring bytes than the AD
    schedules at the same (K, M, V); its win is exposure, not volume."""
    from distributed_tensorflow_tpu.parallel.pp_schedule import (
        build_pp_schedule,
        build_zb_schedule,
    )

    ad = pp_comm_rows(1000, 2, 4, 1, schedule="interleaved")
    zb = pp_comm_rows(1000, 2, 4, 1, schedule="zb")
    t_ad = build_pp_schedule(2, 4, 1).num_ticks
    t_zb = build_zb_schedule(2, 4, 1).num_ticks
    assert [r["bytes"] for r in ad[:2]] == [1000 * t_ad] * 2
    assert [r["bytes"] for r in zb[:2]] == [1000 * t_zb] * 2
    assert t_zb > t_ad
    assert all(r["exposed_bytes"] == r["bytes"] for r in ad)
    assert zb[0]["exposed_bytes"] == zb[0]["bytes"]  # forward exposed
    assert zb[1]["exposed_bytes"] == 0               # cotangents hidden
    # the degenerate 1-stage layout has no ring and no stage axis —
    # no rows, whatever the schedule asks for
    assert pp_comm_rows(1000, 1, 4, 1, schedule="gpipe") == []
    assert pp_comm_rows(1000, 1, 4, 1, schedule="zb",
                        rep_grad_bytes=10) == []


# ------------------------------------------- exact-trajectory equality


def _run_pp(model, opt, base, mesh, batches, v, schedule,
            microbatches=4, keep_prob=0.5, clip=0.05):
    st = shard_state_pp(base, mesh, virtual_stages=v)
    step = make_pp_train_step(
        model, opt, mesh, microbatches=microbatches, keep_prob=keep_prob,
        donate=False,
        grad_transform=pp_clip_transform(clip, virtual_stages=v),
        virtual_stages=v, schedule=schedule)
    for b in batches:
        st, m = step(st, stage_batch_pp(mesh, b))
    return fetch_state_pp(st, model, k_stages=mesh.shape["model"],
                          virtual_stages=v), m


def _assert_params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


#: zb against the AD schedules: same units and vjps, but the explicit
#: F/B/W scan and AD's transpose hand XLA different programs, and the
#: installed compiler no longer sums them in one order. The largest
#: difference of any parameter, over the largest magnitude of its leaf:
#: 1.6e-7 on XLA:CPU and 2.7e-7 in chip_smoke.py's case (one or two f32
#: ulps at the leaf's scale) — held to 1e-6, the bound
#: ``chip_smoke.py --multichip`` holds four chips to in f32 as well
#: (``PP_RTOL[0]``; a v5e 2x2 showed 2.8e-7).
ZB_RTOL = 1e-6


def _assert_params_close(a, b, rtol=ZB_RTOL):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert np.abs(x - y).max() <= rtol * np.abs(y).max()


def test_zb_trajectory_matches_gpipe_and_interleaved():
    """THE acceptance test: --pp_schedule zb follows gpipe (V=1) and
    interleaved (V=2) for the 8-block LM on the 8-device mesh
    (data=2, model=4 / data=4, model=2), --clip_norm set and dropout
    ON: losses and accuracy equal, every parameter within ``ZB_RTOL``
    of its leaf's scale (bitwise until the installed XLA; gpipe vs
    interleaved IS still bitwise — tests/test_pp_interleaved.py)."""
    model = TransformerLM(**KW8)
    opt = get_optimizer("sgd", 0.05)
    base = create_train_state(model, opt, seed=0)
    ds = LMDataSet(64, seq_len=32, vocab_size=16, seed=11)
    batches = [ds.next_batch(16) for _ in range(2)]

    # V=1 on the 4-stage mesh: gpipe vs zb (2 blocks per group)
    mesh4 = make_mesh(MeshSpec(data=2, model=4))
    hg, mg = _run_pp(model, opt, base, mesh4, batches, 1, "gpipe")
    hz, mz = _run_pp(model, opt, base, mesh4, batches, 1, "zb")
    assert float(mg["loss"]) == float(mz["loss"])
    assert float(mg["accuracy"]) == float(mz["accuracy"])
    _assert_params_close(hg, hz)

    # V=2 on the 2-stage mesh: interleaved vs zb (2 blocks per group)
    mesh2 = make_mesh(MeshSpec(data=4, model=2))
    hi, mi = _run_pp(model, opt, base, mesh2, batches, 2, "interleaved")
    hz2, mz2 = _run_pp(model, opt, base, mesh2, batches, 2, "zb")
    assert float(mi["loss"]) == float(mz2["loss"])
    _assert_params_close(hi, hz2)


def test_zb_device_chunked_bitmatches_interleaved():
    """The device-resident chunked sampler under zb == interleaved
    bitwise: the DATA-axis-only sample fold is schedule-independent,
    so the same rows are drawn and the tick-table equivalence carries
    through the scan-chunked composition (clip on)."""
    from distributed_tensorflow_tpu.data.device_data import (
        put_device_data,
    )
    from distributed_tensorflow_tpu.training.device_step import (
        make_pp_device_train_step,
    )

    model = TransformerLM(**KW8)
    opt = get_optimizer("sgd", 0.05)
    base = create_train_state(model, opt, seed=0)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    ds = LMDataSet(64, seq_len=32, vocab_size=16, seed=3)
    data = put_device_data(ds, mesh, data_sharded=True)
    outs = {}
    for sched in ("interleaved", "zb"):
        dev = shard_state_pp(base, mesh, virtual_stages=2)
        dstep = make_pp_device_train_step(
            model, opt, mesh, 16, 4, keep_prob=1.0, chunk=2, donate=False,
            grad_transform=pp_clip_transform(0.05, virtual_stages=2),
            virtual_stages=2, schedule=sched)
        dev, m = dstep(dev, data)
        outs[sched] = (fetch_state_pp(dev, model, k_stages=2,
                                      virtual_stages=2), float(m["loss"]))
    assert outs["interleaved"][1] == outs["zb"][1]
    _assert_params_equal(outs["interleaved"][0], outs["zb"][0])


# ------------------------------------- checkpoint schedule independence


def test_checkpoint_roundtrip_across_schedules(tmp_path):
    """Save under zb -> restore under gpipe (and the reverse) continues
    the exact trajectory: the standard-layout checkpoint contract is
    schedule-independent because fetch_state_pp's output never depends
    on the tick table."""
    from distributed_tensorflow_tpu.checkpoint import (
        restore_latest,
        save_checkpoint,
    )

    model = TransformerLM(**KW8)
    opt = get_optimizer("sgd", 0.05)
    base = create_train_state(model, opt, seed=3)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    ds = LMDataSet(64, seq_len=32, vocab_size=16, seed=1)
    batches = [ds.next_batch(16) for _ in range(2)]

    ref, _ = _run_pp(model, opt, base, mesh, batches, 1, "zb",
                     keep_prob=1.0)

    for s_save, s_resume in (("zb", "gpipe"), ("gpipe", "zb")):
        mid, _ = _run_pp(model, opt, base, mesh, batches[:1], 1, s_save,
                         keep_prob=1.0)
        d = tmp_path / f"ckpt_{s_save}to{s_resume}"
        save_checkpoint(str(d), mid, step=1)
        restored, step = restore_latest(
            str(d), create_train_state(model, opt, seed=9))
        assert step == 1
        done, _ = _run_pp(model, opt, restored, mesh, batches[1:], 1,
                          s_resume, keep_prob=1.0)
        _assert_params_equal(ref, done)


def _parse(flags, args):
    flags.FLAGS._reset()
    flags.FLAGS._parse(args)
    return flags.FLAGS


def test_device_zb_mid_chunk_resume(tmp_path):
    """--pipeline --device_data --pp_schedule=zb through the production
    CLI: stop at a step that is NOT a chunk boundary, resume from the
    standard-layout checkpoint, and land on bit-identical params vs
    the uninterrupted run (the resumed loop realigns with a short
    chunk; determinism must survive the different chunk partitioning
    and the stack/unstack round-trip)."""
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.checkpoint import restore_latest
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()

    def args_for(logdir, iters):
        return [f"--logdir={logdir}", f"--data_dir={tmp_path}/none",
                "--dataset=lm", "--model=lm", "--pipeline",
                "--model_axis=2", "--pp_schedule=zb", "--num_blocks=4",
                "--d_model=32", "--num_heads=2", "--seq_len=32",
                "--vocab_size=16", "--batch_size=16",
                f"--training_iter={iters}", "--display_step=3",
                "--device_data", "--device_chunk=3", "--clip_norm=0.5",
                "--test_eval=false"]

    try:
        res = train(_parse(flags, args_for(f"{tmp_path}/a", 5)),
                    mode="sync")
        assert res.final_step == 5
        res = train(_parse(flags, args_for(f"{tmp_path}/a", 9)),
                    mode="sync")
        assert res.final_step == 9
        res_b = train(_parse(flags, args_for(f"{tmp_path}/b", 9)),
                      mode="sync")
        assert res_b.final_step == 9
    finally:
        flags.FLAGS._reset()

    model = TransformerLM(vocab_size=16, seq_len=32, d_model=32,
                          num_heads=2, num_blocks=4)
    opt = get_optimizer("sgd", 0.001)
    tmpl = lambda: create_train_state(model, opt, seed=9)
    got_a, step_a = restore_latest(f"{tmp_path}/a", tmpl())
    got_b, step_b = restore_latest(f"{tmp_path}/b", tmpl())
    assert step_a == step_b == 9
    _assert_params_equal(got_a, got_b)


# ------------------------------------------------ parse-time validation


def test_pp_schedule_flag_validation():
    from distributed_tensorflow_tpu import flags

    flags.define_reference_flags()
    cases = [
        (["--pp_schedule=zb"], "only applies to --pipeline"),
        (["--pp_schedule=1f1b", "--pipeline"], "must be one of"),
        (["--pipeline", "--model_axis=2", "--num_blocks=8",
          "--virtual_stages=2", "--pp_schedule=gpipe"],
         "virtual_stages=1 special case"),
        (["--pipeline", "--model_axis=2", "--num_blocks=4",
          "--virtual_stages=2", "--batch_size=16",
          "--pp_schedule=zb"], "2 blocks per virtual-stage group"),
    ]
    try:
        for args, want in cases:
            flags.FLAGS._reset()
            with pytest.raises(ValueError, match=want):
                flags.FLAGS._parse(args)
        # the valid zb config parses clean; default stays auto
        flags.FLAGS._reset()
        flags.FLAGS._parse(["--pipeline", "--model_axis=2",
                            "--num_blocks=4", "--pp_schedule=zb",
                            "--batch_size=16"])
        assert flags.FLAGS.pp_schedule == "zb"
        flags.FLAGS._reset()
        flags.FLAGS._parse([])
        assert flags.FLAGS.pp_schedule == "auto"
    finally:
        flags.FLAGS._reset()


# ------------------------------------------------------------- tooling


def test_trace_ops_schedule_zb_cli():
    """tools/trace_ops.py --schedule K M [V] zb prints the combined
    F/B/W table with B and W ticks distinguished and the interleaved
    baseline for comparison — no chip, no trace file."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "trace_ops.py"),
         "--schedule", "2", "4", "zb"],
        capture_output=True, text=True, timeout=300, cwd=root)
    assert p.returncode == 0, p.stderr
    assert "zero-bubble" in p.stdout
    assert "B m0.v0" in p.stdout and "W m3.v0" in p.stdout
    assert "interleaved baseline" in p.stdout
