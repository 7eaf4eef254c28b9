"""Device-resident input: on-device batch sampling + scan-chunked steps
(training/device_step.py, data/device_data.py) — the zero-host-bytes-per-
step mode, single-device and over the 8-device virtual mesh, plus its
--device_data integration into the training loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.data import read_data_sets
from distributed_tensorflow_tpu.data.device_data import put_device_data
from distributed_tensorflow_tpu.models import DeepCNN
from distributed_tensorflow_tpu.training import adam, create_train_state, make_train_step
from distributed_tensorflow_tpu.training.device_step import (
    _SAMPLE_SALT,
    make_device_dp_train_step,
    make_device_train_step,
)


@pytest.fixture(scope="module")
def ds():
    return read_data_sets("/nonexistent", one_hot=True)


@pytest.fixture(scope="module")
def data(ds):
    return put_device_data(ds.train)


def test_put_device_data_shapes_and_dtypes(ds, data):
    assert data.images.dtype == jnp.uint8
    assert data.labels.dtype == jnp.int32
    assert data.num_examples == ds.train.num_examples
    assert data.images.shape[0] == data.labels.shape[0]


def test_chunk_advances_step_and_converges(data):
    model = DeepCNN()
    opt = adam(1e-3)
    state = create_train_state(model, opt, seed=0)
    step = make_device_train_step(model, opt, 64, keep_prob=0.75, chunk=5,
                                  donate=False)
    state, m0 = step(state, data)
    assert int(state.step) == 5
    for _ in range(7):
        state, m = step(state, data)
    assert int(state.step) == 40
    assert float(m["loss"]) < float(m0["loss"])
    assert np.isfinite(float(m["accuracy"]))


def test_device_step_matches_host_step_on_same_batch(data):
    """chunk=1 device-sampled step == make_train_step on the batch the
    sampling PRNG selects: the input side moved into the program, the math
    did not change."""
    model = DeepCNN()
    opt = adam(1e-3)
    state = create_train_state(model, opt, seed=3)
    dstep = make_device_train_step(model, opt, 32, keep_prob=0.75, chunk=1,
                                   donate=False)
    new_dev, m_dev = dstep(state, data)

    # replicate the in-program sampling on the host
    samp = jax.random.fold_in(state.rng, _SAMPLE_SALT)
    idx = np.asarray(jax.random.randint(samp, (32,), 0, data.num_examples))
    batch = (np.asarray(data.images)[idx], np.asarray(data.labels)[idx])
    hstep = make_train_step(model, opt, keep_prob=0.75, donate=False)
    new_host, m_host = hstep(state, batch)

    np.testing.assert_allclose(float(m_dev["loss"]), float(m_host["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(new_dev.params),
                    jax.tree.leaves(new_host.params)):
        # atol 2e-5: XLA fuses the on-device gather+step differently
        # from the host-fed step, and one element in 51200 lands ~7e-6
        # off on this container's CPU backend — same math, different
        # fusion order
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=2e-5)


def test_deterministic_per_seed(data):
    model = DeepCNN()
    opt = adam(1e-3)
    step = make_device_train_step(model, opt, 32, keep_prob=0.75, chunk=4,
                                  donate=False)

    def run(seed):
        state = create_train_state(model, opt, seed=seed)
        for _ in range(3):
            state, _ = step(state, data)
        return np.asarray(state.params["weights"]["out"])

    np.testing.assert_array_equal(run(1), run(1))
    assert not np.array_equal(run(1), run(2))


def test_dp_device_step_replicated_and_finite(ds):
    from distributed_tensorflow_tpu.parallel import make_mesh
    from distributed_tensorflow_tpu.parallel.data_parallel import replicate_state

    mesh = make_mesh()
    data = put_device_data(ds.train, mesh)
    model = DeepCNN()
    opt = adam(1e-3)
    state = replicate_state(mesh, create_train_state(model, opt, seed=0))
    step = make_device_dp_train_step(model, opt, mesh, 64, keep_prob=0.75,
                                     chunk=3, donate=False)
    state, m = step(state, data)
    state, m = step(state, data)
    assert int(state.step) == 6
    assert np.isfinite(float(m["loss"]))
    # replicated invariant: every device shard holds identical params
    w = state.params["weights"]["out"]
    shards = [np.asarray(s.data) for s in w.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


def test_device_step_stateful_model():
    """Batch-norm models thread model_state through the scan body: the
    ResNet's EMA stats must actually update across a chunk."""
    from distributed_tensorflow_tpu.data import read_data_sets
    from distributed_tensorflow_tpu.models import ResNet20
    from distributed_tensorflow_tpu.training import get_optimizer

    ds = read_data_sets("/nonexistent", one_hot=True, dataset="cifar10")
    data = put_device_data(ds.train)
    model = ResNet20()
    opt = get_optimizer("momentum", 0.1)
    state = create_train_state(model, opt, seed=0)
    before = np.asarray(
        jax.tree.leaves(state.model_state)[0]).copy()
    step = make_device_train_step(model, opt, 8, keep_prob=1.0, chunk=2,
                                  donate=False)
    state, m = step(state, data)
    assert int(state.step) == 2
    assert np.isfinite(float(m["loss"]))
    after = np.asarray(jax.tree.leaves(state.model_state)[0])
    assert not np.allclose(before, after), "BN stats never updated"


def test_dp_device_step_batch_divisibility():
    from distributed_tensorflow_tpu.parallel import make_mesh

    mesh = make_mesh()
    with pytest.raises(ValueError, match="not divisible"):
        make_device_dp_train_step(DeepCNN(), adam(1e-3), mesh, 30)


# ------------------------------------------------------- loop integration


def test_train_loop_device_data(tmp_path, capsys):
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._reset()
    flags.FLAGS._parse([
        f"--logdir={tmp_path}/logs",
        f"--data_dir={tmp_path}/no-data",
        "--training_iter=25",  # not a multiple of the chunk: remainder path
        "--batch_size=32",
        "--display_step=10",
        "--optimizer=adam",
        "--learning_rate=0.002",
        "--save_model_secs=100000",
        "--device_data",
        "--device_chunk=10",
    ])
    try:
        res = train(flags.FLAGS, mode="local")
    finally:
        flags.FLAGS._reset()
    assert res.final_step == 25  # remainder chunk respected training_iter
    assert res.test_metrics is not None
    out = capsys.readouterr().out
    assert "job: worker/0 step:  0 mini_batch loss:" in out
    assert "Optimization Finished!" in out


# the three layouts of the device-resident driver (training/loop.py
# ``_train_device``): (mode, the flags that choose the layout)
_TINY_PP = ["--model=lm", "--dataset=lm", "--seq_len=32", "--vocab_size=16",
            "--d_model=32", "--num_heads=2", "--num_blocks=2",
            "--model_axis=2", "--pipeline"]
DEVICE_LAYOUTS = {
    "plain": ("local", []),
    "zero": ("sync", ["--zero=1", "--model=mlp"]),
    "pipeline": ("sync", _TINY_PP),
}


@pytest.mark.parametrize("layout", sorted(DEVICE_LAYOUTS))
def test_train_loop_device_data_resume_realigns_display(tmp_path, capsys,
                                                        layout):
    """Resuming from a step that is not a chunk multiple must realign to
    display boundaries instead of silently never displaying again, in
    every layout (under ZeRO and the pipeline the resumed state goes
    through the layout's own placement first)."""
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    mode, layout_flags = DEVICE_LAYOUTS[layout]

    def run(training_iter):
        flags.FLAGS._reset()
        flags.FLAGS._parse([
            f"--logdir={tmp_path}/logs",
            f"--data_dir={tmp_path}/no-data",
            f"--training_iter={training_iter}",
            "--batch_size=32",
            "--display_step=10",
            "--optimizer=adam",
            "--save_model_secs=100000",
            "--device_data",
            "--device_chunk=10",
            *layout_flags,
        ])
        try:
            return train(flags.FLAGS, mode=mode)
        finally:
            flags.FLAGS._reset()

    run(13)  # final checkpoint lands at the misaligned step 13
    capsys.readouterr()
    res = run(25)  # resumes at 13 -> chunks 7 (realign), 10, 5
    assert res.final_step == 25
    out = capsys.readouterr().out
    assert "step:  20 mini_batch loss:" in out


@pytest.mark.parametrize("layout", ["plain", "pipeline"])
def test_train_loop_device_data_profile_dir(tmp_path, layout):
    """--profile_dir opens the profiler's window in the one driver, so
    under --pipeline too (whose own copy of the loop had none)."""
    import glob

    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    mode, layout_flags = DEVICE_LAYOUTS[layout]
    flags.FLAGS._reset()
    flags.FLAGS._parse([
        f"--logdir={tmp_path}/logs",
        f"--data_dir={tmp_path}/no-data",
        "--training_iter=20",
        "--batch_size=32",
        "--display_step=10",
        "--save_model_secs=100000",
        "--device_data",
        "--device_chunk=5",
        f"--profile_dir={tmp_path}/prof",
        "--profile_steps=5",
        *layout_flags,
    ])
    try:
        train(flags.FLAGS, mode=mode)
    finally:
        flags.FLAGS._reset()
    assert glob.glob(f"{tmp_path}/prof/**/*.trace*", recursive=True) or \
        glob.glob(f"{tmp_path}/prof/**/*.pb", recursive=True), \
        "no profiler trace written"


def test_train_loop_device_data_sync(tmp_path):
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._reset()
    flags.FLAGS._parse([
        f"--logdir={tmp_path}/logs",
        f"--data_dir={tmp_path}/no-data",
        "--training_iter=20",
        "--batch_size=32",
        "--display_step=10",
        "--optimizer=adam",
        "--save_model_secs=100000",
        "--device_data",
        "--device_chunk=10",
    ])
    try:
        res = train(flags.FLAGS, mode="sync")
    finally:
        flags.FLAGS._reset()
    assert res.final_step == 20
    assert res.n_chips == 8
    assert res.test_metrics is not None


# --------------------------- SP x device_data composition (r5, VERDICT #5)


def test_device_sp_step_matches_manual_dense_trajectory():
    """The sequence-parallel resident sampler must be the SP step fed by
    the sampled batch: replicate its exact PRNG stream (salted fold +
    DATA-axis fold) on the host against the DENSE twin and compare full
    trajectories. Pins both halves: the token shards of a data row draw
    the SAME rows (their gathers tile the batch), and the SP grad
    reduction over resident tiles equals the dense gradient."""
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_tpu.data.device_data import put_device_data_sp
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.models.transformer import TransformerLM
    from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh
    from distributed_tensorflow_tpu.parallel.data_parallel import (
        replicate_state,
    )
    from distributed_tensorflow_tpu.parallel.mesh import MODEL_AXIS
    from distributed_tensorflow_tpu.training.device_step import (
        make_device_sp_train_step,
    )
    from distributed_tensorflow_tpu.training.train_state import (
        apply_updates,
        compute_grads,
    )

    kw = dict(vocab_size=16, seq_len=32, d_model=32, num_heads=2,
              num_blocks=2)
    dense = TransformerLM(**kw)
    sp = TransformerLM(**kw, seq_axis=MODEL_AXIS)
    opt = adam(1e-2)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    ds = LMDataSet(64, seq_len=32, vocab_size=16, seed=3)
    data = put_device_data_sp(ds, mesh, per_token_targets=True)
    B, T = 8, 3  # global batch, steps

    state = create_train_state(dense, opt, seed=0)
    dev_state = replicate_state(mesh, state)
    step = make_device_sp_train_step(sp, opt, mesh, B, keep_prob=1.0,
                                     chunk=T, donate=False)
    dev_state, m = step(dev_state, data)

    # manual reference: same PRNG math, dense model, full batch
    x_all = jnp.asarray(ds.images)
    y_all = jnp.asarray(ds.labels)
    for _ in range(T):
        rng, sub = jax.random.split(state.rng)
        # two data shards draw B//2 rows each with their axis fold
        parts = []
        for a in range(2):
            samp = jax.random.fold_in(state.rng, _SAMPLE_SALT)
            samp = jax.random.fold_in(samp, a)
            parts.append(jax.random.randint(samp, (B // 2,), 0,
                                            ds.num_examples))
        grads = []
        metrics = []
        for a, idx in enumerate(parts):
            g, mm, _ = compute_grads(
                dense, state.params, (x_all[idx], y_all[idx]),
                keep_prob=1.0, rng=jax.random.fold_in(sub, a),
                model_state=())
            grads.append(g)
            metrics.append(mm)
        g = jax.tree.map(lambda a_, b_: (a_ + b_) / 2, *grads)
        updates, opt_state = opt.update(g, state.opt_state, state.params,
                                        state.step)
        state = state._replace(params=apply_updates(state.params, updates),
                               opt_state=opt_state, step=state.step + 1,
                               rng=rng)

    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(dev_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    assert int(dev_state.step) == T


def test_device_sp_cli_end_to_end(tmp_path):
    """--seq_parallel --device_data through the production CLI: trains,
    checkpoints, finishes — the fence this replaces survived two rounds
    (loop.py:245-250 in r4)."""
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    try:
        flags.FLAGS._reset()
        flags.FLAGS._parse([
            f"--logdir={tmp_path}/logs", f"--data_dir={tmp_path}/none",
            "--dataset=lm", "--model=lm", "--seq_parallel",
            "--model_axis=4", "--seq_len=32", "--vocab_size=16",
            "--batch_size=8", "--training_iter=6", "--display_step=3",
            "--device_data", "--device_chunk=3", "--test_eval=false",
        ])
        res = train(flags.FLAGS, mode="sync")
        assert res.final_step == 6
        assert np.isfinite(res.train_metrics["loss"])
        import glob as g
        assert g.glob(f"{tmp_path}/logs/ckpt-*")
    finally:
        flags.FLAGS._reset()


def test_device_sp_image_classifier_runs():
    """The pooled-classifier variant: image split reshaped to token
    tiles on the host, labels replicated; the sampled tile feeds the
    seq_axis MiniTransformer."""
    from distributed_tensorflow_tpu.data.device_data import put_device_data_sp
    from distributed_tensorflow_tpu.models.transformer import MiniTransformer
    from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh
    from distributed_tensorflow_tpu.parallel.data_parallel import (
        replicate_state,
    )
    from distributed_tensorflow_tpu.parallel.mesh import MODEL_AXIS
    from distributed_tensorflow_tpu.training.device_step import (
        make_device_sp_train_step,
    )

    ds = read_data_sets("/nonexistent-sp", one_hot=True)
    model = MiniTransformer(seq_axis=MODEL_AXIS, d_model=32, num_heads=2,
                            num_blocks=1)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    data = put_device_data_sp(ds.train, mesh, per_token_targets=False,
                              token_shape=(model.seq_len, model.token_dim))
    opt = adam(1e-3)
    state = replicate_state(mesh, create_train_state(model, opt, seed=0))
    step = make_device_sp_train_step(model, opt, mesh, 8, keep_prob=1.0,
                                     chunk=2, per_token_targets=False)
    state, m = step(state, data)
    assert np.isfinite(float(m["loss"]))
    assert int(state.step) == 2


def test_device_data_lm_non_sp(tmp_path):
    """--device_data with --dataset lm, no SP: the resident sampler
    stages the token table and the plain chunked step trains (the r4
    fence at loop.py:434 is gone)."""
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    try:
        flags.FLAGS._reset()
        flags.FLAGS._parse([
            f"--logdir={tmp_path}/logs2", f"--data_dir={tmp_path}/none",
            "--dataset=lm", "--model=lm", "--seq_len=32",
            "--vocab_size=16", "--batch_size=8", "--training_iter=4",
            "--display_step=2", "--device_data", "--device_chunk=2",
            "--test_eval=false",
        ])
        res = train(flags.FLAGS, mode="local")
        assert res.final_step == 4
        assert np.isfinite(res.train_metrics["loss"])
    finally:
        flags.FLAGS._reset()
