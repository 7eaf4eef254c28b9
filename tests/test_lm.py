"""Causal-LM path: blockwise/ring attention equivalence, the
associative-recall dataset, per-token SP gradient reduction, and the
--seq_parallel --model lm CLI mode.

The per-token SP reduction has its own derivation (P independent loss
seeds partitioning d(P*L)/dtheta — parallel/sequence_parallel.py); the
trajectory test here is what pins it against the dense single-device
step, the same way test_attention.py pins the pooled classifier's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.data import read_data_sets
from distributed_tensorflow_tpu.data.lm import LMDataSet
from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.ops.attention import (
    blockwise_attention,
    multi_head_attention,
)
from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh
from distributed_tensorflow_tpu.parallel.data_parallel import replicate_state
from distributed_tensorflow_tpu.parallel.mesh import MODEL_AXIS
from distributed_tensorflow_tpu.parallel.sequence_parallel import (
    make_sp_eval_step,
    make_sp_train_step,
    stage_batch_sp,
)
from distributed_tensorflow_tpu.training import (
    create_train_state,
    get_optimizer,
    make_train_step,
)
from distributed_tensorflow_tpu.training.train_state import evaluate


# ----------------------------------------------------------- attention ops


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    """blockwise_attention streams k/v blocks through the online-softmax
    recurrence; values AND grads must equal the dense form (same math,
    O(S*block) memory)."""
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    b, s, h, dh = 2, 16, 2, 8
    q = jax.random.normal(kq, (b, s, h, dh))
    k = jax.random.normal(kk, (b, s, h, dh))
    v = jax.random.normal(kv, (b, s, h, dh))

    dense = multi_head_attention(q, k, v, causal=causal)
    for blk in (4, 8, 16):
        out = blockwise_attention(q, k, v, blk, causal=causal)
        np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-6)

    def loss_d(q, k, v):
        return jnp.sum(multi_head_attention(q, k, v, causal=causal) ** 2)

    def loss_b(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, 4, causal=causal) ** 2)

    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss_b, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gd, gb):
        np.testing.assert_allclose(a, b_, rtol=2e-4, atol=2e-5)


def test_blockwise_rejects_ragged_blocks():
    q = jnp.zeros((1, 12, 1, 4))
    with pytest.raises(ValueError, match="divide"):
        blockwise_attention(q, q, q, 5)


# ----------------------------------------------------------------- dataset


def test_lm_dataset_recall_structure():
    """Per-sequence fresh permutations: deterministic per seed, targets
    are the one-token shift, and the recall ceiling (fraction of
    positions with an in-context antecedent) sits strictly between the
    bigram floor and 1 — the quantity a working induction head
    approaches."""
    a = LMDataSet(64, seq_len=32, vocab_size=16, seed=3)
    b = LMDataSet(64, seq_len=32, vocab_size=16, seed=3)
    np.testing.assert_array_equal(a.images, b.images)
    np.testing.assert_array_equal(a.images[:, 1:], a.labels[:, :-1])
    x, y = a.next_batch(8)
    assert x.shape == (8, 32) and y.shape == (8, 32)
    assert x.dtype == np.int32 and y.dtype == np.int32
    ceiling = a.recall_ceiling()
    assert 0.3 < ceiling < 1.0
    # a permutation walk cannot be memorized across sequences: two
    # sequences starting from the same token diverge (fresh perms)
    c = LMDataSet(64, seq_len=32, vocab_size=16, seed=4)
    assert not np.array_equal(a.images, c.images)


def test_lm_dataset_via_read_data_sets():
    ds = read_data_sets("", dataset="lm", seq_len=32, vocab_size=16,
                        validation_size=8)
    assert ds.meta["kind"] == "lm"
    assert ds.meta["vocab_size"] == 16 and ds.meta["seq_len"] == 32
    assert ds.validation is not None and ds.validation.num_examples == 8
    # distinct split seeds: test sequences are not train sequences
    assert not np.array_equal(ds.train.images[:8], ds.test.images[:8])


# ------------------------------------------------------------------ model


def test_lm_per_token_loss_shapes():
    """(B, S, V) logits + (B, S) int targets flow through the SAME loss
    ops as the classifiers (ops/nn.py ndim rule) — no LM-special loss
    path to maintain."""
    model = TransformerLM(vocab_size=16, seq_len=8, d_model=32,
                          num_heads=2, num_blocks=1)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    logits = model.apply(params, x)
    assert logits.shape == (2, 8, 16)
    from distributed_tensorflow_tpu.ops import nn

    loss = nn.softmax_cross_entropy(logits, x)
    acc = nn.accuracy(logits, x)
    assert loss.shape == () and acc.shape == ()


def test_lm_causality():
    """Changing a future token must not change past logits (the causal
    mask is the LM's correctness invariant), in both the dense and the
    blockwise forms."""
    model_d = TransformerLM(vocab_size=16, seq_len=8, d_model=32,
                            num_heads=2, num_blocks=1)
    model_b = TransformerLM(vocab_size=16, seq_len=8, d_model=32,
                            num_heads=2, num_blocks=1, attn_block=4)
    params = model_d.init(jax.random.PRNGKey(0))
    x1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32) % 16
    x2 = x1.at[0, 5].set(9)  # mutate a future position
    for m in (model_d, model_b):
        l1, l2 = m.apply(params, x1), m.apply(params, x2)
        np.testing.assert_allclose(l1[0, :5], l2[0, :5], rtol=1e-6,
                                   atol=1e-6)
        assert not np.allclose(l1[0, 5:], l2[0, 5:])


@pytest.mark.parametrize("attn_block", [None, 4], ids=["dense", "attn_block"])
def test_lm_remat_matches(attn_block, monkeypatch):
    """remat=True recomputes blocks in backward; values and grads are
    bitwise-identical math (jax.checkpoint), so the loss trajectory must
    match the plain form. What a block keeps of a blockwise attention (its
    out and logsumexp) is what the recomputation would have made again:
    the trajectory is bit-equal to the checkpoint that keeps nothing."""
    from distributed_tensorflow_tpu.models import transformer

    mk = lambda remat: TransformerLM(vocab_size=16, seq_len=8, d_model=32,
                                     num_heads=2, num_blocks=2, remat=remat,
                                     attn_block=attn_block)
    x = jnp.arange(32, dtype=jnp.int32).reshape(4, 8) % 16
    y = (x + 1) % 16

    def two_steps(model):
        opt = get_optimizer("sgd", 0.1)
        state = create_train_state(model, opt, seed=0)
        step = make_train_step(model, opt, keep_prob=1.0)
        for _ in range(2):
            state, metrics = step(state, (x, y))
        return float(metrics["loss"]), jax.tree.leaves(state.params)

    plain, _ = two_steps(mk(False))
    remat, params = two_steps(mk(True))
    np.testing.assert_allclose(plain, remat, rtol=1e-6)
    monkeypatch.setattr(
        transformer, "_remat",
        lambda fn, static: jax.checkpoint(fn, static_argnums=static))
    bare, bare_params = two_steps(mk(True))
    assert bare == remat
    for a, b in zip(params, bare_params):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------- SP per-token reduction


def test_lm_sp_trajectory_matches_dense():
    """THE per-token reduction test: the SP step (ring attention over a
    4-way token axis, per-token targets sharded with their tokens,
    uniform pmean) must track the dense single-device trajectory — the
    derivation in parallel/sequence_parallel.py made exact."""
    V, S, B = 16, 32, 8
    dense = TransformerLM(vocab_size=V, seq_len=S, d_model=32,
                          num_heads=2, num_blocks=2)
    spm = TransformerLM(vocab_size=V, seq_len=S, d_model=32,
                        num_heads=2, num_blocks=2, seq_axis=MODEL_AXIS)
    opt = get_optimizer("adam", 1e-3)
    s_d = create_train_state(dense, opt, seed=0)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    s_s = replicate_state(mesh, create_train_state(spm, opt, seed=0))
    step_d = make_train_step(dense, opt, keep_prob=1.0)
    step_s = make_sp_train_step(spm, opt, mesh, keep_prob=1.0,
                                per_token_targets=True)
    eval_s = make_sp_eval_step(spm, mesh, per_token_targets=True)

    ds = LMDataSet(64, seq_len=S, vocab_size=V, seed=0)
    batch = None
    for i in range(4):
        batch = ds.next_batch(B)
        s_d, m_d = step_d(s_d, batch)
        s_s, m_s = step_s(s_s, stage_batch_sp(mesh, batch,
                                              per_token_targets=True))
        # metrics pmean over the token axis = the global token mean
        np.testing.assert_allclose(float(m_d["loss"]), float(m_s["loss"]),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(m_d["accuracy"]),
                                   float(m_s["accuracy"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(jax.device_get(s_d.params)),
                    jax.tree.leaves(jax.device_get(s_s.params))):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-6)
    # the SP eval step reports the same metrics as the dense eval
    m_sp = eval_s(s_s.params, stage_batch_sp(mesh, batch,
                                             per_token_targets=True))
    from distributed_tensorflow_tpu.training import make_eval_step

    m_de = make_eval_step(dense)(s_d.params, batch, ())
    np.testing.assert_allclose(float(m_sp["loss"]), float(m_de["loss"]),
                               rtol=1e-5)


def test_lm_sp_dropout_runs():
    """keep_prob < 1 in SP mode: per-token dropout folds the sequence
    index (decorrelated masks per shard) — not equal to the dense run by
    construction, but it must execute and produce finite loss."""
    V, S = 16, 16
    spm = TransformerLM(vocab_size=V, seq_len=S, d_model=32,
                        num_heads=2, num_blocks=1, seq_axis=MODEL_AXIS)
    opt = get_optimizer("sgd", 0.05)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    state = replicate_state(mesh, create_train_state(spm, opt, seed=0))
    step = make_sp_train_step(spm, opt, mesh, keep_prob=0.8,
                              per_token_targets=True)
    ds = LMDataSet(16, seq_len=S, vocab_size=V, seed=0)
    state, m = step(state, stage_batch_sp(mesh, ds.next_batch(4),
                                          per_token_targets=True))
    assert np.isfinite(float(m["loss"]))


# ----------------------------------------------------------- convergence


def test_lm_learns_in_context_recall():
    """The induction task is unlearnable without attention (fresh
    permutation per sequence: the bigram/MLP floor is 1/V). The tiny LM
    must clear that floor decisively within a short budget — evidence
    the causal attention + per-token loss actually learn."""
    V, S = 16, 32
    ds = read_data_sets("", dataset="lm", seq_len=S, vocab_size=V)
    model = TransformerLM(vocab_size=V, seq_len=S, d_model=64,
                          num_heads=2, num_blocks=2)
    opt = get_optimizer("adam", 3e-3)
    state = create_train_state(model, opt, seed=0)
    step = make_train_step(model, opt, keep_prob=1.0)
    for _ in range(200):
        state, _ = step(state, ds.train.next_batch(32))
    m = evaluate(model, state.params, ds.test, batch_size=256)
    assert m["accuracy"] > 3.0 / V, m  # 3x the no-attention floor


# -------------------------------------------------------------- CLI mode


def test_seq_parallel_cli_mode_lm(tmp_path):
    """--seq_parallel --model lm --dataset lm trains through the FULL
    production loop (staging, supervisor, display evals, final eval,
    checkpoint) on the 2x4 mesh."""
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._reset()
    flags.FLAGS._parse([
        f"--logdir={tmp_path}/logs", f"--data_dir={tmp_path}/none",
        "--dataset=lm", "--model=lm", "--seq_parallel", "--model_axis=4",
        "--seq_len=32", "--vocab_size=16", "--d_model=32",
        "--num_heads=2", "--num_blocks=1",
        "--training_iter=6", "--batch_size=8", "--display_step=3",
        "--optimizer=adam", "--learning_rate=0.002",
        "--save_model_secs=100000",
    ])
    try:
        res = train(flags.FLAGS, mode="sync")
        assert res.final_step == 6
        assert res.test_metrics is not None
        assert np.isfinite(res.test_metrics["loss"])
    finally:
        flags.FLAGS._reset()


def test_lm_model_dataset_pairing_guards(tmp_path):
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._reset()
    flags.FLAGS._parse([
        f"--logdir={tmp_path}/l", f"--data_dir={tmp_path}/n",
        "--dataset=lm", "--model=deep_cnn", "--training_iter=1",
    ])
    try:
        with pytest.raises(ValueError, match="image model"):
            train(flags.FLAGS, mode="local")
        flags.FLAGS._reset()
        flags.FLAGS._parse([
            f"--logdir={tmp_path}/l2", f"--data_dir={tmp_path}/n",
            "--dataset=mnist", "--model=lm", "--training_iter=1",
        ])
        with pytest.raises(ValueError, match="token sequences"):
            train(flags.FLAGS, mode="local")
    finally:
        flags.FLAGS._reset()


def test_attn_block_rejected_with_seq_parallel(tmp_path):
    """--attn_block (local blockwise) and --seq_parallel (ring) are
    mutually exclusive attention flavors; the loop must refuse loudly
    instead of silently ring-attending and blowing up (or quietly
    diverging from the doc) in the final blockwise eval."""
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._reset()
    flags.FLAGS._parse([
        f"--logdir={tmp_path}/l", f"--data_dir={tmp_path}/n",
        "--dataset=lm", "--model=lm", "--seq_parallel", "--model_axis=4",
        "--seq_len=32", "--vocab_size=16", "--attn_block=48",
        "--training_iter=1",
    ])
    try:
        with pytest.raises(ValueError, match="mutually exclusive"):
            train(flags.FLAGS, mode="sync")
    finally:
        flags.FLAGS._reset()


def test_lm_validation_split_any_size():
    """The lm validation split is generated independently (not carved
    from a finite array) — sizes beyond the test split must work."""
    ds = read_data_sets("", dataset="lm", seq_len=16, vocab_size=16,
                        validation_size=600)
    assert ds.validation.num_examples == 600


def test_sp_accum_and_clip_match_dense():
    """--accum_steps and --clip_norm compose with the SP step EXACTLY:
    accumulation is a pre-reduction mean over microbatches and clip a
    post-reduction transform, so SP+accum+clip must track the dense
    step with the same accum+clip."""
    from distributed_tensorflow_tpu.training.train_state import (
        clip_by_global_norm,
    )

    V, S, B = 16, 32, 8
    clip = clip_by_global_norm(0.05)  # tight enough to bind every step
    dense = TransformerLM(vocab_size=V, seq_len=S, d_model=32,
                          num_heads=2, num_blocks=1)
    spm = TransformerLM(vocab_size=V, seq_len=S, d_model=32,
                        num_heads=2, num_blocks=1, seq_axis=MODEL_AXIS)
    opt = get_optimizer("sgd", 0.5)
    s_d = create_train_state(dense, opt, seed=0)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    s_s = replicate_state(mesh, create_train_state(spm, opt, seed=0))
    step_d = make_train_step(dense, opt, keep_prob=1.0,
                             grad_transform=clip, accum_steps=2)
    step_s = make_sp_train_step(spm, opt, mesh, keep_prob=1.0,
                                per_token_targets=True,
                                grad_transform=clip, accum_steps=2)
    ds = LMDataSet(32, seq_len=S, vocab_size=V, seed=1)
    for _ in range(3):
        b = ds.next_batch(B)
        s_d, m_d = step_d(s_d, b)
        s_s, m_s = step_s(s_s, stage_batch_sp(mesh, b,
                                              per_token_targets=True))
        np.testing.assert_allclose(float(m_d["loss"]), float(m_s["loss"]),
                                   rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(jax.device_get(s_d.params)),
                     jax.tree.leaves(jax.device_get(s_s.params))):
        np.testing.assert_allclose(a, b_, rtol=3e-4, atol=3e-6)


def test_sp_full_split_eval_matches_dense():
    """The sharded full-split evaluator (periodic/final SP evals) must
    equal the dense evaluate() on the same split — including a tail
    smaller than the data axis, which it handles by replication (mean
    over replicated examples == mean over the tail, exactly)."""
    from distributed_tensorflow_tpu.training.loop import (
        _make_sp_full_split_eval,
    )

    V, S = 16, 32
    dense = TransformerLM(vocab_size=V, seq_len=S, d_model=32,
                          num_heads=2, num_blocks=1)
    spm = TransformerLM(vocab_size=V, seq_len=S, d_model=32,
                        num_heads=2, num_blocks=1, seq_axis=MODEL_AXIS)
    opt = get_optimizer("sgd", 0.1)
    state_d = create_train_state(dense, opt, seed=0)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    state_s = replicate_state(mesh, create_train_state(spm, opt, seed=0))
    sp_eval = make_sp_eval_step(spm, mesh, per_token_targets=True)
    stage = lambda b: stage_batch_sp(mesh, b, per_token_targets=True)
    # 13 examples, eval batch 8, data_ways 2: one full batch of 8, one
    # of 4, and a 1-example tail exercising the replication path
    split = LMDataSet(13, seq_len=S, vocab_size=V, seed=5)
    full_eval = _make_sp_full_split_eval(sp_eval, stage, data_ways=2,
                                         batch_size=8)
    m_sp = full_eval(state_s, split)
    m_dense = evaluate(dense, state_d.params, split, batch_size=8)
    np.testing.assert_allclose(m_sp["loss"], m_dense["loss"], rtol=1e-5)
    np.testing.assert_allclose(m_sp["accuracy"], m_dense["accuracy"],
                               rtol=1e-6)


def test_sp_span_flag_requires_seq_parallel(tmp_path):
    """--sp_span_hosts without --seq_parallel must refuse loudly (the
    loud-pairing convention), not silently train a different mode —
    at PARSE time since r18 (the check was promoted out of the
    dttlint DTT006 baseline into _validate_pairing_flags), and the
    train()-time library guard stays for non-CLI callers."""
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._reset()
    try:
        with pytest.raises(ValueError, match="sp_span_hosts"):
            flags.FLAGS._parse([
                f"--logdir={tmp_path}/l", f"--data_dir={tmp_path}/n",
                "--sp_span_hosts", "--model_axis=8",
                "--training_iter=1",
            ])
        # the library-level guard, for callers that never parse argv
        flags.FLAGS._reset()
        flags.FLAGS._parse([
            f"--logdir={tmp_path}/l", f"--data_dir={tmp_path}/n",
            "--model_axis=8", "--training_iter=1",
        ])
        flags.FLAGS.sp_span_hosts = True  # post-parse, bypasses validators
        with pytest.raises(ValueError, match="sp_span_hosts"):
            train(flags.FLAGS, mode="sync")
    finally:
        flags.FLAGS._reset()


def test_lm_dataset_large_vocab_storage():
    """vocab > 256 switches to u16 storage; ids round-trip exactly."""
    ds = LMDataSet(8, seq_len=16, vocab_size=1000, seed=0)
    x, y = ds.next_batch(4)
    assert x.dtype == np.int32
    assert int(x.max()) < 1000 and int(x.min()) >= 0
    assert ds._tokens.dtype == np.uint16
    with pytest.raises(ValueError, match="vocab_size"):
        LMDataSet(4, seq_len=8, vocab_size=1)


# ------------------------------------------- streamed softmax-CE (r5)


@pytest.mark.parametrize("cd", [None, jnp.bfloat16])
def test_streamed_ce_matches_dense_head(cd):
    """streamed_softmax_ce_head == dense(head) + softmax_cross_entropy +
    accuracy, values AND grads, under jit (the train-step condition) —
    including a block size that does NOT divide the token count (the
    padding path). bf16 note: dh is bitwise (same per-block chain); dw/db
    tolerance covers the accumulation-order difference (streamed sums
    per-block partials in f32 — tighter than the dense single bf16 dot)."""
    from distributed_tensorflow_tpu.ops import nn

    rng = np.random.default_rng(1)
    B, S, d, V = 2, 7, 16, 37  # N=14, block=4 -> 2 pad rows
    h = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    if cd is not None:
        h = h.astype(cd)
    w = jnp.asarray(rng.normal(size=(d, V)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(V,)) * 0.3, jnp.float32)
    y = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)

    @jax.jit
    def dense_lg(h, w, b):
        logits = nn.dense(h, w, b, compute_dtype=cd).astype(jnp.float32)
        return nn.softmax_cross_entropy(logits, y), nn.accuracy(logits, y)

    @jax.jit
    def stream_lg(h, w, b):
        return nn.streamed_softmax_ce_head(h, w, b, y, block=4,
                                           compute_dtype=cd)

    (l0, a0), (l1, a1) = dense_lg(h, w, b), stream_lg(h, w, b)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    assert float(a0) == float(a1)
    g0 = jax.jit(jax.grad(lambda *a: dense_lg(*a)[0], argnums=(0, 1, 2)))(
        h, w, b)
    g1 = jax.jit(jax.grad(lambda *a: stream_lg(*a)[0], argnums=(0, 1, 2)))(
        h, w, b)
    tol = 1e-6 if cd is None else 6e-3
    for x0, x1 in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(x0, np.float32),
                                   np.asarray(x1, np.float32), atol=tol)


def test_lm_ce_block_matches_dense_loss_and_grads():
    """The model-level hook: a ce_block TransformerLM must produce the
    same loss/accuracy/param-grads as the identical model without it
    (f32 — exact to fp tolerance)."""
    from distributed_tensorflow_tpu.training.train_state import (
        loss_and_metrics,
    )

    kw = dict(vocab_size=37, seq_len=16, d_model=32, num_heads=4,
              num_blocks=2)
    m0 = TransformerLM(**kw)
    m1 = TransformerLM(**kw, ce_block=8)
    p = m0.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.integers(0, 37, size=(3, 16)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 37, size=(3, 16)), jnp.int32)

    f0 = jax.jit(lambda p: loss_and_metrics(m0, p, (x, y), train=True)[0])
    f1 = jax.jit(lambda p: loss_and_metrics(m1, p, (x, y), train=True)[0])
    np.testing.assert_allclose(float(f0(p)), float(f1(p)), rtol=1e-6)
    g0 = jax.jit(jax.grad(f0))(p)
    g1 = jax.jit(jax.grad(f1))(p)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_lm_ce_block_trains_and_evaluates():
    """End to end through the standard step/eval machinery: training a
    ce_block model reduces loss, and evaluate() routes through the
    streamed head (same loss_and_metrics hook)."""
    model = TransformerLM(vocab_size=16, seq_len=32, d_model=32,
                          num_heads=2, num_blocks=2, attn_block=8,
                          ce_block=16)
    opt = get_optimizer("adam", 1e-2)
    state = create_train_state(model, opt, seed=0)
    step = make_train_step(model, opt, keep_prob=1.0)
    ds = LMDataSet(16, seq_len=32, vocab_size=16, seed=0)
    first = None
    for i in range(30):
        state, m = step(state, ds.next_batch(8))
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first, (first, float(m["loss"]))
    ev = evaluate(model, state.params, _SplitLike(ds, 64), batch_size=32)
    assert 0.0 <= ev["accuracy"] <= 1.0 and np.isfinite(ev["loss"])


class _SplitLike:
    """Minimal dataset-split adapter over LMDataSet for evaluate()."""

    def __init__(self, ds, n):
        x, y = ds.next_batch(n)
        self.images, self.labels = x, y
        self.num_examples = n


def test_lm_ce_block_cli_flag(tmp_path):
    """--ce_block reaches the model through build_model_for."""
    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import build_model_for

    flags.define_reference_flags()
    meta = {"kind": "lm", "vocab_size": 64, "seq_len": 128}
    try:
        flags.FLAGS._reset()
        flags.FLAGS._parse(["--model=lm", "--dataset=lm", "--ce_block=64"])
        assert build_model_for(flags.FLAGS, meta).ce_block == 64
        flags.FLAGS._reset()
        flags.FLAGS._parse(["--model=lm", "--dataset=lm"])
        assert build_model_for(flags.FLAGS, meta).ce_block is None
    finally:
        flags.FLAGS._reset()


def test_streamed_ce_out_of_range_labels_match_dense():
    """Out-of-range ids: zero loss and zero gradient, exactly like
    softmax_cross_entropy's all-zero one-hot row (the documented
    semantics for labels that bypass the loaders' validation)."""
    from distributed_tensorflow_tpu.ops import nn

    rng = np.random.default_rng(3)
    B, S, d, V = 2, 6, 8, 11
    h = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, V)) * 0.3, jnp.float32)
    b = jnp.zeros((V,), jnp.float32)
    y = np.asarray(rng.integers(0, V, size=(B, S)), np.int32)
    y[0, 0] = V + 3   # invalid
    y[1, 2] = V       # boundary-invalid
    y = jnp.asarray(y)

    @jax.jit
    def dense_l(h, w, b):
        logits = nn.dense(h, w, b).astype(jnp.float32)
        return nn.softmax_cross_entropy(logits, y)

    @jax.jit
    def stream_l(h, w, b):
        return nn.streamed_softmax_ce_head(h, w, b, y, block=4)[0]

    np.testing.assert_allclose(float(dense_l(h, w, b)),
                               float(stream_l(h, w, b)), rtol=1e-6)
    g0 = jax.jit(jax.grad(dense_l, argnums=(0, 1, 2)))(h, w, b)
    g1 = jax.jit(jax.grad(stream_l, argnums=(0, 1, 2)))(h, w, b)
    for a, c in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)


def test_sp_ce_block_matches_sp_dense_head():
    """ce_block composes with SP: the streamed head's shard-local mean
    is exactly the per-token derivation's loss seed, so trajectories
    match the unstreamed SP step to fp tolerance."""
    mesh = make_mesh(MeshSpec(data=2, model=4))
    kw = dict(vocab_size=16, seq_len=32, d_model=32, num_heads=2,
              num_blocks=2, seq_axis=MODEL_AXIS)
    m_plain = TransformerLM(**kw)
    m_ce = TransformerLM(**kw, ce_block=4)
    # sgd, not adam: updates linear in grads, so the pin measures the
    # streamed head's gradient fidelity instead of adam's sqrt(v)
    # amplification of f32 accumulation-order ulps
    opt = get_optimizer("sgd", 0.05)
    base = create_train_state(m_plain, opt, seed=0)

    states = []
    for m in (m_plain, m_ce):
        ds = LMDataSet(64, seq_len=32, vocab_size=16, seed=5)  # same walk
        state = replicate_state(mesh, base)
        step = make_sp_train_step(m, opt, mesh, keep_prob=1.0,
                                  per_token_targets=True, donate=False)
        for i in range(3):
            b = stage_batch_sp(mesh, ds.next_batch(8),
                               per_token_targets=True)
            state, metrics = step(state, b)
        states.append((state, metrics))
    (s0, m0), (s1, m1) = states
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(m0["accuracy"]),
                               float(m1["accuracy"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
