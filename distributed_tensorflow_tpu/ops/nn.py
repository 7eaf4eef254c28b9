"""Neural-net op layer: the XLA:TPU equivalents of the reference's C++ kernels.

The reference calls TensorFlow's C++ kernels — Conv2D/BiasAdd/Relu
(``MNISTDist.py:52-56``), MaxPool (``:59-62``), MatMul (``:82-89``),
SoftmaxCrossEntropyWithLogits (``:148``). Here every op is a pure function
lowered by XLA onto the TPU's MXU (convs/matmuls) and VPU (elementwise),
letting the compiler fuse bias+relu into the conv rather than hand-scheduling.

Layout choices are TPU-first: NHWC activations and HWIO kernels (the
reference's layout too, which XLA:TPU handles natively), channels as the
minor dimension so tiles map onto the (8,128) vregs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.utils.profiling import scoped

# dimension_numbers matching the reference's NHWC/HWIO convention
# (tf.nn.conv2d default, MNISTDist.py:54)
_CONV_DIMS = ("NHWC", "HWIO", "NHWC")


def conv2d(x, w, b=None, strides: int = 1, *, compute_dtype=None):
    """SAME-padded conv + bias + ReLU (reference ``conv2d``, MNISTDist.py:52-56).

    One ``lax.conv_general_dilated`` call; XLA fuses the bias-add and ReLU
    into the conv epilogue on TPU. ``compute_dtype=jnp.bfloat16`` runs the
    MXU in bf16 with f32 accumulation (preferred_element_type) — params stay
    in f32 master copies.
    """
    in_dtype = x.dtype
    if compute_dtype is not None:
        # uniform low-precision compute: the TPU MXU accumulates bf16
        # matmul/conv products in f32 in hardware, and keeping operand and
        # result dtypes equal keeps the conv VJP well-typed
        x = x.astype(compute_dtype)
        w = w.astype(compute_dtype)
    y = lax.conv_general_dilated(
        x,
        w,
        window_strides=(strides, strides),
        padding="SAME",
        dimension_numbers=_CONV_DIMS,
    )
    if compute_dtype is not None:
        y = y.astype(in_dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return jax.nn.relu(y)


def maxpool2d(x, k: int = 2):
    """k×k max-pool, stride k, SAME padding (reference ``maxpool2d``, MNISTDist.py:59-62)."""
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        window_dimensions=(1, k, k, 1),
        window_strides=(1, k, k, 1),
        padding="SAME",
    )


def dense(x, w, b=None, *, compute_dtype=None):
    """x @ w + b (reference FC layers, MNISTDist.py:83,89).

    With ``compute_dtype`` the matmul runs in that dtype end-to-end
    (operands and result), then casts back. On TPU the MXU still
    accumulates bf16 products in f32 in hardware; other backends may
    keep low-precision partial sums."""
    if compute_dtype is not None:
        y = jnp.dot(x.astype(compute_dtype), w.astype(compute_dtype)).astype(x.dtype)
    else:
        y = jnp.dot(x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def normalize_if_u8(x, compute_dtype=None):
    """Thin-wire input contract, shared by every model's ``apply``: uint8
    pixels that crossed the host->device link raw are normalized to [0,1]
    on device (the scale fuses into the first conv/matmul); any other
    dtype passes through untouched."""
    if x.dtype == jnp.uint8:
        return x.astype(compute_dtype or jnp.float32) / 255.0
    return x


def dropout(x, keep_prob, rng, *, deterministic: bool = False):
    """Inverted dropout (reference ``tf.nn.dropout``, MNISTDist.py:86).

    ``keep_prob`` may be a traced scalar (mirrors the reference's
    ``keep_prob`` placeholder, MNISTDist.py:115). ``deterministic=True``
    (or rng None) is the eval path — identity, like feeding 1.0.
    """
    if deterministic or rng is None:
        return x
    keep_prob = jnp.asarray(keep_prob, x.dtype)
    mask = jax.random.bernoulli(rng, keep_prob, x.shape)
    # guard against keep_prob == 0 division (XLA-safe select)
    scale = jnp.where(keep_prob > 0, 1.0 / jnp.maximum(keep_prob, 1e-8), 0.0)
    return jnp.where(mask, x * scale, jnp.zeros_like(x))


def softmax_cross_entropy(logits, labels):
    """Mean softmax cross-entropy over the batch (reference cost, MNISTDist.py:148).

    ``labels`` may be one-hot [B, C] (reference parity) or integer class
    ids [B] (the thin-wire input path: int labels cost 1/40th the
    host->device bytes of one-hot f32). Numerically-stable log-softmax
    form; XLA fuses the whole reduction.

    Integer labels must be in [0, C): out-of-range ids one-hot to an
    all-zero row and contribute zero loss/gradient (jax.nn.one_hot
    semantics) rather than clamping. The loaders ENFORCE validity at
    DataSet construction (datasets.py raises on any id outside
    [0, num_classes)); callers feeding external labels should validate
    upstream.
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    if labels.ndim == logits.ndim - 1:  # integer class ids
        # one-hot CONTRACTION, not take_along_axis: a [B]-indexed gather
        # lowers to a sequential per-example dynamic-slice loop on TPU —
        # profiled at 0.42 ms/step (17% of the whole train step!) at
        # batch 2048, vs ~nothing for the masked sum the VPU vectorizes
        # (PERF.md round 3). Same value, same gradient.
        onehot = jax.nn.one_hot(labels, logp.shape[-1], dtype=logp.dtype)
        # where(), not onehot*logp: a masked class with logit -inf gives
        # logp=-inf there, and 0 * -inf = NaN would poison the sum — the
        # gather this replaces only ever read the label's entry
        per_example = -jnp.sum(jnp.where(onehot != 0, logp, 0.0), axis=-1)
    else:
        per_example = -jnp.sum(labels.astype(jnp.float32) * logp, axis=-1)
    return jnp.mean(per_example)


def _head_logits(h_blk, w, b, cd):
    """One row-block's logits, numerically IDENTICAL to the unstreamed
    head: ``dense(h, w, b, compute_dtype=cd).astype(f32)`` (the LM head,
    models/transformer.py) — dot in ``cd``, cast back to h's dtype, bias
    in that dtype, then the f32 cast the loss sees."""
    if cd is not None:
        y = jnp.dot(h_blk.astype(cd), w.astype(cd)).astype(h_blk.dtype)
    else:
        y = jnp.dot(h_blk, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 8))
def _streamed_ce(h2, w, b, labels2, block, cd, n_valid, weights2=None,
                 denom=None):
    return _streamed_ce_forward(h2, w, b, labels2, block, cd, n_valid,
                                weights2, denom)[0]


def _row_weights(vmask, wts):
    """One block's f32 weight of each row in the loss and in the hit
    count: the padding mask alone, or times the caller's weights (a row
    of weight nought is no hit either)."""
    vf = vmask.astype(jnp.float32)
    if wts is None:
        return vf, vf
    wts = wts.astype(jnp.float32)
    return vf * wts, vf * (wts > 0).astype(jnp.float32)


def _weight_blocks(weights2, nb, block):
    """The scan's operand for the rows' weights: () where there are none
    (the scan is then the unweighted head's, to the operation)."""
    return () if weights2 is None else (weights2.reshape(nb, block),)


def _streamed_ce_forward(h2, w, b, labels2, block, cd, n_valid,
                         weights2=None, denom=None):
    """Forward scan over row blocks; returns ((loss, acc), lse (N,))."""
    n_pad, d = h2.shape
    nb = n_pad // block
    hb = h2.reshape(nb, block, d)
    lb = labels2.reshape(nb, block)
    valid = (jnp.arange(n_pad) < n_valid).reshape(nb, block)

    def step(carry, inp):
        h_blk, lbl, vmask, *wts = inp
        logits = _head_logits(h_blk, w, b, cd)  # (R, V) f32 — the peak
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        onehot = jax.nn.one_hot(lbl, logits.shape[-1], dtype=logits.dtype)
        # where(), not onehot*logits — same -inf rationale as
        # softmax_cross_entropy above
        lab = jnp.sum(jnp.where(onehot != 0, logits, 0.0), axis=-1)
        vf, hf = _row_weights(vmask, *wts or (None,))
        # out-of-range ids: zero loss AND zero gradient, matching
        # softmax_cross_entropy's one_hot semantics (all-zero row);
        # accuracy still counts the row in its denominator (a miss) —
        # exactly what argmax == out-of-range-id yields
        ok = ((lbl >= 0) & (lbl < logits.shape[-1])).astype(jnp.float32)
        loss_sum, corr_sum = carry
        loss_sum = loss_sum + jnp.sum((lse - lab) * vf * ok)
        hit = (jnp.argmax(logits, axis=-1) == lbl).astype(jnp.float32)
        corr_sum = corr_sum + jnp.sum(hit * hf)
        return (loss_sum, corr_sum), lse

    (loss_sum, corr_sum), lses = lax.scan(
        step, (jnp.float32(0.0), jnp.float32(0.0)),
        (hb, lb, valid) + _weight_blocks(weights2, nb, block))
    inv = jnp.float32(1.0 / (n_valid if denom is None else denom))
    return (loss_sum * inv, corr_sum * inv), lses


def _streamed_ce_fwd(h2, w, b, labels2, block, cd, n_valid, weights2=None,
                     denom=None):
    out, lses = _streamed_ce_forward(h2, w, b, labels2, block, cd, n_valid,
                                     weights2, denom)
    return out, (h2, w, b, labels2, lses, weights2)


def _head_block_grads(g, h_blk, w, cd):
    """One row-block's (dh, dw) from its dL/dlogits ``g`` (f32): both
    products in ``cd`` where there is one, dh back in h's dtype, dw f32."""
    if cd is not None:
        gc = g.astype(cd)
        return (jnp.dot(gc, w.astype(cd).T).astype(h_blk.dtype),
                jnp.dot(h_blk.astype(cd).T, gc).astype(jnp.float32))
    return jnp.dot(g, w.T).astype(h_blk.dtype), jnp.dot(h_blk.T, g)


@scoped("lm_head")
def _streamed_ce_bwd(block, cd, n_valid, denom, res, ct):
    """The streamed backward: recompute each block's logits from
    (h, w, b) and its saved row logsumexps — dL/dlogits = softmax -
    onehot, never materialized beyond one (block, V) panel. dw/db
    accumulate in f32 across the scan; dh blocks stack. The accuracy
    output's cotangent is ignored (argmax has no gradient)."""
    h2, w, b, labels2, lses, weights2 = res
    g_loss = ct[0]
    n_pad, d = h2.shape
    nb = n_pad // block
    hb = h2.reshape(nb, block, d)
    lb = labels2.reshape(nb, block)
    valid = (jnp.arange(n_pad) < n_valid).reshape(nb, block)
    lsb = lses.reshape(nb, block)
    scale = g_loss.astype(jnp.float32) / (n_valid if denom is None else denom)

    def step(carry, inp):
        dw, db = carry
        h_blk, lbl, vmask, lse_blk, *wts = inp
        logits = _head_logits(h_blk, w, b, cd)
        p = jnp.exp(logits - lse_blk[:, None])
        onehot = jax.nn.one_hot(lbl, logits.shape[-1], dtype=jnp.float32)
        ok = ((lbl >= 0) & (lbl < logits.shape[-1])).astype(jnp.float32)
        vf = _row_weights(vmask, *wts or (None,))[0]
        g = (p - onehot) * (vf * ok * scale)[:, None]
        dh_blk, dw_blk = _head_block_grads(g, h_blk, w, cd)
        dw = dw + dw_blk
        if b is not None:
            db = db + jnp.sum(g, axis=0)
        return (dw, db), dh_blk

    dw0 = jnp.zeros(w.shape, jnp.float32)
    db0 = None if b is None else jnp.zeros(b.shape, jnp.float32)
    (dw, db), dhb = lax.scan(
        step, (dw0, db0),
        (hb, lb, valid, lsb) + _weight_blocks(weights2, nb, block))
    import numpy as np

    from jax.dtypes import float0

    return (dhb.reshape(n_pad, d), dw.astype(w.dtype),
            None if b is None else db.astype(b.dtype),
            np.zeros(labels2.shape, float0),
            None if weights2 is None else jnp.zeros_like(weights2))


_streamed_ce.defvjp(_streamed_ce_fwd, _streamed_ce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _streamed_ce_rows(h2, w, b, labels2, block, cd):
    return _streamed_ce_rows_fwd(h2, w, b, labels2, block, cd)[0]


def _streamed_ce_rows_fwd(h2, w, b, labels2, block, cd):
    """Forward scan over row blocks: ((every row's cross-entropy, every
    row's hit), residuals). An out-of-range id costs nought, as above."""
    n, d = h2.shape
    nb = n // block

    def step(_, inp):
        h_blk, lbl = inp
        logits = _head_logits(h_blk, w, b, cd)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        onehot = jax.nn.one_hot(lbl, logits.shape[-1], dtype=logits.dtype)
        lab = jnp.sum(jnp.where(onehot != 0, logits, 0.0), axis=-1)
        ok = ((lbl >= 0) & (lbl < logits.shape[-1])).astype(jnp.float32)
        hit = (jnp.argmax(logits, axis=-1) == lbl).astype(jnp.float32)
        return None, ((lse - lab) * ok, hit, lse)

    _, (ce, hit, lses) = lax.scan(
        step, None, (h2.reshape(nb, block, d), labels2.reshape(nb, block)))
    return ((ce.reshape(n), hit.reshape(n)),
            (h2, w, b, labels2, lses.reshape(n)))


@scoped("lm_head")
def _streamed_ce_rows_bwd(block, cd, res, ct):
    """``_streamed_ce_bwd`` with a cotangent a ROW (``ct[0]``, what the
    caller's weighting of the rows hands back) where that one has the
    loss's one scalar; the hits' cotangent is ignored. Its own scan, so
    that the scalar form's program stays what it was."""
    h2, w, b, labels2, lses = res
    n, d = h2.shape
    nb = n // block

    def step(carry, inp):
        dw, db = carry
        h_blk, lbl, lse_blk, g_rows = inp
        logits = _head_logits(h_blk, w, b, cd)
        p = jnp.exp(logits - lse_blk[:, None])
        onehot = jax.nn.one_hot(lbl, logits.shape[-1], dtype=jnp.float32)
        ok = ((lbl >= 0) & (lbl < logits.shape[-1])).astype(jnp.float32)
        g = (p - onehot) * (ok * g_rows.astype(jnp.float32))[:, None]
        dh_blk, dw_blk = _head_block_grads(g, h_blk, w, cd)
        dw = dw + dw_blk
        if b is not None:
            db = db + jnp.sum(g, axis=0)
        return (dw, db), dh_blk

    dw0 = jnp.zeros(w.shape, jnp.float32)
    db0 = None if b is None else jnp.zeros(b.shape, jnp.float32)
    (dw, db), dhb = lax.scan(
        step, (dw0, db0),
        (h2.reshape(nb, block, d), labels2.reshape(nb, block),
         lses.reshape(nb, block), ct[0].reshape(nb, block)))
    import numpy as np

    from jax.dtypes import float0

    return (dhb.reshape(n, d), dw.astype(w.dtype),
            None if b is None else db.astype(b.dtype),
            np.zeros(labels2.shape, float0))


_streamed_ce_rows.defvjp(_streamed_ce_rows_fwd, _streamed_ce_rows_bwd)


def _padded_rows(h, labels, block):
    """(h as (N + pad, d), labels as (N + pad,), N, pad): zero rows of
    label 0 up to a whole number of blocks."""
    d = h.shape[-1]
    n_valid = 1
    for s in h.shape[:-1]:
        n_valid *= int(s)
    if labels.shape != h.shape[:-1]:
        raise ValueError(f"labels shape {labels.shape} != hidden leading "
                         f"shape {h.shape[:-1]}")
    h2 = h.reshape(n_valid, d)
    labels2 = labels.reshape(n_valid)
    pad = (-n_valid) % int(block)
    if pad:
        # zero rows, label 0, masked out by n_valid inside the op; the
        # concat/slice transpose drops their gradient automatically
        h2 = jnp.concatenate([h2, jnp.zeros((pad, d), h2.dtype)])
        labels2 = jnp.concatenate(
            [labels2, jnp.zeros((pad,), labels2.dtype)])
    return h2, labels2, n_valid, pad


@scoped("lm_head")
def streamed_softmax_ce_rows(h, w, b, labels, block: int,
                             compute_dtype=None):
    """The streamed head of ``streamed_softmax_ce_head`` with nothing summed:
    (every row's cross-entropy, every row's hit), both f32 of the labels'
    shape, differentiable in h, w and b under ANY cotangent a row. For a
    loss whose weight of a row is itself a function of the parameters (a
    learned exit distribution over passes: the gradient reaches the
    weights through the rows' cross-entropies, which the caller holds),
    and for a caller that wants the rows' losses apart. The (block, V)
    panel is the peak in both passes, as there."""
    h2, labels2, n_valid, _ = _padded_rows(h, labels, block)
    ce, hit = _streamed_ce_rows(h2, w, b, labels2, int(block), compute_dtype)
    return (ce[:n_valid].reshape(labels.shape),
            hit[:n_valid].reshape(labels.shape))


@scoped("lm_head")
def streamed_softmax_ce_head(h, w, b, labels, block: int,
                             compute_dtype=None, weights=None,
                             denominator=None):
    """Fused dense head + softmax-CE + accuracy, streamed over row
    blocks: the vocab-axis flash (the round-4 lesson applied to the
    loss). The unstreamed LM head materializes (B, S, V) f32 logits
    PLUS their gradient — at the vocab sizes that make an LM real
    (8k-50k) that dwarfs what the flash attention backward saved. Here
    the logits never exist beyond one (block, V) f32 panel: a
    ``lax.scan`` over row blocks computes each block's logits, its
    rows' logsumexp + label logit + argmax hit (forward), and a custom
    VJP recomputes the block's softmax from the saved per-row
    logsumexps in the backward — O(block * V) peak in BOTH passes,
    same recurrence discipline as ops/attention.py's flash backward.

    ``h``: (..., d) hidden states (any leading shape — (B, S) for the
    LM); ``labels``: integer ids of h's leading shape; ``w``/(``b``):
    the head projection. Values and gradients match
    ``softmax_cross_entropy(dense(h, w, b, compute_dtype), labels)``
    + ``accuracy`` to fp tolerance (pinned by tests/test_lm.py).
    ``b`` may be None (a head without a bias). ``weights`` (of the
    labels' shape, f32) gives each row its weight in the loss, and
    ``denominator`` what the weighted sum is divided by (the masked-
    diffusion loss: a masked position weighs 1/t, the others nought, the
    denominator is the row count whatever the mask); a row of weight
    nought is left out of the hit count too. With neither it is the mean
    over every row. The weights carry no gradient (they are data: the
    noise's). A caller whose weights depend on the parameters takes the
    rows' cross-entropies from ``streamed_softmax_ce_rows`` and weights
    them itself: a weight's cotangent is then its row's cross-entropy.
    Returns (mean loss f32, accuracy f32).
    """
    h2, labels2, n_valid, pad = _padded_rows(h, labels, block)
    if weights is None and denominator is None:
        return _streamed_ce(h2, w, b, labels2, int(block), compute_dtype,
                            n_valid)
    weights2 = None
    if weights is not None:
        weights2 = lax.stop_gradient(weights.reshape(n_valid))
        if pad:
            weights2 = jnp.concatenate(
                [weights2, jnp.zeros((pad,), weights2.dtype)])
    return _streamed_ce(h2, w, b, labels2, int(block), compute_dtype,
                        n_valid, weights2,
                        None if denominator is None else float(denominator))


def accuracy(logits, labels):
    """Minibatch argmax-equality accuracy (reference, MNISTDist.py:152-153).
    ``labels``: one-hot [B, C] or integer class ids [B]."""
    pred = jnp.argmax(logits, axis=-1)
    if labels.ndim == logits.ndim - 1:
        true = labels.astype(pred.dtype)
    else:
        true = jnp.argmax(labels, axis=-1)
    return jnp.mean((pred == true).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("num_classes",))
def one_hot(labels, num_classes: int = 10):
    return jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)


def batch_norm(x, scale, bias, running_mean, running_var, *,
               train: bool, momentum: float = 0.9, eps: float = 1e-5):
    """Batch normalization over NHWC (stats over N,H,W).

    Returns (y, (new_running_mean, new_running_var)). In train mode the
    batch statistics normalize and the running stats are EMA-updated; in
    eval mode the running stats normalize and pass through unchanged.
    Not in the reference (its CNN has no normalization); needed by the
    ResNet-20/CIFAR-10 config (BASELINE.md config 4).
    """
    reduce_axes = tuple(range(x.ndim - 1))
    if train:
        mean = jnp.mean(x, axis=reduce_axes)
        var = jnp.var(x, axis=reduce_axes)
        new_mean = momentum * running_mean + (1.0 - momentum) * mean
        new_var = momentum * running_var + (1.0 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_mean, new_var = running_mean, running_var
    inv = jax.lax.rsqrt(var + eps)
    y = (x - mean) * inv * scale + bias
    return y, (new_mean, new_var)
