"""MiniTransformer: an attention model family for the long-context path.

The reference framework has no attention model — this is the build's
extension exercising the sequence-parallel machinery
(ops/attention.ring_attention + parallel/sequence_parallel) on the same
datasets: an image is read as a SEQUENCE of rows (MNIST: 28 tokens of 28
pixels; CIFAR-10: 32 tokens of 96), embedded, run through pre-LN
transformer blocks, mean-pooled and classified. Pure pytree-of-arrays +
``apply`` like every model here — jits, shards, grads as a function.

Sequence parallelism: constructed with ``seq_axis="model"`` the model is
SPMD-aware — called inside shard_map with the token dimension sharded
over that mesh axis it slices its own positional embeddings by
``lax.axis_index``, runs RING attention over the axis, and mean-pools
with a ``psum``. Everything before the pool is per-token compute whose
parameter gradients arrive as P-scaled partials per shard while the
post-pool head's arrive replicated — one uniform pmean over the
sequence axis reduces both exactly (see
parallel/sequence_parallel.py for the derivation).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.models.cnn import truncated_normal_init
from distributed_tensorflow_tpu.models.registry import register_model
from distributed_tensorflow_tpu.ops import nn
from distributed_tensorflow_tpu.ops.attention import (
    blockwise_attention,
    multi_head_attention,
    ring_attention,
)
from distributed_tensorflow_tpu.utils.profiling import scope, scoped


def _layernorm(x, gain, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gain + bias).astype(x.dtype)


def _attn_half_params(w, d, h, dh, dtype):
    """The attention half's parameters — ONE constructor for the dense
    and MoE block forms (like _attn_half on the compute side), so the
    layouts cannot diverge."""
    return {
        "ln1_g": jnp.ones((d,), dtype),
        "ln1_b": jnp.zeros((d,), dtype),
        "qkv": w((d, 3, h, dh)),
        "proj": w((h * dh, d)),
        "ln2_g": jnp.ones((d,), dtype),
        "ln2_b": jnp.zeros((d,), dtype),
    }


def _block_params(w, d, h, dh, mlp_dim, dtype):
    """One pre-LN block's parameter dict (shared by both transformer
    families so their checkpoints stay structurally interchangeable)."""
    return {
        **_attn_half_params(w, d, h, dh, dtype),
        "mlp_in": {"w": w((d, mlp_dim)), "b": jnp.zeros((mlp_dim,), dtype)},
        "mlp_out": {"w": w((mlp_dim, d)), "b": jnp.zeros((d,), dtype)},
    }


def _transformer_block(h, blk, attn_fn, cd):
    """One pre-LN transformer block: LN -> attention -> residual ->
    LN -> MLP -> residual. ``attn_fn(q, k, v)`` supplies the attention
    flavor (dense / blockwise / ring, causal or not) so the block is the
    ONE implementation both model families and every parallelism mode
    run."""
    return _mlp_half(_attn_half(h, blk, attn_fn, cd), blk, cd)


@scoped("mlp")
def _mlp_half(h, blk, cd):
    """LN -> relu MLP -> residual — the dense block's second half,
    shared with serving/decode.py's incremental step so the two code
    paths cannot diverge (the KV-cache bitwise-parity contract rides on
    this being the one implementation)."""
    y = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
    y = jax.nn.relu(nn.dense(y, blk["mlp_in"]["w"], blk["mlp_in"]["b"],
                             compute_dtype=cd))
    return h + nn.dense(y, blk["mlp_out"]["w"], blk["mlp_out"]["b"],
                        compute_dtype=cd)


def _attn_half(h, blk, attn_fn, cd):
    """LN -> attention -> residual (shared by the dense-MLP and MoE
    block forms)."""
    return _attn_half_kv(h, blk, attn_fn, cd)[0]


@scoped("attn_proj")
def _attn_half_kv(h, blk, attn_fn, cd):
    """``_attn_half`` that also hands back this block's (k, v) — the
    serving prefill captures them into the decode cache, computed by the
    SAME projection the training forward runs (returns
    ``(h_out, k, v)``; k/v are (B, S, H, Dh) in the attention input
    dtype)."""
    y = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
    qkv = jnp.einsum("bsd,dthe->tbshe", y, blk["qkv"].astype(y.dtype))
    a = attn_fn(qkv[0], qkv[1], qkv[2])
    a = a.reshape(*a.shape[:2], -1)  # (B, S, H*Dh)
    return h + nn.dense(a, blk["proj"], compute_dtype=cd), qkv[1], qkv[2]


def _moe_block_params(w, d, h, dh, mlp_dim, num_experts, dtype):
    """MoE block: same attention half as _block_params; the MLP becomes
    E experts behind a top-1 router (ops/moe.py)."""
    return {
        **_attn_half_params(w, d, h, dh, dtype),
        "moe": {
            "router": w((d, num_experts)),
            "w1": w((num_experts, d, mlp_dim)),
            "b1": jnp.zeros((num_experts, mlp_dim), dtype),
            "w2": w((num_experts, mlp_dim, d)),
            "b2": jnp.zeros((num_experts, d), dtype),
        },
    }


def _transformer_block_moe(h, blk, attn_fn, cd, capacity_factor,
                           moe_axis):
    """MoE block form: returns (h, load_balance_loss)."""
    from distributed_tensorflow_tpu.ops.moe import switch_moe

    h = _attn_half(h, blk, attn_fn, cd)
    with scope("mlp"):
        y = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
        y, aux = switch_moe(y, blk["moe"], capacity_factor=capacity_factor,
                            axis_name=moe_axis, compute_dtype=cd)
        return h + y, aux["lb_loss"]


@register_model("transformer")
class MiniTransformer:
    """Row-sequence transformer classifier.

    ``seq_axis=None`` (default): dense attention, runs anywhere a
    DeepCNN runs. ``seq_axis="model"``: ring attention + sharded
    positional slices + psum pooling — must then be applied inside
    shard_map with tokens sharded over that axis (the sequence-parallel
    step builder does this).
    """

    stateful = False

    def __init__(
        self,
        image_size: int = 28,
        channels: int = 1,
        num_classes: int = 10,
        d_model: int = 128,
        num_heads: int = 4,
        num_blocks: int = 2,
        mlp_ratio: int = 4,
        compute_dtype: Any = None,
        seq_axis: str | None = None,
        remat: bool = False,
        **_unused,  # registry passes hidden_units etc. to every model
    ):
        if d_model % num_heads:
            raise ValueError(f"d_model={d_model} % num_heads={num_heads} != 0")
        self.remat = remat
        self.image_size = image_size
        self.channels = channels
        self.num_classes = num_classes
        self.d_model = d_model
        self.num_heads = num_heads
        self.num_blocks = num_blocks
        self.mlp_dim = mlp_ratio * d_model
        self.compute_dtype = compute_dtype
        self.seq_axis = seq_axis
        self.seq_len = image_size           # one token per image row
        self.token_dim = image_size * channels

    def init(self, key, dtype=jnp.float32):
        d, h = self.d_model, self.num_heads
        dh = d // h
        keys = iter(jax.random.split(key, 4 + 7 * self.num_blocks))

        def w(shape, stddev=0.02):
            return truncated_normal_init(next(keys), shape, stddev, dtype)

        params = {
            "embed": {"w": w((self.token_dim, d)), "b": jnp.zeros((d,), dtype)},
            "pos": w((self.seq_len, d)),
            "blocks": [],
            "ln_f": {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
            "head": {
                "w": w((d, self.num_classes)),
                "b": jnp.zeros((self.num_classes,), dtype),
            },
        }
        for _ in range(self.num_blocks):
            params["blocks"].append(
                _block_params(w, d, h, dh, self.mlp_dim, dtype))
        return params

    # ---- forward -------------------------------------------------------
    def apply(self, params, x, *, keep_prob=1.0, rng=None, train: bool = False):
        cd = self.compute_dtype
        x = nn.normalize_if_u8(x, cd)
        # (B, 784[*C]) or (B, S, token): accept both layouts. In SP mode
        # x is the LOCAL token block (B, S/P, token) handed in by the
        # shard_map step.
        if x.ndim == 2:
            x = x.reshape(-1, self.seq_len, self.token_dim)
        if cd is not None:
            x = x.astype(cd)

        d = self.d_model
        h = nn.dense(x, params["embed"]["w"], params["embed"]["b"],
                     compute_dtype=cd)
        pos = params["pos"]
        if self.seq_axis is not None:
            # my shard's slice of the positional table
            s_local = x.shape[1]
            start = lax.axis_index(self.seq_axis) * s_local
            pos = lax.dynamic_slice_in_dim(pos, start, s_local, axis=0)
        h = h + pos.astype(h.dtype)

        if self.seq_axis is not None:
            attn = lambda q, k, v: ring_attention(q, k, v, self.seq_axis)
        else:
            attn = multi_head_attention
        blk_fn = _transformer_block
        if self.remat:
            blk_fn = jax.checkpoint(_transformer_block,
                                    static_argnums=(2, 3))
        for blk in params["blocks"]:
            h = blk_fn(h, blk, attn, cd)

        h = _layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"])
        # mean-pool over the FULL sequence: local sum, psum across the
        # sequence shards, divide by the global length
        pooled = h.sum(axis=1)
        if self.seq_axis is not None:
            pooled = lax.psum(pooled, self.seq_axis)
        pooled = pooled / jnp.asarray(self.seq_len, pooled.dtype)
        pooled = nn.dropout(pooled, keep_prob, rng, deterministic=not train)
        logits = nn.dense(pooled, params["head"]["w"], params["head"]["b"],
                          compute_dtype=cd)
        return logits.astype(jnp.float32)

    def num_params(self, params=None):
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.key(0)))
        return sum(int(jnp.size(p)) for p in jax.tree.leaves(params))


@register_model("lm")
class TransformerLM:
    """Causal (next-token) transformer language model — the long-context
    flagship. The reference framework is images-only (MNISTDist.py:68);
    this is the build's beyond-parity extension, and the end-to-end
    consumer of the causal attention forms.

    Input: integer token ids (B, S); output: per-token logits (B, S, V).
    The per-token cross-entropy and accuracy come from the SAME loss ops
    the classifiers use — ``ops.nn.softmax_cross_entropy`` and
    ``accuracy`` already handle labels.ndim == logits.ndim - 1, so the
    whole train-state/step/loop stack runs unchanged on (B, S) integer
    targets.

    Attention flavors (all causal):
    - ``seq_axis=None, attn_block=None``: dense triangle — fine to a few
      thousand tokens, O(S^2) memory.
    - ``attn_block=N``: single-device blockwise streaming softmax —
      O(S*N) peak memory, the one-chip long-context path.
    - ``seq_axis="model"``: RING attention over the mesh axis; tokens
      sharded, k/v blocks rotating on ICI — the multi-chip long-context
      path (must run inside the SP shard_map step).
    ``remat=True`` wraps each block in ``jax.checkpoint`` — activation
    memory drops from O(num_blocks * S * d) to O(S * d) + one block's
    recompute, the standard trade for long sequences.

    ``ce_block=N`` streams the LOSS head the same way ``attn_block``
    streams attention: the train/eval steps route through
    ``loss_with_metrics`` (ops.nn.streamed_softmax_ce_head), which
    never materializes the (B, S, V) f32 logits — O(N * V) peak in
    both passes. The other memory wall of large-vocab long context
    (the flash VJPs removed the O(S^2) one). ``apply`` still exists
    and still returns full logits (generation/inspection); training
    simply never calls it when ``ce_block`` is set.
    """

    stateful = False

    def __init__(
        self,
        vocab_size: int = 64,
        seq_len: int = 256,
        d_model: int = 128,
        num_heads: int = 4,
        num_blocks: int = 2,
        mlp_ratio: int = 4,
        compute_dtype: Any = None,
        seq_axis: str | None = None,
        attn_block: int | None = None,
        remat: bool = False,
        ce_block: int | None = None,
        moe_experts: int = 0,
        moe_capacity: float = 1.25,
        moe_aux: float = 0.01,
        moe_axis: str | None = None,
        **_unused,
    ):
        if d_model % num_heads:
            raise ValueError(f"d_model={d_model} % num_heads={num_heads} != 0")
        if seq_axis is not None and attn_block is not None:
            raise ValueError("seq_axis (ring) and attn_block (local "
                             "blockwise) are mutually exclusive attention "
                             "flavors")
        if moe_axis is not None and not moe_experts:
            raise ValueError("moe_axis (expert parallelism) needs "
                             "moe_experts > 0")
        if moe_axis is not None and seq_axis is not None:
            raise ValueError("moe_axis and seq_axis both claim the mesh's "
                             "model axis — pick one")
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.d_model = d_model
        self.num_heads = num_heads
        self.num_blocks = num_blocks
        self.mlp_dim = mlp_ratio * d_model
        self.compute_dtype = compute_dtype
        self.seq_axis = seq_axis
        self.attn_block = attn_block
        self.remat = remat
        self.ce_block = ce_block
        self.moe_experts = int(moe_experts)
        self.moe_capacity = float(moe_capacity)
        self.moe_aux = float(moe_aux)
        self.moe_axis = moe_axis

    def init(self, key, dtype=jnp.float32):
        d, h = self.d_model, self.num_heads
        dh = d // h
        keys = iter(jax.random.split(key, 4 + 8 * self.num_blocks))

        def w(shape, stddev=0.02):
            return truncated_normal_init(next(keys), shape, stddev, dtype)

        params = {
            "tok": w((self.vocab_size, d)),
            "pos": w((self.seq_len, d)),
            "blocks": [],
            "ln_f": {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
            "head": {
                "w": w((d, self.vocab_size)),
                "b": jnp.zeros((self.vocab_size,), dtype),
            },
        }
        for _ in range(self.num_blocks):
            if self.moe_experts:
                params["blocks"].append(_moe_block_params(
                    w, d, h, dh, self.mlp_dim, self.moe_experts, dtype))
            else:
                params["blocks"].append(
                    _block_params(w, d, h, dh, self.mlp_dim, dtype))
        return params

    def apply_hidden(self, params, x, *, keep_prob=1.0, rng=None,
                     train: bool = False):
        """Everything up to (but not including) the vocab head: final
        hidden states (B, S, d) after ln_f + dropout. The streamed-CE
        path consumes this directly so the (B, S, V) logits never
        materialize; ``apply`` adds the head on top."""
        return self._hidden_and_aux(params, x, keep_prob=keep_prob,
                                    rng=rng, train=train)[0]

    def attention_fn(self):
        """``(q, k, v) -> out``, all (B, S, H, Dh): this model's causal
        attention flavor (ring / blockwise / dense). The one place the
        choice is made: the pipeline stages call it, and the TP step
        wraps it per head shard (parallel/tensor_parallel.py)."""
        if self.seq_axis is not None:
            return lambda q, k, v: ring_attention(
                q, k, v, self.seq_axis, causal=True)
        if self.attn_block is not None:
            return lambda q, k, v: blockwise_attention(
                q, k, v, self.attn_block, causal=True)
        return lambda q, k, v: multi_head_attention(q, k, v, causal=True)

    def _hidden_and_aux(self, params, x, *, keep_prob=1.0, rng=None,
                        train: bool = False):
        """(hidden, moe load-balance loss total) — the aux term is 0.0
        for dense-MLP models; loss_with_metrics adds it to the training
        loss scaled by ``moe_aux``."""
        cd = self.compute_dtype
        # x: integer ids (B, S) — or the LOCAL token block (B, S/P) when
        # called inside the SP shard_map step
        with scope("embed"):
            h = jnp.take(params["tok"], x, axis=0)
            pos = params["pos"]
            if self.seq_axis is not None:
                s_local = x.shape[1]
                start = lax.axis_index(self.seq_axis) * s_local
                pos = lax.dynamic_slice_in_dim(pos, start, s_local, axis=0)
            h = h + pos.astype(h.dtype)
            if cd is not None:
                h = h.astype(cd)

        attn = self.attention_fn()

        lb_total = jnp.float32(0.0)
        if self.moe_experts:
            moe_fn = _transformer_block_moe
            if self.remat:
                moe_fn = jax.checkpoint(_transformer_block_moe,
                                        static_argnums=(2, 3, 4, 5))
            for blk in params["blocks"]:
                h, lb = moe_fn(h, blk, attn, cd, self.moe_capacity,
                               self.moe_axis)
                lb_total = lb_total + lb
        else:
            blk_fn = _transformer_block
            if self.remat:
                blk_fn = jax.checkpoint(_transformer_block,
                                        static_argnums=(2, 3))
            for blk in params["blocks"]:
                h = blk_fn(h, blk, attn, cd)

        with scope("lm_head"):
            h = _layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"])
            if rng is not None and self.seq_axis is not None:
                # per-token dropout: decorrelate the mask across sequence
                # shards (each shard holds DIFFERENT tokens — unlike the
                # classifier's post-pool dropout, which must be identical)
                rng = jax.random.fold_in(rng,
                                         lax.axis_index(self.seq_axis))
            return (nn.dropout(h, keep_prob, rng, deterministic=not train),
                    lb_total)

    def apply(self, params, x, *, keep_prob=1.0, rng=None, train: bool = False):
        h = self.apply_hidden(params, x, keep_prob=keep_prob, rng=rng,
                              train=train)
        with scope("lm_head"):
            logits = nn.dense(h, params["head"]["w"], params["head"]["b"],
                              compute_dtype=self.compute_dtype)
            return logits.astype(jnp.float32)

    @property
    def wants_loss_hook(self) -> bool:
        """True when training/eval must route through
        ``loss_with_metrics`` (training.loss_and_metrics checks this):
        the streamed CE head and/or the MoE auxiliary loss."""
        return bool(self.ce_block or self.moe_experts)

    def loss_with_metrics(self, params, x, y, *, keep_prob=1.0, rng=None,
                          train: bool = False):
        """(loss, metrics) — the train/eval hook. With ``ce_block`` the
        CE is the streamed head (values/grads match apply +
        softmax_cross_entropy to fp tolerance, tests/test_lm.py); with
        ``moe_experts`` the TRAINING loss adds ``moe_aux`` times the
        Switch load-balance term (metrics report it either way; eval
        loss stays the plain CE)."""
        h, lb = self._hidden_and_aux(params, x, keep_prob=keep_prob,
                                     rng=rng, train=train)
        if self.ce_block:
            ce, acc = nn.streamed_softmax_ce_head(
                h, params["head"]["w"], params["head"]["b"], y,
                block=self.ce_block, compute_dtype=self.compute_dtype)
        else:
            with scope("lm_head"):
                logits = nn.dense(h, params["head"]["w"],
                                  params["head"]["b"],
                                  compute_dtype=self.compute_dtype)
                logits = logits.astype(jnp.float32)
                ce = nn.softmax_cross_entropy(logits, y)
                acc = nn.accuracy(logits, y)
        metrics = {"loss": ce, "accuracy": acc}
        loss = ce
        if self.moe_experts:
            metrics["moe_lb"] = lb
            if train:
                loss = ce + self.moe_aux * lb
        return loss, metrics

    def num_params(self, params=None):
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.key(0)))
        return sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
