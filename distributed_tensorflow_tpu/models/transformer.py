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

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.models.cnn import truncated_normal_init
from distributed_tensorflow_tpu.models.registry import register_model
from distributed_tensorflow_tpu.ops import nn
from distributed_tensorflow_tpu.ops.attention import (
    REMAT_KEPT,
    Mask,
    blockwise_attention,
    multi_head_attention,
    ring_attention,
)
from distributed_tensorflow_tpu.utils import telemetry
from distributed_tensorflow_tpu.utils.profiling import scope, scoped


def _layernorm(x, gain, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gain + bias).astype(x.dtype)


class BlockArch(NamedTuple):
    """The choices of ONE LAYER beyond the first form's (LayerNorm, learned
    positions added at the embedding, the model's head count under the
    model's mask, a ReLU MLP with biases): read by the two halves, the
    block forms and their parameter builders, so that every caller of
    those (training, ``serving/decode.py``) takes the same choices from one
    place. ``None`` stands for the first form. A model's layers are alike
    unless it is given a plan (``TransformerLM(layer_plan=...)``): then
    each layer has its own value, and ``TransformerLM.plan`` is the tuple
    of them that ``init`` and the forward pass walk."""

    norm: str = "layernorm"     # or "rmsnorm" (no bias leaf)
    norm_eps: float = 1e-5
    rope_theta: float = 0.0     # > 0: rotary positions on q and k
    kv_heads: int = 0           # 0: as many as query heads
    qk_norm: bool = False       # RMSNorm over the head width on q and k
    gated: bool = False         # feed-forward silu(gate) * up
    biases: bool = True         # on the feed-forward
    # a layer's own, where a plan gives them
    heads: int = 0              # query heads; 0: the model's
    window: int = 0             # > 0: attention over the last `window` keys
    rope_fraction: float = 1.0  # share of the head width that is rotated
    rope_yarn: tuple = ()       # (factor, original positions, beta_fast,
    #                             beta_slow, attention factor), or none
    attn_gate: bool = False     # sigmoid gate a head and row on attention
    ffn: str = "dense"          # or "switch" / "routed" (ops/moe.py)
    shared_dim: int = 0         # routed: a gated expert every row takes
    sandwich: bool = False      # a second norm on each half's OUTPUT
    attn_gate_elementwise: bool = False  # a sigmoid gate an element of a
    #                                      head, from q's projection's second half
    shared_gate: bool = False   # routed: a sigmoid gate a row on the shared
    #                             expert's output
    linear: tuple = ()          # a linear layer's (key heads, key width,
    #                             value width, conv taps); ``heads`` are its
    #                             value heads (``_linear_half``)


LAYER_ATTENTIONS = ("full", "window", "linear")
# RMSNorm whose leaf is w in x / rms(x) * (1 + w), w drawn as 0
ZERO_CENTRED = "rmsnorm_zero_centred"
NORMS = ("layernorm", "rmsnorm", ZERO_CENTRED)
LAYER_FFNS = ("dense", "routed")


def parse_layer_plan(text: str) -> list[tuple[str, int, str]]:
    """``"full:48:dense,window:64:routed"`` -> [(attention, query heads,
    feed-forward)], one a layer; anything else is a ValueError that says
    what an entry is."""
    out = []
    for entry in text.split(","):
        parts = entry.strip().split(":")
        if len(parts) != 3 or parts[0] not in LAYER_ATTENTIONS \
                or parts[2] not in LAYER_FFNS or not parts[1].isdigit() \
                or int(parts[1]) < 1:
            raise ValueError(
                f"layer_plan entry {entry!r} is not <attention>:<query "
                f"heads>:<feed-forward> with attention one of "
                f"{LAYER_ATTENTIONS}, heads >= 1 and feed-forward one of "
                f"{LAYER_FFNS}")
        out.append((parts[0], int(parts[1]), parts[2]))
    return out


def parse_rope_yarn(value) -> tuple:
    """``"64,4096,64,1,1.4158883"`` (or the five numbers) -> (factor,
    original positions, beta_fast, beta_slow, attention factor); empty:
    none."""
    if not value:
        return ()
    parts = value.split(",") if isinstance(value, str) else list(value)
    try:
        yarn = tuple(float(x) for x in parts)
    except ValueError:
        yarn = ()
    if len(yarn) != 5 or yarn[0] < 1 or yarn[1] < 1 \
            or not yarn[2] > yarn[3] > 0 or yarn[4] <= 0:
        raise ValueError(
            f"rope_yarn {value!r} is not factor,original_positions,"
            f"beta_fast,beta_slow,attention_factor (factor >= 1, beta_fast "
            f"> beta_slow > 0)")
    return yarn


def _rmsnorm(x, gain, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain).astype(x.dtype)


def _gain(g, arch):
    """An RMSNorm's gain from its leaf: the leaf, or one plus the leaf where
    the norm is zero-centred."""
    return 1.0 + g if arch.norm == ZERO_CENTRED else g


def _norm(x, params, prefix, arch):
    """The normalisation whose leaves are ``params[prefix + "g"]`` (and
    ``"b"``), by ``arch``."""
    if arch is None:
        return _layernorm(x, params[prefix + "g"], params[prefix + "b"])
    if arch.norm == "layernorm":
        return _layernorm(x, params[prefix + "g"], params[prefix + "b"],
                          arch.norm_eps)
    return _rmsnorm(x, _gain(params[prefix + "g"], arch), arch.norm_eps)


def _gain_leaf(shape, dtype, arch):
    """A norm's gain as it is drawn: ones, or zeros where the norm is
    zero-centred."""
    if arch is not None and arch.norm == ZERO_CENTRED:
        return jnp.zeros(shape, dtype)
    return jnp.ones(shape, dtype)


def _norm_params(prefix, d, dtype, arch):
    out = {prefix + "g": _gain_leaf((d,), dtype, arch)}
    if arch is None or arch.norm == "layernorm":
        out[prefix + "b"] = jnp.zeros((d,), dtype)
    return out


def _residual(h, out, blk, prefix, arch):
    """``h`` plus a half's output; with sandwich norms the output is
    normalised first (leaves ``prefix + "post_g"`` ...), so that what a
    layer adds to the stream has a scale of its own however often the
    layer is run."""
    if arch is not None and arch.sandwich:
        out = _norm(out, blk, prefix + "post_", arch)
    return h + out


def yarn_frequencies(theta, dr: int, yarn):
    """(the Dr / 2 inverse frequencies, the factor on cos and sin) of YaRN
    (arXiv:2309.00071, as ``rope_type: yarn`` computes it): frequency i of
    base ``theta`` over ``dr`` rotated dimensions is kept where it turns
    more than ``beta_fast`` times over the ``original`` positions, divided
    by ``factor`` where it turns fewer than ``beta_slow`` times, and
    blended linearly between the two dimensions where that happens."""
    factor, original, beta_fast, beta_slow, attention_factor = yarn
    base = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)

    def turns_at(turns):  # the dimension that turns this often
        return (dr * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns_at(beta_fast)), 0)
    hi = min(math.ceil(turns_at(beta_slow)), dr - 1)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp), attention_factor


def rope(x, pos, theta, fraction: float = 1.0, yarn=()):
    """Rotary positions in the rotate-half form: x (B, S, H, Dh), pos (S,)
    position ids. f32 inside, x's dtype out. ``fraction`` < 1 rotates the
    first ``fraction * Dh`` of the head width and passes the rest through;
    ``yarn`` blends the frequencies (``yarn_frequencies``) and scales cos
    and sin."""
    dh = x.shape[-1]
    dr = dh if fraction == 1.0 else int(round(dh * fraction))
    if yarn:
        inv, factor = yarn_frequencies(theta, dr, yarn)
    else:
        inv = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # (S, Dr/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    if yarn:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    xr = xf if dr == dh else xf[..., :dr]
    x1, x2 = xr[..., : dr // 2], xr[..., dr // 2:]
    out = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    if dr != dh:
        out = jnp.concatenate([out, xf[..., dr:]], -1)
    return out.astype(x.dtype)


def _attn_half_params(w, d, h, dh, dtype, arch=None):
    """The attention half's parameters — ONE constructor for the dense
    and MoE block forms (like _attn_half on the compute side), so the
    layouts cannot diverge. Fewer key/value heads than query heads split
    ``qkv`` into ``q`` and ``kv``; ``qk_norm`` adds a gain a head width;
    ``attn_gate`` a (d, H) matrix, one gate a head;
    ``attn_gate_elementwise`` doubles ``q``'s width a head, [q ; gate]."""
    kv = h if arch is None or not arch.kv_heads else arch.kv_heads
    elementwise = arch is not None and arch.attn_gate_elementwise
    out = _norm_params("ln1_", d, dtype, arch)
    if kv == h and not elementwise:
        out["qkv"] = w((d, 3, h, dh))
    else:
        out["q"] = w((d, h, 2 * dh if elementwise else dh))
        out["kv"] = w((d, 2, kv, dh))
    if arch is not None and arch.qk_norm:
        out["q_norm_g"] = _gain_leaf((dh,), dtype, arch)
        out["k_norm_g"] = _gain_leaf((dh,), dtype, arch)
    out["proj"] = w((h * dh, d))
    if arch is not None and arch.attn_gate:
        out["gate"] = w((d, h))
    if arch is not None and arch.sandwich:
        out.update(_norm_params("ln1_post_", d, dtype, arch))
    out.update(_norm_params("ln2_", d, dtype, arch))
    return out


def _mlp_params(w, d, mlp_dim, dtype, arch=None):
    gated = arch is not None and arch.gated
    shapes = {"mlp_in": (d, (2 if gated else 1) * mlp_dim),
              "mlp_out": (mlp_dim, d)}
    out = {}
    for name, shape in shapes.items():
        out[name] = {"w": w(shape)}
        if arch is None or arch.biases:
            out[name]["b"] = jnp.zeros((shape[1],), dtype)
    if arch is not None and arch.sandwich:
        out.update(_norm_params("ln2_post_", d, dtype, arch))
    return out


def _block_params(w, d, h, dh, mlp_dim, dtype, arch=None):
    """One pre-LN block's parameter dict (shared by both transformer
    families so their checkpoints stay structurally interchangeable)."""
    return {
        **_mixer_params(w, d, h, dh, dtype, arch),
        **_mlp_params(w, d, mlp_dim, dtype, arch),
    }


def _transformer_block(h, blk, attn_fn, cd, arch=None, pos=None):
    """One pre-LN transformer block: LN -> attention -> residual ->
    LN -> MLP -> residual. ``attn_fn(q, k, v)`` supplies the attention
    flavor (dense / blockwise / ring, causal or not) so the block is the
    ONE implementation both model families and every parallelism mode
    run."""
    return _mlp_half(_attn_half(h, blk, attn_fn, cd, arch, pos), blk, cd,
                     arch)


@scoped("mlp")
def _mlp_half(h, blk, cd, arch=None):
    """LN -> relu MLP -> residual — the dense block's second half,
    shared with serving/decode.py's incremental step so the two code
    paths cannot diverge (the KV-cache bitwise-parity contract rides on
    this being the one implementation). ``arch.gated``: silu(gate) * up
    from one (d, 2 m) matrix, the gate's columns first."""
    y = _norm(h, blk, "ln2_", arch)
    y = nn.dense(y, blk["mlp_in"]["w"], blk["mlp_in"].get("b"),
                 compute_dtype=cd)
    if arch is not None and arch.gated:
        m = y.shape[-1] // 2
        y = jax.nn.silu(y[..., :m]) * y[..., m:]
    else:
        y = jax.nn.relu(y)
    return _residual(h, nn.dense(y, blk["mlp_out"]["w"],
                                 blk["mlp_out"].get("b"), compute_dtype=cd),
                     blk, "ln2_", arch)


def _attn_half(h, blk, attn_fn, cd, arch=None, pos=None):
    """LN -> attention -> residual (shared by the dense-MLP and MoE
    block forms); of a linear layer, LN -> the gated delta rule ->
    residual."""
    if arch is not None and arch.linear:
        return _linear_half(h, blk, cd, arch)
    return _attn_half_kv(h, blk, attn_fn, cd, arch, pos)[0]


@scoped("attn_proj")
def _attn_half_kv(h, blk, attn_fn, cd, arch=None, pos=None):
    """``_attn_half`` that also hands back this block's (k, v) — the
    serving prefill captures them into the decode cache, computed by the
    SAME projection the training forward runs (returns
    ``(h_out, k, v)``; k/v are (B, S, Hkv, Dh) in the attention input
    dtype, after the q/k norm and the rotary positions where ``arch``
    has them; ``pos``: the rows' position ids, 0..S-1 if None)."""
    y = _norm(h, blk, "ln1_", arch)
    if "qkv" in blk:
        qkv = jnp.einsum("bsd,dthe->tbshe", y, blk["qkv"].astype(y.dtype))
        q, k, v = qkv[0], qkv[1], qkv[2]
    else:
        q = jnp.einsum("bsd,dhe->bshe", y, blk["q"].astype(y.dtype))
        kv = jnp.einsum("bsd,dthe->tbshe", y, blk["kv"].astype(y.dtype))
        k, v = kv[0], kv[1]
    if arch is not None and arch.attn_gate_elementwise:
        q, gate = jnp.split(q, 2, axis=-1)
    if arch is not None and arch.qk_norm:
        q = _rmsnorm(q, _gain(blk["q_norm_g"], arch), arch.norm_eps)
        k = _rmsnorm(k, _gain(blk["k_norm_g"], arch), arch.norm_eps)
    if arch is not None and arch.rope_theta:
        if pos is None:
            pos = jnp.arange(q.shape[1])
        form = (arch.rope_theta, arch.rope_fraction, arch.rope_yarn)
        q, k = rope(q, pos, *form), rope(k, pos, *form)
    a = attn_fn(q, k, v)
    if arch is not None and arch.attn_gate:
        # one gate a head and row, from the normalised input
        gate = jnp.einsum("bsd,dh->bsh", y, blk["gate"].astype(y.dtype))
        a = a * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            a.dtype)[..., None]
    elif arch is not None and arch.attn_gate_elementwise:
        a = a * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(a.dtype)
    a = a.reshape(*a.shape[:2], -1)  # (B, S, H*Dh)
    return _residual(h, nn.dense(a, blk["proj"], compute_dtype=cd), blk,
                     "ln1_", arch), k, v


def _linear_half_params(w, d, dtype, arch):
    """The linear layer's half (``_linear_half``): ``qkvz`` (d, the q, k, v
    and z channels, in that order), ``ba`` (d, 2 value heads: b, then a),
    the conv's taps (kernel, the q, k and v channels) ~ U(-0.5, 0.5),
    ``a_log`` = log A with A ~ U(1e-4, 16) and ``dt_bias`` ones a value
    head, the gated norm's gain (ones), the output projection ``proj``."""
    nk, dk, dv, taps = arch.linear
    nv = arch.heads
    out = _norm_params("ln1_", d, dtype, arch)
    out["qkvz"] = w((d, 2 * nk * dk + 2 * nv * dv))
    out["ba"] = w((d, 2 * nv))
    out["conv"] = w((taps, 2 * nk * dk + nv * dv), uniform=(-0.5, 0.5))
    out["a_log"] = jnp.log(w((nv,), uniform=(1e-4, 16.0)))
    out["dt_bias"] = jnp.ones((nv,), dtype)
    out["o_norm_g"] = jnp.ones((dv,), dtype)
    out["proj"] = w((nv * dv, d))
    out.update(_norm_params("ln2_", d, dtype, arch))
    return out


def _mixer_params(w, d, h, dh, dtype, arch):
    """The first half of a layer: attention's, or the linear layer's."""
    if arch is not None and arch.linear:
        return _linear_half_params(w, d, dtype, arch)
    return _attn_half_params(w, d, h, dh, dtype, arch)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@scoped("attn_proj")
def _linear_half(h, blk, cd, arch):
    """LN -> the linear layer (Gated DeltaNet) -> residual: the projections
    ``qkvz`` and ``ba`` and the output projection under ``attn_proj``; all
    between them under ``linear_attention``: the causal depthwise conv of
    the q, k and v channels and its SiLU, beta = sigmoid(b) and the log
    decay g = -exp(a_log) softplus(a + dt_bias) a value head and token, the
    l2 norms of q and k (q over sqrt(dk)), each key head repeated to the
    value heads that read it (value head n reads key head n // (value heads
    / key heads)), the gated delta rule (``ops/linear_attention.py``), and
    RMSNorm(o) * gain * silu(z) a value head, all in float32."""
    from distributed_tensorflow_tpu.ops.linear_attention import (
        gated_delta_rule,
    )

    y = _norm(h, blk, "ln1_", arch)
    qkvz = nn.dense(y, blk["qkvz"], compute_dtype=cd)
    ba = nn.dense(y, blk["ba"], compute_dtype=cd)
    nk, dk, dv, _ = arch.linear
    nv = arch.heads
    b, s, _ = qkvz.shape
    with scope("linear_attention"):
        f32 = jnp.float32
        mixed = 2 * nk * dk + nv * dv
        taps = blk["conv"]
        # depthwise over the sequence, causal: c_t = sum_i w_i x_{t-K+1+i}
        x = jax.nn.silu(lax.conv_general_dilated(
            qkvz[..., :mixed].astype(f32), taps.astype(f32)[:, None, :],
            window_strides=(1,), padding=((taps.shape[0] - 1, 0),),
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=mixed, precision=lax.Precision.HIGHEST))
        q = _l2norm(x[..., :nk * dk].reshape(b, s, nk, dk)) * dk ** -0.5
        k = _l2norm(x[..., nk * dk:2 * nk * dk].reshape(b, s, nk, dk))
        q, k = (jnp.repeat(t, nv // nk, axis=2) for t in (q, k))
        beta = jax.nn.sigmoid(ba[..., :nv].astype(f32))
        g = -jnp.exp(blk["a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., nv:].astype(f32) + blk["dt_bias"].astype(f32))
        o = gated_delta_rule(q, k, x[..., 2 * nk * dk:].reshape(b, s, nv, dv),
                             g, beta)
        z = qkvz[..., mixed:].astype(f32).reshape(b, s, nv, dv)
        o = _rmsnorm(o, blk["o_norm_g"].astype(f32), arch.norm_eps) \
            * jax.nn.silu(z)
        o = o.reshape(b, s, nv * dv).astype(y.dtype)
    return _residual(h, nn.dense(o, blk["proj"], compute_dtype=cd), blk,
                     "ln1_", arch)


def _routed_block_params(w, d, h, dh, ffn_dim, num_experts, held, dtype,
                         arch):
    """Routed block: the first half of ``_block_params``; the
    feed-forward is ``held`` gated experts of width ``ffn_dim`` behind a
    router over ``num_experts`` (``ops/moe.py:routed_experts``), and where
    ``arch.shared_dim`` one more gated expert, whole here, that every row
    takes (``arch.shared_gate``: behind a sigmoid gate, a (d, 1) matrix)."""
    out = {
        **_mixer_params(w, d, h, dh, dtype, arch),
        "moe": {
            "router": w((d, num_experts)),
            "w1": w((held, d, 2 * ffn_dim)),
            "w2": w((held, ffn_dim, d)),
        },
    }
    if arch.shared_dim:
        out["shared"] = {"w1": w((d, 2 * arch.shared_dim)),
                         "w2": w((arch.shared_dim, d))}
        if arch.shared_gate:
            out["shared"]["gate"] = w((d, 1))
    return out


class Routing(NamedTuple):
    """The routed layers' choices, the model's (``ops/moe.py``)."""

    top_k: int
    first_expert: int = 0
    capacity: float = 1.25
    scoring: str = "softmax"
    scale: float = 1.0


def _shared_expert(y, shared, cd):
    """The expert every row takes: silu(gate) * up from one (d, 2 f)
    matrix, the gate's columns first (as the routed experts'); where the
    expert has a ``gate`` leaf (d, 1), times sigmoid(y gate) a row."""
    up = nn.dense(y, shared["w1"], compute_dtype=cd)
    f = up.shape[-1] // 2
    out = nn.dense(jax.nn.silu(up[..., :f]) * up[..., f:], shared["w2"],
                   compute_dtype=cd)
    if "gate" in shared:
        gate = nn.dense(y, shared["gate"], compute_dtype=cd)
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
    return out


def _transformer_block_routed(h, blk, attn_fn, cd, arch, pos, routing):
    """Routed block form: returns (h, the layer's routing counters)."""
    from distributed_tensorflow_tpu.ops.moe import routed_experts

    h = _attn_half(h, blk, attn_fn, cd, arch, pos)
    with scope("moe_router"):
        y = _norm(h, blk, "ln2_", arch)
    out, aux = routed_experts(y, blk["moe"], top_k=routing.top_k,
                              first_expert=routing.first_expert,
                              capacity_factor=routing.capacity,
                              compute_dtype=cd, scoring=routing.scoring,
                              scale=routing.scale)
    if "shared" in blk:
        with scope("moe_shared"):
            out = out + _shared_expert(y, blk["shared"], cd)
    with scope("moe_router"):
        return h + out, aux


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


def _planned_block(h, blk, attn_fn, cd, arch, pos, moe):
    """One layer as its plan entry (``arch``, a ``BlockArch``) says:
    (h, what its feed-forward reports: the routed layer's counters, the
    Switch layer's load-balance loss, None). ``moe``: the ``Routing`` of a
    routed layer, (capacity, axis) of a Switch layer."""
    if arch.ffn == "routed":
        return _transformer_block_routed(h, blk, attn_fn, cd, arch, pos, moe)
    if arch.ffn == "switch":
        return _transformer_block_moe(h, blk, attn_fn, cd, *moe)
    return _transformer_block(h, blk, attn_fn, cd, arch, pos), None


def exit_log_distribution(gate_logits):
    """(T, ...) f32 logits of the exit gate after each of T passes -> (T,
    ...) ``log p(t)`` of leaving after pass t: ``p(t) = s(g_t) prod_{j<t}
    (1 - s(g_j))`` for t < T and the remainder ``prod_{j<T} (1 - s(g_j))``
    for t = T (the last pass's own gate is not read), so that p sums to 1.
    From log-sigmoids: a saturated gate gives a large negative number and
    no infinity."""
    g = gate_logits.astype(jnp.float32)
    none = jnp.zeros_like(g[:1])
    stayed = jnp.concatenate(
        [none, jnp.cumsum(jax.nn.log_sigmoid(-g[:-1]), axis=0)])
    return stayed + jnp.concatenate([jax.nn.log_sigmoid(g[:-1]), none])


def _remat(fn, static_argnums, shows=REMAT_KEPT, **says):
    """``fn`` (a block) under ``--remat``: ``jax.checkpoint`` whose backward
    pass recomputes the block from its input, all but the values that
    ``ops/attention.py`` names (``REMAT_KEPT``: a blockwise attention's
    ``out`` and logsumexp). Those are kept, because a whole kernel call (or
    scan) stands behind them and they are small beside it; q, k and v come
    back from the projections, matmuls that run near the peak. A block
    whose attention is dense or the ring names nothing and keeps nothing.
    The one wrapper of every site that rematerializes a block.

    The first block that shows the policy every name it ``shows`` records
    the ``remat_saved`` instant: the names and the bytes a block they cost
    (and ``says``: of a plan, which kind of layer this is). A block that
    shows none (a linear layer: its scan is run again, and nothing of it
    is kept) records the instant at once, with no name and no bytes."""
    named = jax.checkpoint_policies.save_only_these_names(*REMAT_KEPT)
    kept = {}
    if not shows:
        telemetry.get_tracer().record_instant(
            "remat_saved", names=[], bytes_per_block=0, **says)

    def policy(prim, *avals, **params):
        keep = named(prim, *avals, **params)
        if keep and params["name"] not in kept:
            kept[params["name"]] = avals[0].size * avals[0].dtype.itemsize
            if len(kept) == len(shows):
                telemetry.get_tracer().record_instant(
                    "remat_saved", names=sorted(kept),
                    bytes_per_block=sum(kept.values()), **says)
        return keep

    return jax.checkpoint(fn, static_argnums=static_argnums, policy=policy)


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
            blk_fn = _remat(_transformer_block, (2, 3))
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
    ``remat=True`` wraps each block in ``jax.checkpoint`` (``_remat``) —
    activation memory drops from O(num_blocks * S * d) to O(S * d) + one
    block's recompute, the standard trade for long sequences. Of a block
    with ``attn_block`` the attention's out and logsumexp are kept too
    (B S H Dh of the compute dtype + B H S f32 a block), so the recompute
    runs every part of the block but the attention's forward.

    ``ce_block=N`` streams the LOSS head the same way ``attn_block``
    streams attention: the train/eval steps route through
    ``loss_with_metrics`` (ops.nn.streamed_softmax_ce_head), which
    never materializes the (B, S, V) f32 logits — O(N * V) peak in
    both passes. The other memory wall of large-vocab long context
    (the flash VJPs removed the O(S^2) one). ``apply`` still exists
    and still returns full logits (generation/inspection); training
    simply never calls it when ``ce_block`` is set.

    Further choices, each off by default (the tree and the program are
    then the first form's): ``norm`` / ``norm_eps`` (RMSNorm has no bias
    leaf), ``rope_theta`` (rotary positions, no ``pos`` table),
    ``num_kv_heads`` under ``num_heads`` query heads of ``head_dim``,
    ``qk_norm``, ``mlp_gated``, ``biases`` (off: none on the feed-forward
    nor the head); ``moe_top_k`` > 0 makes the feed-forward the DROPLESS
    routed layer (``ops/moe.py:routed_experts``: ``moe_experts`` the
    router's width, ``moe_top_k`` experts a token, ``moe_ffn_dim`` their
    width, of which this model HOLDS ``moe_held_experts`` from
    ``moe_first_expert`` on; ``moe_capacity`` sizes its sorted buffer,
    which is what a step costs; an overflow makes the loss NaN).

    ``layer_plan`` makes the layers DIFFER: one entry a layer,
    ``<attention>:<query heads>:<feed-forward>`` joined by commas
    (``parse_layer_plan``), attention ``full`` (the model's mask) or
    ``window`` (the causal window of ``attn_window`` keys, rotary positions
    of ``window_rope_theta`` on the whole head width), feed-forward
    ``dense`` or ``routed``. ``rope_fraction`` and ``rope_yarn`` shape the
    full layers' rotary positions, ``attn_gate`` gates every layer's
    attention output a head and row, ``moe_shared_dim`` puts a shared gated
    expert beside every routed layer's, ``moe_scoring`` and ``moe_scale``
    say how the router's logits become weights. ``plan`` is the tuple of
    the layers' ``BlockArch`` values that ``init`` and the forward pass
    walk; without ``layer_plan`` its entries are one value. An entry
    ``linear:<value heads>:<feed-forward>`` is a LINEAR layer (Gated
    DeltaNet, ``_linear_half``: the gated delta rule of
    ``ops/linear_attention.py`` over ``linear_key_heads`` key heads of
    ``linear_key_dim`` and its value heads of ``linear_value_dim``, after a
    causal conv of ``linear_conv`` taps); ``attn_gate_elementwise`` gates
    the attention layers' output an element, from a second half of q's
    projection; ``moe_shared_gate`` puts a sigmoid gate a row on the shared
    expert; ``norm="rmsnorm_zero_centred"`` is RMSNorm whose leaves are the
    gain less one (drawn as zeros).

    ``loop_passes=T`` > 1 runs the stack T TIMES over the same weights
    (``_run_passes``: one ``lax.scan``, so one set of layers in the program
    and one accumulator of their gradients): ``ln_f`` closes every pass, its
    output feeds the next pass, the one head and an exit gate (``exit_gate``:
    one linear unit a token), and the loss is the mean over tokens of the
    passes' cross-entropies weighted by the exit distribution the gates
    give, less ``loop_exit_beta`` times that distribution's entropy
    (``_looped_loss``); ``apply`` returns the last pass's logits.
    ``sandwich_norm`` puts a second norm on the OUTPUT of each half of a
    layer before the residual add; ``mlp_dim`` is the dense feed-forward's
    width where it is not ``mlp_ratio * d_model``. With one pass, no
    entropy term and no sandwich norms the tree and the program are what
    they were.

    ``objective="masked_diffusion"`` trains by diffusion over blocks of
    ``diffusion_block`` tokens: ``noise_batch`` (called under the step's
    key, and under a key folded from ``noise_seed`` by the eval) masks
    each position of a block with that block's probability t ~
    U(``diffusion_t_min``, 1) (the mask id is the vocabulary's last, which
    the data leaves out), the network sees ``[noised ; clean]`` (2 S rows,
    positions 0..S-1 twice, the block-diffusion ``Mask``), and the loss is
    the cross-entropy of the masked positions' own tokens, weighted 1/t,
    over B S.
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
        norm: str = "layernorm",
        norm_eps: float = 1e-5,
        rope_theta: float = 0.0,
        num_kv_heads: int = 0,
        head_dim: int = 0,
        qk_norm: bool = False,
        mlp_gated: bool = False,
        biases: bool = True,
        moe_top_k: int = 0,
        moe_ffn_dim: int = 0,
        moe_first_expert: int = 0,
        moe_held_experts: int = 0,
        objective: str = "next_token",
        diffusion_block: int = 4,
        diffusion_t_min: float = 1e-3,
        noise_seed: int = 0,
        layer_plan: str = "",
        attn_window: int = 0,
        window_rope_theta: float = 0.0,
        rope_fraction: float = 1.0,
        rope_yarn: str | tuple = (),
        attn_gate: bool = False,
        moe_shared_dim: int = 0,
        moe_scoring: str = "softmax",
        moe_scale: float = 1.0,
        mlp_dim: int = 0,
        sandwich_norm: bool = False,
        loop_passes: int = 1,
        loop_exit_beta: float = 0.0,
        attn_gate_elementwise: bool = False,
        moe_shared_gate: bool = False,
        linear_key_heads: int = 0,
        linear_key_dim: int = 0,
        linear_value_dim: int = 0,
        linear_conv: int = 0,
        **_unused,
    ):
        if d_model % num_heads and not head_dim:
            raise ValueError(f"d_model={d_model} % num_heads={num_heads} != 0")
        if norm not in NORMS:
            raise ValueError(f"norm={norm!r} is not one of {NORMS}")
        if objective not in ("next_token", "masked_diffusion"):
            raise ValueError(f"objective={objective!r} is neither next_token "
                             f"nor masked_diffusion")
        if num_kv_heads and num_heads % num_kv_heads:
            raise ValueError(f"num_heads={num_heads} must divide over "
                             f"num_kv_heads={num_kv_heads}")
        if moe_top_k and not moe_experts:
            raise ValueError("moe_top_k needs moe_experts > 0")
        if objective == "masked_diffusion" and (
                seq_axis is not None or seq_len % diffusion_block):
            raise ValueError("masked diffusion needs blocks that divide "
                             "seq_len and no sequence parallelism")
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
        self.mlp_dim = int(mlp_dim) or mlp_ratio * d_model
        self.compute_dtype = compute_dtype
        self.seq_axis = seq_axis
        self.attn_block = attn_block
        self.remat = remat
        self.ce_block = ce_block
        self.moe_experts = int(moe_experts)
        self.moe_capacity = float(moe_capacity)
        self.moe_aux = float(moe_aux)
        self.moe_axis = moe_axis
        self.head_dim = int(head_dim) or d_model // num_heads
        self.moe_top_k = int(moe_top_k)
        self.moe_ffn_dim = int(moe_ffn_dim) or self.mlp_dim
        self.moe_first_expert = int(moe_first_expert)
        self.moe_held_experts = int(moe_held_experts) or self.moe_experts
        self.objective = objective
        self.diffusion_block = int(diffusion_block)
        self.diffusion_t_min = float(diffusion_t_min)
        self.noise_seed = int(noise_seed)
        ffn = "routed" if self.moe_top_k else \
            "switch" if self.moe_experts else "dense"
        arch = BlockArch(norm, float(norm_eps), float(rope_theta),
                         int(num_kv_heads), bool(qk_norm), bool(mlp_gated),
                         bool(biases), rope_fraction=float(rope_fraction),
                         rope_yarn=parse_rope_yarn(rope_yarn),
                         attn_gate=bool(attn_gate), ffn=ffn,
                         shared_dim=int(moe_shared_dim) if self.moe_top_k
                         else 0, sandwich=bool(sandwich_norm),
                         attn_gate_elementwise=bool(attn_gate_elementwise),
                         shared_gate=bool(moe_shared_gate))
        if arch.attn_gate and arch.attn_gate_elementwise:
            raise ValueError("attn_gate (one gate a head) and "
                             "attn_gate_elementwise (one an element) are two "
                             "forms of one gate: pick one")
        if moe_shared_gate and not moe_shared_dim:
            raise ValueError("moe_shared_gate gates the shared expert: it "
                             "needs moe_shared_dim > 0")
        if (arch.rope_fraction != 1.0 or arch.rope_yarn) and not rope_theta:
            raise ValueError("rope_fraction and rope_yarn shape rotary "
                             "positions: they need rope_theta > 0")
        if moe_shared_dim and not self.moe_top_k:
            raise ValueError("moe_shared_dim is the routed layer's shared "
                             "expert: it needs moe_top_k > 0")
        # None is the first form: its callers, its tree and its program
        # (what the layers' feed-forward is, is the plan's to say)
        self.arch = None if arch._replace(ffn="dense") == BlockArch() \
            else arch
        self.routing = Routing(self.moe_top_k, self.moe_first_expert,
                               self.moe_capacity, str(moe_scoring),
                               float(moe_scale))
        self.layer_plan = str(layer_plan or "")
        self.attn_window = int(attn_window)
        # the linear layers' (key heads, key width, value width, conv taps)
        self.linear = (int(linear_key_heads), int(linear_key_dim),
                       int(linear_value_dim), int(linear_conv))
        self.plan = self._build_plan(arch, float(window_rope_theta))
        if self.moe_top_k and (moe_axis is not None or not (
                0 <= self.moe_first_expert
                <= self.moe_experts - self.moe_held_experts)):
            raise ValueError(
                f"the routed layer holds experts {self.moe_first_expert}.."
                f"{self.moe_first_expert + self.moe_held_experts - 1} of "
                f"{self.moe_experts} by its configuration, not by a mesh "
                f"axis")
        self.loop_passes = int(loop_passes)
        self.loop_exit_beta = float(loop_exit_beta)
        if self.loop_passes < 1 or self.loop_exit_beta < 0:
            raise ValueError("loop_passes must be >= 1 and loop_exit_beta "
                             ">= 0")
        if self.loop_passes == 1 and self.loop_exit_beta:
            raise ValueError("loop_exit_beta weighs the entropy of the exit "
                             "distribution over passes: it needs "
                             "loop_passes > 1")
        if (self.loop_passes > 1 or arch.sandwich) and any(
                x.ffn != "dense" for x in self.plan):
            raise ValueError("loop_passes > 1 and sandwich_norm are the "
                             "dense feed-forward layers': no moe_experts")
        if self.loop_passes > 1 and (seq_axis is not None
                                     or objective != "next_token"):
            raise ValueError("loop_passes > 1 runs under the next-token "
                             "objective on one device's whole sequence: no "
                             "seq_axis")
        if objective == "masked_diffusion":
            # found by the steps and the eval (getattr): absent otherwise
            self.noise_batch = self._noise_batch

    def _build_plan(self, arch, window_rope_theta):
        """One ``BlockArch`` a layer. Without ``layer_plan`` every layer is
        the model's one value; with it, entry l says layer l's attention,
        query heads and feed-forward."""
        if not self.layer_plan:
            if self.attn_window or window_rope_theta:
                raise ValueError("attn_window and window_rope_theta are the "
                                 "window layers': name those in layer_plan")
            if any(self.linear):
                raise ValueError("linear_key_heads, linear_key_dim, "
                                 "linear_value_dim and linear_conv are the "
                                 "linear layers': name those in layer_plan")
            return (arch,) * self.num_blocks
        if self.seq_axis is not None or self.moe_axis is not None \
                or self.objective != "next_token":
            raise ValueError("layer_plan runs under the next-token "
                             "objective on one device's whole sequence: no "
                             "seq_axis, no moe_axis")
        entries = parse_layer_plan(self.layer_plan)
        if len(entries) != self.num_blocks:
            raise ValueError(f"layer_plan names {len(entries)} layers, "
                             f"num_blocks is {self.num_blocks}")
        kv = arch.kv_heads
        plan = []
        for attention, heads, ffn in entries:
            if attention == "linear":
                if min(self.linear) < 1 or heads % self.linear[0]:
                    raise ValueError(
                        "layer_plan names a linear layer: it needs "
                        "linear_key_heads, linear_key_dim, linear_value_dim "
                        "and linear_conv > 0, and its value heads "
                        f"({heads}) divided over the key heads "
                        f"({self.linear[0]})")
            elif kv and heads % kv:
                raise ValueError(f"layer_plan: {heads} query heads do not "
                                 f"divide over {kv} key/value heads")
            if ffn == "routed" and not self.moe_top_k:
                raise ValueError("layer_plan names a routed layer: it "
                                 "needs moe_top_k > 0")
            layer = arch._replace(heads=heads, ffn=ffn, shared_dim=(
                arch.shared_dim if ffn == "routed" else 0))
            if attention == "window":
                if self.attn_window < 1:
                    raise ValueError("layer_plan names a window layer: it "
                                     "needs attn_window > 0")
                layer = layer._replace(window=self.attn_window)
                if window_rope_theta:
                    layer = layer._replace(rope_theta=window_rope_theta,
                                           rope_fraction=1.0, rope_yarn=())
            elif attention == "linear":
                layer = layer._replace(linear=self.linear)
            plan.append(layer)
        if self.moe_top_k and not any(x.ffn == "routed" for x in plan):
            raise ValueError("moe_top_k > 0 and layer_plan names no routed "
                             "layer")
        if any(self.linear) and not any(x.linear for x in plan):
            raise ValueError("linear_key_heads, linear_key_dim, "
                             "linear_value_dim and linear_conv are set and "
                             "layer_plan names no linear layer")
        return tuple(plan)

    @property
    def mask_token(self) -> int:
        """The id that stands for a masked position: the vocabulary's
        last, which the data never draws (data/lm.py)."""
        return self.vocab_size - 1

    def init(self, key, dtype=jnp.float32):
        d, dh = self.d_model, self.head_dim
        arch = self.arch
        # 8 keys a layer; a plan's layers may hold more matrices (a gate, a
        # shared expert and its gate, the linear layer's conv and decays):
        # 12
        keys = iter(jax.random.split(
            key, 4 + (12 if self.layer_plan else 8) * self.num_blocks))

        def w(shape, stddev=0.02, uniform=None):
            if uniform is not None:  # U(low, high)
                return jax.random.uniform(next(keys), shape, dtype, *uniform)
            return truncated_normal_init(next(keys), shape, stddev, dtype)

        params = {"tok": w((self.vocab_size, d))}
        if arch is None or not arch.rope_theta:
            params["pos"] = w((self.seq_len, d))
        params["blocks"] = []
        params["ln_f"] = _norm_params("", d, dtype, arch)
        params["head"] = {"w": w((d, self.vocab_size))}
        if arch is None or arch.biases:
            params["head"]["b"] = jnp.zeros((self.vocab_size,), dtype)
        if self.loop_passes > 1:
            # one linear unit on a pass's normed output, a token
            params["exit_gate"] = {"w": w((d, 1)), "b": jnp.zeros((1,), dtype)}
        for layer in self.plan:
            h = layer.heads or self.num_heads
            if layer.ffn == "routed":
                params["blocks"].append(_routed_block_params(
                    w, d, h, dh, self.moe_ffn_dim, self.moe_experts,
                    self.moe_held_experts, dtype, layer))
            elif layer.ffn == "switch":
                params["blocks"].append(_moe_block_params(
                    w, d, h, dh, self.mlp_dim, self.moe_experts, dtype))
            else:
                params["blocks"].append(_block_params(
                    w, d, h, dh, self.mlp_dim, dtype, layer))
        return params

    def _noise_batch(self, batch, key):
        """(tokens (B, S), _) -> ([noised ; clean] (B, 2 S) int32, the
        loss weights (B, S) f32: 1/t at a masked position, else 0). A block
        of ``diffusion_block`` positions shares t ~ U(t_min, 1); a
        position is masked where its own u ~ U(0, 1) falls under it."""
        x0 = batch[0].astype(jnp.int32)
        b, s = x0.shape
        k_t, k_u = jax.random.split(key)
        t = jax.random.uniform(k_t, (b, s // self.diffusion_block),
                               jnp.float32, self.diffusion_t_min, 1.0)
        t = jnp.repeat(t, self.diffusion_block, axis=1)
        masked = jax.random.uniform(k_u, (b, s), jnp.float32) < t
        noised = jnp.where(masked, self.mask_token, x0)
        return (jnp.concatenate([noised, x0], axis=1),
                jnp.where(masked, 1.0 / t, 0.0))

    def apply_hidden(self, params, x, *, keep_prob=1.0, rng=None,
                     train: bool = False):
        """Everything up to (but not including) the vocab head: final
        hidden states (B, S, d) after ln_f + dropout. The streamed-CE
        path consumes this directly so the (B, S, V) logits never
        materialize; ``apply`` adds the head on top."""
        h = self._hidden_and_aux(params, x, keep_prob=keep_prob, rng=rng,
                                 train=train)[0]
        # of a stack run several times, the last pass's
        return h[-1] if self.loop_passes > 1 else h

    def attention_fn(self, window: int = 0):
        """``(q, k, v) -> out``, all (B, S, H, Dh): this model's causal
        attention flavor (ring / blockwise / dense), or with ``window`` a
        window layer's. The one place the choice is made: the pipeline
        stages call it, and the TP step wraps it per head shard
        (parallel/tensor_parallel.py)."""
        if self.seq_axis is not None:
            return lambda q, k, v: ring_attention(
                q, k, v, self.seq_axis, causal=True)
        mask = None
        if window:
            mask = Mask("window", window=window)
        elif self.objective == "masked_diffusion":
            mask = Mask("block_diffusion", self.seq_len, self.diffusion_block)
        if mask is not None:
            if self.attn_block is not None:
                return lambda q, k, v: blockwise_attention(
                    q, k, v, self.attn_block, mask=mask)
            return lambda q, k, v: multi_head_attention(q, k, v, mask=mask)
        if self.attn_block is not None:
            return lambda q, k, v: blockwise_attention(
                q, k, v, self.attn_block, causal=True)
        return lambda q, k, v: multi_head_attention(q, k, v, causal=True)

    def _layer_fns(self):
        """layer's ``BlockArch`` -> ``(h, blk, ids) -> (h, aux)``: each
        KIND of layer of the plan once (its attention, its block form, its
        checkpoint under ``remat``), so that layers alike share a trace."""
        cd = self.compute_dtype
        moe = {"routed": self.routing,
               "switch": (self.moe_capacity, self.moe_axis)}
        full = self.attention_fn()
        fns = {}
        for layer in dict.fromkeys(self.plan):
            attn = self.attention_fn(layer.window) if layer.window else full
            block = _planned_block
            if self.remat:
                says = {}
                if self.layer_plan:
                    says = {"attention": "linear" if layer.linear else
                            "window" if layer.window else "full",
                            "heads": layer.heads, "ffn": layer.ffn}
                if layer.linear:  # names nothing: its scan runs again
                    says["shows"] = ()
                block = _remat(_planned_block, (2, 3, 4, 6), **says)

            def run(h, blk, ids, block=block, attn=attn, layer=layer):
                return block(h, blk, attn, cd, layer, ids, moe.get(layer.ffn))

            fns[layer] = run
        return fns

    def _hidden_and_aux(self, params, x, *, keep_prob=1.0, rng=None,
                        train: bool = False):
        """(hidden, moe load-balance loss total) — the aux term is 0.0
        for dense-MLP models; loss_with_metrics adds it to the training
        loss scaled by ``moe_aux``. With the routed layer the second is
        the routing counters of the layers (a dict); under masked
        diffusion x is ``[noised ; clean]`` and the hidden states are the
        noised half's. With ``loop_passes`` > 1: (every pass's hidden
        states (T, B, S, d), the exit gate's logits (T, B, S))."""
        cd = self.compute_dtype
        arch = self.arch
        diffusion = self.objective == "masked_diffusion"
        # x: integer ids (B, S) — or the LOCAL token block (B, S/P) when
        # called inside the SP shard_map step
        with scope("embed"):
            h = jnp.take(params["tok"], x, axis=0)
            if "pos" in params:
                pos = params["pos"]
                if self.seq_axis is not None:
                    s_local = x.shape[1]
                    start = lax.axis_index(self.seq_axis) * s_local
                    pos = lax.dynamic_slice_in_dim(pos, start, s_local,
                                                   axis=0)
                elif diffusion:
                    pos = jnp.concatenate([pos, pos])
                h = h + pos.astype(h.dtype)
            if cd is not None:
                h = h.astype(cd)
            # the rows' position ids, where a block wants them (rotary):
            # both halves of the doubled sequence count from 0
            ids = None
            if diffusion:
                ids = jnp.tile(jnp.arange(self.seq_len), 2)

        fns = self._layer_fns()
        if self.loop_passes > 1:
            return self._run_passes(params, fns, h, ids, keep_prob, rng,
                                    train)
        lb_total = jnp.float32(0.0)
        routed = []
        for blk, layer in zip(params["blocks"], self.plan):
            h, aux = fns[layer](h, blk, ids)
            if layer.ffn == "routed":
                routed.append(aux)
            elif layer.ffn == "switch":
                lb_total = lb_total + aux
        if routed:
            by = {k: jnp.stack([a[k] for a in routed]) for k in routed[0]}
            # the fullest expert and buffer of any layer, the layers' means
            lb_total = {
                "rows_per_expert_max": by["rows_per_expert_max"].max(),
                "rows_per_expert_mean": by["rows_per_expert_mean"].mean(),
                "overflow_rows": by["overflow_rows"].sum(),
                "buffer_fill_max": by["buffer_fill"].max(),
                "tiles_run_frac": by["tiles_run_frac"].mean(),
                "dispatch_tiles_frac": by["dispatch_tiles_frac"].mean(),
                "unrouted_frac": by["unrouted_frac"].mean(),
            }

        with scope("lm_head"):
            if diffusion:
                h = h[:, : self.seq_len]  # the noised half alone is scored
            h = _norm(h, params["ln_f"], "", arch)
            if rng is not None and self.seq_axis is not None:
                # per-token dropout: decorrelate the mask across sequence
                # shards (each shard holds DIFFERENT tokens — unlike the
                # classifier's post-pool dropout, which must be identical)
                rng = jax.random.fold_in(rng,
                                         lax.axis_index(self.seq_axis))
            return (nn.dropout(h, keep_prob, rng, deterministic=not train),
                    lb_total)

    def _run_passes(self, params, fns, h, ids, keep_prob, rng, train):
        """The stack run ``loop_passes`` times: pass t takes in what pass
        t - 1 handed on, the final norm closes every pass, and its output
        goes to the head, to the exit gate and into the next pass. Returns
        ((T, B, S, d) the passes' outputs, (T, B, S) f32 the gate's
        logits).

        The passes are a ``lax.scan`` whose body closes over the ONE set of
        blocks: the compiled program holds the layers once, and the
        backward pass sums the T contributions to a shared weight's
        gradient in one accumulator. Under ``remat`` a block keeps what it
        keeps (its input and ``REMAT_KEPT``) once a pass, T times a step.
        ``loop_exit`` holds the gate's unit alone here; the scan's own work
        (the outputs stacked, the shared gradients' accumulation) is under
        no scope of the catalog and reads as unscoped."""
        t_passes, layers = self.loop_passes, len(self.plan)
        blocks, gate = params["blocks"], params["exit_gate"]
        if len(blocks) != layers:
            raise ValueError(f"{len(blocks)} blocks are not the {layers} "
                             f"that every pass runs")

        def close_pass(h, ln_f, gate):
            with scope("lm_head"):
                h = _norm(h, ln_f, "", self.arch)
            with scope("loop_exit"):
                # one unit: a multiply and a row sum in f32, no MXU pass
                logit = jnp.sum(h.astype(jnp.float32)
                                * gate["w"][:, 0].astype(jnp.float32),
                                axis=-1) + gate["b"].astype(jnp.float32)
            return h, logit

        kept = None
        if self.remat:
            # else the norm and the gate keep three f32 copies of the
            # stream a pass (805 MB at 4 x 8,192 rows of 2,048)
            close_pass = jax.checkpoint(close_pass)
            # kept a pass: the blocks' inputs, and out + logsumexp
            rows = h.shape[0] * h.shape[1]
            kept = layers * rows * h.shape[2] * h.dtype.itemsize
            if self.attn_block is not None:
                kept += sum(rows * (x.heads or self.num_heads)
                            * (self.head_dim * h.dtype.itemsize + 4)
                            for x in self.plan)
        telemetry.get_tracer().record_instant(
            "loop_plan", passes=t_passes, layers=layers, lowered="scan",
            kept_bytes_per_pass=kept)

        def one_pass(h, _):
            for blk, layer in zip(blocks, self.plan):
                h = fns[layer](h, blk, ids)[0]
            h, logit = close_pass(h, params["ln_f"], gate)
            return h, (h, logit)

        _, (hs, logits) = lax.scan(one_pass, h, None, length=t_passes)
        with scope("lm_head"):
            return (nn.dropout(hs, keep_prob, rng, deterministic=not train),
                    logits)

    def _looped_loss(self, params, hs, gate_logits, y, train):
        """The loss of a stack run T times: a token's cross-entropy after
        every pass (the one head, T x B x S rows), weighted by its exit
        distribution p(t), less ``loop_exit_beta`` times that
        distribution's entropy; the mean over tokens. The gradient reaches
        the gate through p, so the rows' cross-entropies are values it is
        taken AROUND as well as through. The eval's ``loss`` is the
        expected cross-entropy without the entropy term; ``accuracy`` is
        the last pass's. Counters under ``loop_``: the batch's mean p(t),
        the expected number of passes, the entropy, each pass's mean
        cross-entropy (a later pass that reads no lower than the first
        says the loop is not helping)."""
        labels = jnp.broadcast_to(y, (self.loop_passes,) + y.shape)
        head = params["head"]
        if self.ce_block:
            ce, hit = nn.streamed_softmax_ce_rows(
                hs, head["w"], head.get("b"), labels, block=self.ce_block,
                compute_dtype=self.compute_dtype)
        else:
            with scope("lm_head"):
                logits = nn.dense(hs, head["w"], head.get("b"),
                                  compute_dtype=self.compute_dtype)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                onehot = jax.nn.one_hot(labels, logp.shape[-1],
                                        dtype=logp.dtype)
                ce = -jnp.sum(jnp.where(onehot != 0, logp, 0.0), axis=-1)
                hit = (jnp.argmax(logp, -1) == labels).astype(jnp.float32)
        with scope("loop_exit"):
            log_p = exit_log_distribution(gate_logits)
            p = jnp.exp(log_p)
            expected = jnp.mean(jnp.sum(p * ce, axis=0))
            entropy = jnp.mean(-jnp.sum(p * log_p, axis=0))
            loss = expected - self.loop_exit_beta * entropy
            by_pass = p.reshape(self.loop_passes, -1).mean(axis=1)
            ce_by_pass = ce.reshape(self.loop_passes, -1).mean(axis=1)
            metrics = {"loss": loss if train else expected,
                       "accuracy": jnp.mean(hit[-1]),
                       "loop_expected_passes": jnp.sum(
                           by_pass * jnp.arange(1, self.loop_passes + 1)),
                       "loop_exit_entropy": entropy}
            for t in range(self.loop_passes):
                metrics[f"loop_exit_p{t + 1}"] = by_pass[t]
                metrics[f"loop_ce_{t + 1}"] = ce_by_pass[t]
        return loss, metrics

    def apply(self, params, x, *, keep_prob=1.0, rng=None, train: bool = False):
        h = self.apply_hidden(params, x, keep_prob=keep_prob, rng=rng,
                              train=train)
        with scope("lm_head"):
            logits = nn.dense(h, params["head"]["w"],
                              params["head"].get("b"),
                              compute_dtype=self.compute_dtype)
            return logits.astype(jnp.float32)

    @property
    def wants_loss_hook(self) -> bool:
        """True when training/eval must route through
        ``loss_with_metrics`` (training.loss_and_metrics checks this):
        the streamed CE head, the MoE auxiliary loss or counters, the
        masked-diffusion loss, the loss over the passes of a stack run
        several times."""
        return bool(self.ce_block or self.moe_experts
                    or self.objective == "masked_diffusion"
                    or self.loop_passes > 1)

    def loss_with_metrics(self, params, x, y, *, keep_prob=1.0, rng=None,
                          train: bool = False):
        """(loss, metrics) — the train/eval hook. With ``ce_block`` the
        CE is the streamed head (values/grads match apply +
        softmax_cross_entropy to fp tolerance, tests/test_lm.py); with
        ``moe_experts`` the TRAINING loss adds ``moe_aux`` times the
        Switch load-balance term (metrics report it either way; eval
        loss stays the plain CE); with ``loop_passes`` > 1 it is
        ``_looped_loss``."""
        h, lb = self._hidden_and_aux(params, x, keep_prob=keep_prob,
                                     rng=rng, train=train)
        if self.loop_passes > 1:
            return self._looped_loss(params, h, lb, y, train)
        diffusion = self.objective == "masked_diffusion"
        weights = denominator = None
        if diffusion:
            # x is [noised ; clean] and y the weights of ``noise_batch``: a
            # masked position predicts its own token, no shift
            weights, y = y, x[:, self.seq_len:]
            denominator = float(y.shape[0] * y.shape[1])
        if self.ce_block:
            ce, acc = nn.streamed_softmax_ce_head(
                h, params["head"]["w"], params["head"].get("b"), y,
                block=self.ce_block, compute_dtype=self.compute_dtype,
                weights=weights, denominator=denominator)
        else:
            with scope("lm_head"):
                logits = nn.dense(h, params["head"]["w"],
                                  params["head"].get("b"),
                                  compute_dtype=self.compute_dtype)
                logits = logits.astype(jnp.float32)
                if diffusion:
                    logp = jax.nn.log_softmax(logits, axis=-1)
                    own = jnp.take_along_axis(logp, y[..., None], -1)[..., 0]
                    ce = -jnp.sum(own * weights) / denominator
                    acc = jnp.sum((jnp.argmax(logits, -1) == y)
                                  * (weights > 0)) / denominator
                else:
                    ce = nn.softmax_cross_entropy(logits, y)
                    acc = nn.accuracy(logits, y)
        metrics = {"loss": ce, "accuracy": acc}
        if diffusion:
            masked = jnp.mean((weights > 0).astype(jnp.float32))
            # hits among the masked positions, not among all
            metrics["accuracy"] = acc / jnp.maximum(masked, 1e-9)
            metrics["diffusion_masked_frac"] = masked
        loss = ce
        if self.moe_top_k:
            metrics.update({f"moe_{k}": v for k, v in lb.items()})
            # an overflow is a failed step, never a silent drop
            loss = jnp.where(lb["overflow_rows"] > 0, jnp.nan, ce)
            metrics["loss"] = loss
        elif self.moe_experts:
            metrics["moe_lb"] = lb
            if train:
                loss = ce + self.moe_aux * lb
        return loss, metrics

    def num_params(self, params=None):
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.key(0)))
        return sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
