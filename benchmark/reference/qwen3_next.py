"""The family ``qwen3_next``: a decoder of linear-attention layers (Gated
DeltaNet) among gated full-attention layers, every layer a mixture of small
experts beside a gated shared one (``model_type: qwen3_next``,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), cut to one chip's
share of a stated deployment, as ``models/transformer.py:TransformerLM``
trains it under ``--layer_plan``. A configuration file names it
(``"family": "qwen3_next"``) and the harness finds here, by the names of
``harness/manifest.py:FAMILY_NAMES``: the sizes and the trainer's flags the
configuration maps to, the plain reference for the first training steps and
the operation counts (at the end of the file).

The equations (S tokens; d the hidden size; ``N(x, w) = x / sqrt(mean(x^2)
+ eps) (1 + w)``, the zero-centred norm, ``w`` drawn as 0 (A); what the
published ``config.json`` does not say is marked (A) and listed under
``assumed`` in the configuration's file):

1. ``h = E[x]`` (no position table). Layer l is full attention where (l +
   1) is a multiple of ``full_attention_interval``, linear otherwise.
   ``h <- h + Mixer(N(h, w1))``, then ``h <- h + MoE(N(h, w2))``.
2. *Gated full attention.* ``[q ; gate] = a Wq``, H heads of 2 Dh split a
   head (A: the gate is elementwise over the head width); ``k = a Wk``,
   ``v = a Wv`` (Hkv heads); ``q, k = N(q, wq), N(k, wk)`` over Dh; rotary
   positions (rotate-half) on the first ``r Dh`` of q's and k's width,
   ``inv_i = theta^(-2i / (r Dh))``; query head n reads key/value head ``n
   // (H / Hkv)``; ``o = softmax(q k^T / sqrt(Dh) + M) v``, M the causal
   mask built densely from the indices; ``o = o * sigmoid(gate)``; the
   output ``o Wo``. No biases.
3. *Gated DeltaNet* (arXiv:2412.06464). ``[q k v z] = a Wqkvz`` (q and k of
   Hk key heads of dk, v and z of Hv value heads of dv), ``[b a] = a Wba``
   (Hv each); the q, k and v channels pass a causal depthwise convolution of
   K taps, ``c_t = sum_i w_i x_{t - K + 1 + i}``, then SiLU; ``beta =
   sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` a value head and
   token; ``q, k = l2(q), l2(k)`` (``x / sqrt(sum x^2 + 1e-6)``), ``q = q /
   sqrt(dk)``; value head n reads key head ``n // (Hv / Hk)``. Then the
   recurrence, a value head at a time from ``S_0 = 0`` (dk x dv):
   ``S <- exp(g_t) S``; ``u_t = beta_t (v_t - S^T k_t)``; ``S <- S + k_t
   u_t^T``; ``o_t = S^T q_t``. ``o = rms(o) * w_o * silu(z)`` over dv (an
   ordinary gain, drawn as 1); the output ``o Wout``. (A) init: ``A ~ U(1e-4,
   16)``, ``dt_bias`` ones, the taps ``U(-0.5, 0.5)``.
4. *The mixture.* ``p = softmax(b Wr)`` over all E experts, ``T =
   top_k(p)``, ``w_e = p_e / sum_{e' in T} p_e'``; ``MoE(b) = sum_{e in T, e
   held} w_e F_e(b) + sigmoid(b . w_sg) F_shared(b)``, every F a SwiGLU.
   Experts that this chip does not hold add nothing; the shared expert and
   its gate are whole on every chip.
5. *Loss.* ``z = N(h, w_f) W_head``; the mean over the batch's rows and
   positions of the cross-entropy of the next token.

It imports nothing of ``distributed_tensorflow_tpu``. The linear layers run
the RECURRENCE above, a token at a time (a ``lax.scan`` over positions,
checkpointed every ``SEGMENT`` tokens so that its backward pass keeps a
state a segment), not the program's chunked form: the program's chunks are
held to the definition. Attention runs a head at a time and, inside a head,
``QUERY_BLOCK`` query rows at a time against all keys (a dense score matrix
of the block under the mask); the held experts are a ``lax.scan``, each
applied to every row and selected by its weight; what acts on a row alone
(the feed-forward, the head and its loss) runs over blocks of ``ROW_BLOCK``
rows; each layer, head, block and segment is rematerialised. None of that
changes a value. What the families share (the procedural tokens, the
sampled rows' key chain, the float8 control's rounding, Adam, the leaves'
names and norms) is ``opt_lm``'s, imported.

``precision="fp8"`` is the control: every linear layer (q with its gate, k,
v, the output projections, ``qkvz``, ``ba``, both matrices of every expert
and of the shared one, the shared expert's gate, the head) rounds its
operands and its result to float8 e4m3, one scale a tensor (a head's slice
of q, a block of rows where the layer runs over blocks); the router stays
float32 as the program's does, and so does all between a linear layer's
projections. ``keep_rows`` plants the half-batch fault, ``learning_rate=0``
the unchanged state.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import opt_lm as base

leaf_names = base.leaf_names
first_batches = base.first_batches
KEYS_PER_LAYER = 12  # of the seed's split: a layer draws at most 11 arrays
ROW_BLOCK = 1024     # rows at a time through what acts on a row alone
QUERY_BLOCK = 4096   # query rows of a head's attention at a time
SEGMENT = 128        # tokens of the recurrence between two kept states
L2_EPS = 1e-6


# ---- the configuration, as the counts, the reference and the trainer take it

def sizes(config: dict, mix: dict) -> dict:
    held = config["experts_held"]
    if held["count"] != config["num_experts"]:
        raise ValueError("num_experts is the count of experts held")
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("every layer of this family is a mixture of experts")
    layers, every = config["num_hidden_layers"], config["full_attention_interval"]
    return {"d_model": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "num_blocks": layers,
            "layer_types": tuple(
                "full_attention" if (layer + 1) % every == 0
                else "linear_attention" for layer in range(layers)),
            "rope_theta": float(config["rope_theta"]),
            "rope_fraction": float(config["partial_rotary_factor"]),
            "key_heads": config["linear_num_key_heads"],
            "value_heads": config["linear_num_value_heads"],
            "key_dim": config["linear_key_head_dim"],
            "value_dim": config["linear_value_head_dim"],
            "conv": config["linear_conv_kernel_dim"],
            "router_width": held["router_width"],
            "held_experts": held["count"],
            "first_expert": held["first"],
            "top_k": config["num_experts_per_tok"],
            "expert_dim": config["moe_intermediate_size"],
            "shared_dim": config["shared_expert_intermediate_size"],
            "vocab_size": config["vocab_size"],
            "norm_eps": config["rms_norm_eps"],
            "seq_len": mix["seq_len"]}


def trainer_flags(config: dict, mix: dict) -> dict:
    """The model's own flags of ``mnist_dist.py``, each named by its
    mechanism."""
    if config["tie_word_embeddings"] or not config["norm_topk_prob"] \
            or config["hidden_act"] != "silu" or config["use_sliding_window"] \
            or config["rope_scaling"] is not None:
        raise ValueError("the head is untied, the top-k weights renormalised, "
                         "the feed-forwards SwiGLU, attention full and its "
                         "rotary positions unscaled")
    s = sizes(config, mix)
    plan = ",".join(
        f"full:{s['heads']}:routed" if t == "full_attention"
        else f"linear:{s['value_heads']}:routed" for t in s["layer_types"])
    out = {"d_model": s["d_model"], "num_heads": s["heads"],
           "num_blocks": s["num_blocks"], "vocab_size": s["vocab_size"],
           "norm": "rmsnorm_zero_centred", "norm_eps": s["norm_eps"],
           "num_kv_heads": s["kv_heads"], "head_dim": s["head_dim"],
           "qk_norm": True, "mlp_gated": True, "biases": False,
           "layer_plan": plan, "rope_theta": s["rope_theta"],
           "rope_fraction": s["rope_fraction"],
           "attn_gate_elementwise": True,
           "linear_key_heads": s["key_heads"], "linear_key_dim": s["key_dim"],
           "linear_value_dim": s["value_dim"], "linear_conv": s["conv"],
           "moe_experts": s["router_width"], "moe_top_k": s["top_k"],
           "moe_ffn_dim": s["expert_dim"],
           "moe_first_expert": s["first_expert"],
           "moe_held_experts": s["held_experts"],
           "moe_shared_dim": s["shared_dim"], "moe_shared_gate": True}
    # the trainer's parser passes an unknown flag over in silence: a
    # checkout without these mechanisms would train another model under
    # this configuration's name. ``run.py`` has imported the trainer's
    # entry by now (nothing is imported here); ask it, and fail at once
    trainer = sys.modules.get("mnist_dist")
    if trainer is not None:
        missing = [k for k in out if not hasattr(trainer.FLAGS, k)]
        if missing:
            raise ValueError(
                f"this checkout's trainer has no flag for {missing}: it "
                f"cannot run a configuration of the family qwen3_next")
    return out


# ---- parameters -----------------------------------------------------------

def init_params(seed: int, sizes: dict, prng: str = "threefry2x32"):
    """Truncated normal (two sigma) times 0.02 for every matrix, zeros for
    the zero-centred norms, ones for the linear layers' output gain and
    ``dt_bias``. The seed's key splits in two; the first half splits into
    4 + 12 L keys, taken in the order token table, head, then of each layer
    q, kv, proj (full) or qkvz, ba, the conv's taps, A, proj (linear), then
    router, w1, w2, the shared expert's w1, w2 and gate."""
    d, kv, dh = sizes["d_model"], sizes["kv_heads"], sizes["head_dim"]
    nk, dk = sizes["key_heads"], sizes["key_dim"]
    nv, dv = sizes["value_heads"], sizes["value_dim"]
    held, f, fs = (sizes["held_experts"], sizes["expert_dim"],
                   sizes["shared_dim"])
    pkey = jax.random.split(base._key(seed, prng))[0]
    keys = iter(jax.random.split(pkey, 4 + KEYS_PER_LAYER * sizes["num_blocks"]))

    def w(shape):
        return base.INIT_STDDEV * jax.random.truncated_normal(
            next(keys), -2.0, 2.0, shape, jnp.float32)

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    zeros = lambda n: jnp.zeros((n,), jnp.float32)  # noqa: E731
    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"tok": w((sizes["vocab_size"], d)), "blocks": [],
              "ln_f": {"g": zeros(d)}, "head": {"w": w((d, sizes["vocab_size"]))}}
    for kind in sizes["layer_types"]:
        heads = sizes["heads"]
        if kind == "full_attention":
            blk = {"ln1_g": zeros(d), "q": w((d, heads, 2 * dh)),
                   "kv": w((d, 2, kv, dh)), "q_norm_g": zeros(dh),
                   "k_norm_g": zeros(dh), "proj": w((heads * dh, d))}
        else:
            blk = {"ln1_g": zeros(d), "qkvz": w((d, 2 * nk * dk + 2 * nv * dv)),
                   "ba": w((d, 2 * nv)),
                   "conv": uniform((sizes["conv"], 2 * nk * dk + nv * dv),
                                   -0.5, 0.5),
                   "a_log": jnp.log(uniform((nv,), 1e-4, 16.0)),
                   "dt_bias": ones(nv), "o_norm_g": ones(dv),
                   "proj": w((nv * dv, d))}
        blk["ln2_g"] = zeros(d)
        blk["moe"] = {"router": w((d, sizes["router_width"])),
                      "w1": w((held, d, 2 * f)), "w2": w((held, f, d))}
        blk["shared"] = {"w1": w((d, 2 * fs)), "w2": w((fs, d)),
                         "gate": w((d, 1))}
        params["blocks"].append(blk)
    return params


# ---- the model, one sequence at a time --------------------------------------

def _norm(x, w, eps):
    """The zero-centred norm of equation 1."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _rotate(x, pos, theta, share):
    """x (rows, heads, Dh), pos (rows,): rotate-half on the first ``share *
    Dh`` dimensions, the rest passed through."""
    dr = int(round(share * x.shape[-1]))
    inv = theta ** (-2.0 * jnp.arange(dr // 2, dtype=jnp.float32) / dr)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    xr, rest = x[..., :dr], x[..., dr:]
    x1, x2 = xr[..., : dr // 2], xr[..., dr // 2:]
    return jnp.concatenate(
        [xr * cos + jnp.concatenate([-x2, x1], -1) * sin, rest], -1)


def _query_blocks(q, k, v):
    """softmax(q k^T / sqrt(Dh) + M) v for (rows, Dh) q, k, v, M the causal
    mask from the indices; ``QUERY_BLOCK`` query rows at a time, each block
    rematerialised."""
    rows, dh = q.shape
    block = QUERY_BLOCK if rows > QUERY_BLOCK and rows % QUERY_BLOCK == 0 \
        else rows

    @jax.checkpoint
    def one(qb, start):
        i = start + jnp.arange(block)[:, None]
        j = jnp.arange(rows)[None, :]
        scores = jnp.where(j <= i, jnp.dot(qb, k.T) / math.sqrt(dh), -jnp.inf)
        return jnp.dot(jax.nn.softmax(scores, axis=-1), v)

    starts = jnp.arange(0, rows, block)
    out = jax.lax.map(lambda a: one(*a),
                      (q.reshape(rows // block, block, dh), starts))
    return out.reshape(rows, -1)


@functools.partial(jax.checkpoint, static_argnums=(7, 8))
def _head(a, wq, wo, gq, k, v, n, sizes_t, precision):
    """Query head ``n`` of equation 2, from the normalised rows ``a`` to its
    part of ``o Wo``: (rows, d). ``k``, ``v``: its key/value head's (rows,
    Dh), normalised and rotated."""
    sizes = dict(sizes_t)
    dh = sizes["head_dim"]
    qg = base._linear(a, wq[:, n], precision)                   # (rows, 2 Dh)
    q = _rotate(_norm(qg[:, :dh], gq, sizes["norm_eps"])[:, None],
                jnp.arange(a.shape[0]), sizes["rope_theta"],
                sizes["rope_fraction"])[:, 0]
    o = _query_blocks(q, k, v) * jax.nn.sigmoid(qg[:, dh:])
    return base._linear(o, wo[n], precision)


def full_attention(a, blk, sizes_t, precision):
    """Equation 2 on normalised rows (rows, d): the mixer's output."""
    sizes = dict(sizes_t)
    rows, d = a.shape
    kv, dh, heads = sizes["kv_heads"], sizes["head_dim"], sizes["heads"]
    kvp = base._linear(a, blk["kv"].reshape(d, 2 * kv * dh), precision)
    k, v = jnp.moveaxis(kvp.reshape(rows, 2, kv, dh), 1, 0)
    k = _rotate(_norm(k, blk["k_norm_g"], sizes["norm_eps"]), jnp.arange(rows),
                sizes["rope_theta"], sizes["rope_fraction"])
    group = heads // kv
    wo = blk["proj"].reshape(heads, dh, d)

    def add_head(y, n):  # a loop: the compiled program holds one head
        return y + _head(a, blk["q"], wo, blk["q_norm_g"], k[:, n // group],
                         v[:, n // group], n, sizes_t, precision), None

    return jax.lax.scan(add_head, jnp.zeros_like(a), jnp.arange(heads))[0]


def delta_rule(q, k, v, g, beta):
    """The recurrence of equation 3, a token at a time: q, k (S, H, dk), v
    (S, H, dv), g and beta (S, H) -> o (S, H, dv). A ``lax.scan`` over
    segments of ``SEGMENT`` positions, each a rematerialised scan over its
    tokens, so that the backward pass keeps one state a segment."""
    s, h, dk = q.shape

    def one_token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[:, None, None] * state
        u = beta_t[:, None] * (v_t - jnp.einsum("hde,hd->he", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t)

    @jax.checkpoint
    def one_segment(state, xs):
        return jax.lax.scan(one_token, state, xs)

    seg = SEGMENT if s % SEGMENT == 0 else s
    xs = tuple(x.reshape(s // seg, seg, *x.shape[1:])
               for x in (q, k, v, g, beta))
    state = jnp.zeros((h, dk, v.shape[-1]), jnp.float32)
    return jax.lax.scan(one_segment, state, xs)[1].reshape(s, h, -1)


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _delta_inputs(a, blk, sizes_t, precision):
    """Equation 3 from the normalised rows to the recurrence's inputs and
    z: (q, k, v, g, beta, z)."""
    sizes = dict(sizes_t)
    rows = a.shape[0]
    nk, dk = sizes["key_heads"], sizes["key_dim"]
    nv, dv = sizes["value_heads"], sizes["value_dim"]
    qkvz = base._linear(a, blk["qkvz"], precision)
    ba = base._linear(a, blk["ba"], precision)
    mixed = 2 * nk * dk + nv * dv
    x = qkvz[:, :mixed]
    taps = blk["conv"]
    width = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, mixed), x.dtype), x])
    x = jax.nn.silu(sum(taps[i] * padded[i:i + rows] for i in range(width)))
    q = _l2(x[:, :nk * dk].reshape(rows, nk, dk)) / math.sqrt(dk)
    k = _l2(x[:, nk * dk:2 * nk * dk].reshape(rows, nk, dk))
    group = nv // nk
    q, k = q[:, np.arange(nv) // group], k[:, np.arange(nv) // group]
    v = x[:, 2 * nk * dk:].reshape(rows, nv, dv)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(blk["a_log"]) * jax.nn.softplus(ba[:, nv:] + blk["dt_bias"])
    return q, k, v, g, beta, qkvz[:, mixed:].reshape(rows, nv, dv)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _delta_output(o, z, blk, sizes_t, precision):
    sizes = dict(sizes_t)
    o = _rms(o, blk["o_norm_g"], sizes["norm_eps"]) * jax.nn.silu(z)
    return base._linear(o.reshape(o.shape[0], -1), blk["proj"], precision)


def linear_attention(a, blk, sizes_t, precision):
    """Equation 3 on normalised rows (rows, d): the mixer's output."""
    q, k, v, g, beta, z = _delta_inputs(a, blk, sizes_t, precision)
    return _delta_output(delta_rule(q, k, v, g, beta), z, blk, sizes_t,
                         precision)


def _swiglu(b, w1, w2, precision):
    f = w2.shape[0]
    up = base._linear(b, w1, precision)
    return base._linear(jax.nn.silu(up[:, :f]) * up[:, f:], w2, precision)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _expert(b, w1, w2, weight, e, precision):
    """Expert ``e`` on every row, times the row's weight for it (nought
    where the row did not choose it)."""
    return weight[:, None] * _swiglu(b, w1[e], w2[e], precision)


def routed_layer(b, moe, sizes: dict, precision: str = "f32",
                 first: int | None = None):
    """(rows, d) -> the part of the mixture's output that the experts
    ``first .. first + held - 1`` give (``moe["w1"]``'s), for every row."""
    first = sizes["first_expert"] if first is None else first
    held = moe["w1"].shape[0]
    probs = jax.nn.softmax(jnp.dot(b, moe["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, sizes["top_k"])
    gate = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    def add_expert(y, e):  # a loop: the compiled program holds one expert
        weight = jnp.sum(jnp.where(top_e == first + e, gate, 0.0), axis=-1)
        return y + _expert(b, moe["w1"], moe["w2"], weight, e, precision), None

    return jax.lax.scan(add_expert, jnp.zeros_like(b), jnp.arange(held))[0]


def feed_forward(b, blk, sizes: dict, precision: str = "f32"):
    """Equation 4 on normalised rows (rows, d)."""
    shared = blk["shared"]
    gate = jax.nn.sigmoid(base._linear(b, shared["gate"], precision))
    return (routed_layer(b, blk["moe"], sizes, precision)
            + gate * _swiglu(b, shared["w1"], shared["w2"], precision))


def by_row_blocks(fn, x):
    """``fn`` (rows, d) -> (rows, ...), which acts on each row alone, over
    blocks of ``ROW_BLOCK`` rows, each rematerialised."""
    rows = x.shape[0]
    if rows <= ROW_BLOCK or rows % ROW_BLOCK:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape(rows // ROW_BLOCK, ROW_BLOCK, *x.shape[1:]))
    return out.reshape(rows, *out.shape[2:])


def _block(h, blk, linear, sizes_t, precision):
    sizes = dict(sizes_t)
    eps = sizes["norm_eps"]
    mixer = linear_attention if linear else full_attention
    h = h + mixer(_norm(h, blk["ln1_g"], eps), blk, sizes_t, precision)
    return by_row_blocks(
        lambda x: x + feed_forward(_norm(x, blk["ln2_g"], eps), blk, sizes,
                                   precision), h)


def hidden(params, x, sizes_t, precision: str = "f32"):
    """(S,) token ids -> (S, d) after the last layer."""
    sizes = dict(sizes_t)
    h = params["tok"][x]
    block = jax.checkpoint(_block, static_argnums=(2, 3, 4))
    for blk, kind in zip(params["blocks"], sizes["layer_types"]):
        h = block(h, blk, kind == "linear_attention", sizes_t, precision)
    return h


def summed_loss(params, tokens, sizes_t, precision: str = "f32"):
    """(R, S + 1) tokens: the sum over the rows' R x S positions of -log
    p(next token)."""
    sizes = dict(sizes_t)
    total = jnp.float32(0.0)
    for row in tokens:
        x, y = row[:-1], row[1:]
        h = hidden(params, x, sizes_t, precision)

        def own_log_probability(hy):  # rows of [h ; the next token's id]
            h, y = hy[:, :-1], hy[:, -1].astype(jnp.int32)
            z = base._linear(_norm(h, params["ln_f"]["g"], sizes["norm_eps"]),
                             params["head"]["w"], precision)
            logp = jax.nn.log_softmax(z, axis=-1)
            return jnp.take_along_axis(logp, y[:, None], axis=-1)

        # the id rides beside its row (exact in float32: ids are under 2^24)
        hy = jnp.concatenate([h, y[:, None].astype(h.dtype)], axis=-1)
        total = total - by_row_blocks(own_log_probability, hy).sum()
    return total


# ---- training steps -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sizes_t", "precision"))
def _mean_loss_and_gradient(params, tokens, sizes_t, precision):
    """Of the batch's (R, S + 1) tokens, the mean over R x S positions."""
    def mean_loss(p):
        return summed_loss(p, tokens, sizes_t, precision) \
            / (tokens.shape[0] * (tokens.shape[1] - 1))

    return jax.value_and_grad(mean_loss)(params)


def first_steps(seed: int, sizes: dict, batches, learning_rate: float, *,
                config: dict | None = None, mix: dict | None = None,
                precision: str = "f32", keep_rows=None,
                prng: str = "threefry2x32", first_gradient_of_other=None,
                keep_first_gradient: bool = False) -> dict:
    """Drive the reference through ``len(batches)`` Adam steps from the
    seed (``batches`` as ``first_batches`` gives them: (rows, S + 1)
    tokens). Returns each step's loss (before its update), the norm of
    every leaf of the first gradient and the norm of every leaf's change
    over all the steps, and on request the norms of (another run's first
    gradient less this one's), or this run's own on the host. The mean is
    over rows x S, the rows kept where ``keep_rows`` says so (none of a
    batch of one row is kept by its half: the mean is then over nothing,
    NaN). 16 B a parameter: parameters, m, v and the one gradient."""
    sizes_t = tuple(sorted(sizes.items()))
    with jax.default_matmul_precision("highest"):
        params = init_params(seed, sizes, prng)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms, extra = [], None, {}
        for step, tokens in enumerate(batches, start=1):
            if keep_rows is not None:
                tokens = tokens[np.asarray(keep_rows, dtype=np.int64)]
            loss, grads = _mean_loss_and_gradient(
                params, jnp.asarray(tokens), sizes_t, precision)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = base.leaf_norms(grads)
                if first_gradient_of_other is not None:
                    others = first_gradient_of_other
                    extra["grad_differences"] = base.leaf_differences(
                        grads, others() if callable(others) else others)
                    del others
                if keep_first_gradient:
                    extra["first_gradient"] = jax.device_get(
                        jax.tree.leaves(grads))
            flat_p, treedef = jax.tree.flatten(params)
            flat_m, flat_v = jax.tree.leaves(m), jax.tree.leaves(v)
            flat_g = jax.tree.leaves(grads)
            del params, m, v, grads
            out = []
            while flat_p:
                out.append(base._adam_leaf(
                    flat_p.pop(0), flat_m.pop(0), flat_v.pop(0),
                    flat_g.pop(0), jnp.float32(step),
                    jnp.float32(learning_rate)))
            params = jax.tree.unflatten(treedef, [o[0] for o in out])
            m = jax.tree.unflatten(treedef, [o[1] for o in out])
            v = jax.tree.unflatten(treedef, [o[2] for o in out])
            del out
        del m, v
        start = init_params(seed, sizes, prng)
        names = leaf_names(params)
        change = {}
        flat_new, flat_old = jax.tree.leaves(params), jax.tree.leaves(start)
        del params, start
        for name in names:
            change[name] = float(base._norm(flat_new.pop(0) - flat_old.pop(0)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, **extra}


# ---- the counts -----------------------------------------------------------
#
# Operations and bytes a training step needs for one token, from the sizes
# alone: a product of an (m, k) by a (k, n) matrix is 2 m k n operations,
# the backward pass twice the forward, attention is counted over the causal
# half, the linear layers' core by the recurrence whatever chunks compute
# it, and nothing that is recomputed (``--remat``, the flash backward, the
# streamed head) is counted twice.

def scope_flops_per_token(sizes: dict) -> dict:
    """``train_flops_per_token`` by the program's scope
    (``telemetry.SCOPES``). ``attn_proj``: of a full layer q with its gate,
    k, v and the output projection, of a linear layer ``qkvz``, ``ba`` and
    the output projection, 6 operations a parameter. ``attention``: the
    full layers' QK^T and PV over the causal half, S / 2 keys a token: 6 H
    Dh S a layer. ``linear_attention``: the recurrence's decay of S, S^T k,
    the rank-one update and the read-out, 7 dk dv a value head and token,
    three times for forward and backward: 21 Hv dk dv a layer (the conv,
    the gates and the norms are a few operations a channel: not counted).
    ``moe_router``: the (d, E) product. ``moe_experts``: the EXPECTED count
    under uniform routing: a row meets top_k x held / E of the experts held
    here, each 3 d f parameters. ``moe_shared``: the shared expert's three
    matrices and its gate. ``lm_head``: the (d, V) product. ``embed`` is a
    lookup."""
    d, kv, dh, heads = (sizes["d_model"], sizes["kv_heads"],
                        sizes["head_dim"], sizes["heads"])
    nk, dk = sizes["key_heads"], sizes["key_dim"]
    nv, dv = sizes["value_heads"], sizes["value_dim"]
    held_per_row = sizes["top_k"] * sizes["held_experts"] / sizes["router_width"]
    out = dict.fromkeys(("attn_proj", "attention", "linear_attention",
                         "moe_router", "moe_experts", "moe_shared"), 0.0)
    for kind in sizes["layer_types"]:
        if kind == "full_attention":
            out["attn_proj"] += 6.0 * (2 * d * heads * dh + 2 * d * kv * dh
                                       + heads * dh * d)
            out["attention"] += 6.0 * heads * dh * sizes["seq_len"]
        else:
            out["attn_proj"] += 6.0 * (d * (2 * nk * dk + 2 * nv * dv)
                                       + d * 2 * nv + nv * dv * d)
            out["linear_attention"] += 21.0 * nv * dk * dv
        out["moe_router"] += 6.0 * d * sizes["router_width"]
        out["moe_experts"] += 6.0 * 3 * d * sizes["expert_dim"] * held_per_row
        out["moe_shared"] += 6.0 * (3 * d * sizes["shared_dim"] + d)
    out["lm_head"] = 6.0 * d * sizes["vocab_size"]
    out["embed"] = 0.0
    return out


def scope_bytes_per_token(sizes: dict) -> dict:
    """Bytes that cross HBM a token in the scopes whose roofline is read by
    bandwidth. ``linear_attention``: the core's inputs (the q, k, v, z, b
    and a channels, bf16) read and its output (o) written once forward; in
    the backward its inputs and dO read and the inputs' gradients written:
    (3 in + 2 out) x 2 B a linear layer. The state never crosses HBM a
    token, and is not counted."""
    writes = sizes["value_heads"] * sizes["value_dim"]
    reads = (2 * sizes["key_heads"] * sizes["key_dim"] + 2 * writes
             + 2 * sizes["value_heads"])
    linear = sum(kind != "full_attention" for kind in sizes["layer_types"])
    return {"linear_attention": 2.0 * (3 * reads + 2 * writes) * linear}


def train_flops_per_token(sizes: dict) -> float:
    return sum(scope_flops_per_token(sizes).values())


def total_params(sizes: dict) -> int:
    d, kv, dh, heads = (sizes["d_model"], sizes["kv_heads"],
                        sizes["head_dim"], sizes["heads"])
    nk, dk = sizes["key_heads"], sizes["key_dim"]
    nv, dv = sizes["value_heads"], sizes["value_dim"]
    total = 2 * sizes["vocab_size"] * d + d
    for kind in sizes["layer_types"]:
        if kind == "full_attention":
            total += (2 * d * heads * dh + 2 * d * kv * dh + heads * dh * d
                      + 2 * dh)
        else:
            mixed = 2 * nk * dk + nv * dv
            total += (d * (mixed + nv * dv) + d * 2 * nv
                      + sizes["conv"] * mixed + 2 * nv + dv + nv * dv * d)
        total += (2 * d + d * sizes["router_width"]
                  + sizes["held_experts"] * 3 * d * sizes["expert_dim"]
                  + 3 * d * sizes["shared_dim"] + d)
    return total


def adam_bytes_per_step(sizes: dict) -> int:
    """f32 master, gradient, m and v read, master, m and v written."""
    return 7 * 4 * total_params(sizes)


def state_bytes(sizes: dict) -> int:
    """f32 master, m and v resident between steps."""
    return 3 * 4 * total_params(sizes)


def allreduce_bytes_per_step(sizes: dict) -> int:
    """f32 gradients of every parameter."""
    return 4 * total_params(sizes)
