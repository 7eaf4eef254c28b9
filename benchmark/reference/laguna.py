"""The family ``laguna``: a decoder whose layers differ (``model_type:
laguna``, https://huggingface.co/poolside/Laguna-XS.2): full and
sliding-window attention layers of different head counts in one stack, a
leading dense layer, and mixtures of sigmoid-routed experts beside a shared
one, cut to one chip's share of a stated deployment, as
``models/transformer.py:TransformerLM`` trains it under ``--layer_plan``. A
configuration file names it (``"family": "laguna"``) and the harness finds
here, by the names of ``harness/manifest.py:FAMILY_NAMES``: the sizes and
the trainer's flags the configuration maps to, the plain reference for the
first training steps and the operation counts (at the end of the file).

The equations (S tokens; d the hidden size; Dh the head width; Hkv
key/value heads; layer l has the type tau_l and H_l query heads;
``rms(x, g) = x / sqrt(mean(x^2) + eps) g``):

1. ``h = E[x]`` (no position table).
2. *Attention of layer l.* ``a = rms(h, g1)``; ``q = a Wq`` (H_l heads),
   ``k = a Wk``, ``v = a Wv`` (Hkv heads); ``q = rms(q, gq)``, ``k = rms(k,
   gk)`` over the head width; rotary positions (rotate-half) on the first
   ``r Dh`` of q's and k's width, the rest passed through. Sliding layers:
   r = 1, ``inv_i = theta_s^(-2i/Dh)``. Full layers: r = 0.5 (Dr = r Dh),
   YaRN: ``inv_i = (1 - c_i) theta^(-2i/Dr) / F + c_i theta^(-2i/Dr)`` with
   ``c_i = 1 - clip((i - lo) / (hi - lo), 0, 1)``, ``lo = floor(t(beta_fast))``,
   ``hi = ceil(t(beta_slow))``, ``t(b) = Dr ln(P / (2 pi b)) / (2 ln theta)``
   (P the original positions), cos and sin multiplied by the attention
   factor. Query head n reads key/value head ``n // (H_l / Hkv)``; ``P =
   softmax(q k^T / sqrt(Dh) + M)``, ``M_full``: j <= i, ``M_sliding``:
   i - W < j <= i, built densely from the indices; ``o = P v``; ``o_n =
   sigmoid(a Wg)_n o_n``, one gate a head and row; ``h += o Wo``. No biases.
3. *Feed-forward.* ``b = rms(h, g2)``. A dense layer: ``h += W2 (silu(Wg
   b) * (Wu b))``. A mixture: ``s = sigmoid(b Wr)`` over all E experts;
   ``T = top_k(s)``; ``w_e = c s_e / sum_{e' in T} s_e'`` (c the routed
   scaling factor); ``h += sum_{e in T, e held} w_e F_e(b) + F_shared(b)``,
   every F a SwiGLU. Experts that this chip does not hold add nothing; the
   shared expert is whole on every chip.
4. *Loss.* ``z = rms(h, g_f) W_head``; the mean over the batch's rows and
   positions of the cross-entropy of the next token.

It imports nothing of ``distributed_tensorflow_tpu`` and is written for one
sequence at a time, a head at a time (its query projection, its dense (S, S)
score matrix under the mask, its gate and its rows of the output
projection: no array holds all heads' queries); the held experts are a ``lax.scan``, each
applied to every row and selected by a boolean; the loss is taken over whole
logits; each block and each head and expert inside it is rematerialised, and
what acts on a row alone (the feed-forward, the head and its loss) runs over
blocks of ``ROW_BLOCK`` rows, so that the published widths fit beside the
optimizer state (at 8,192 rows the dense layer's hidden activation is 537 MB
a copy). None of that changes a value. What the families share (the procedural tokens, the sampled
rows' key chain, the float8 control's rounding, Adam, the leaves' names and
norms) is ``opt_lm``'s, imported.

``precision="fp8"`` is the control: every linear layer (q, k, v, the gate,
the output projection, the dense feed-forward, both matrices of every
expert and of the shared one, the head) rounds its operands and its result
to float8 e4m3, one scale a tensor (a head's slice of q, of the gate and
of the output projection; a block of rows where the layer runs over
blocks); the router stays float32 as the program's does.
``keep_rows`` plants the half-batch fault, ``learning_rate=0`` the
unchanged state.
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
KEYS_PER_LAYER = 12  # of the seed's split: a layer draws at most 9 matrices
ROW_BLOCK = 1024     # rows at a time through what acts on a row alone


# ---- the configuration, as the counts, the reference and the trainer take it

def _rope(config: dict, kind: str) -> tuple:
    """(theta, rotated share, YaRN's five numbers or none)."""
    r = config["rope_parameters"][kind]
    yarn = ()
    if r["rope_type"] == "yarn":
        yarn = (float(r["factor"]),
                float(r["original_max_position_embeddings"]),
                float(r["beta_fast"]), float(r["beta_slow"]),
                float(r["attention_factor"]))
    elif r["rope_type"] != "default":
        raise ValueError(f"rotary positions of type {r['rope_type']!r}")
    return (float(r["rope_theta"]), float(r["partial_rotary_factor"]), yarn)


def sizes(config: dict, mix: dict) -> dict:
    held = config["experts_held"]
    if held["count"] != config["num_experts"]:
        raise ValueError("num_experts is the count of experts held")
    layers = config["num_hidden_layers"]
    # the published per-layer lists, of which this cut holds the first
    types = tuple(config["layer_types"][:layers])
    if not set(types) <= {"full_attention", "sliding_attention"}:
        raise ValueError(f"layer types {sorted(set(types))}")
    return {"d_model": config["hidden_size"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "num_blocks": layers,
            "layer_types": types,
            "layer_heads": tuple(
                config["num_attention_heads_per_layer"][:layers]),
            "mlp_types": tuple(config["mlp_layer_types"][:layers]),
            "window": config["sliding_window"],
            "rope_full": _rope(config, "full_attention"),
            "rope_sliding": _rope(config, "sliding_attention"),
            "dense_dim": config["intermediate_size"],
            "router_width": held["router_width"],
            "held_experts": held["count"],
            "first_expert": held["first"],
            "top_k": config["num_experts_per_tok"],
            "expert_dim": config["moe_intermediate_size"],
            "shared_dim": config["shared_expert_intermediate_size"],
            "routed_scale": float(config["moe_routed_scaling_factor"]),
            "vocab_size": config["vocab_size"],
            "norm_eps": config["rms_norm_eps"],
            "seq_len": mix["seq_len"]}


def trainer_flags(config: dict, mix: dict) -> dict:
    """The model's own flags of ``mnist_dist.py``, each named by its
    mechanism."""
    if config["attention_bias"] or config["tie_word_embeddings"] \
            or not config["gating"] \
            or config["moe_apply_router_weight_on_input"]:
        raise ValueError("attention has no biases and a gate on its output, "
                         "the head is untied, the router's weight is on the "
                         "experts' output")
    s = sizes(config, mix)
    if s["dense_dim"] != 4 * s["d_model"]:
        raise ValueError("the trainer's dense feed-forward is 4 x d_model "
                         f"wide; intermediate_size {s['dense_dim']} is not")
    theta_s, share_s, yarn_s = s["rope_sliding"]
    if share_s != 1 or yarn_s:
        raise ValueError("the window layers' rotary positions are plain, on "
                         "the whole head width")
    theta, share, yarn = s["rope_full"]
    plan = ",".join(
        f"{'full' if t == 'full_attention' else 'window'}:{h}:"
        f"{'dense' if m == 'dense' else 'routed'}"
        for t, h, m in zip(s["layer_types"], s["layer_heads"], s["mlp_types"]))
    out = {"d_model": s["d_model"], "num_heads": config["num_attention_heads"],
           "num_blocks": s["num_blocks"], "vocab_size": s["vocab_size"],
           "norm": "rmsnorm", "norm_eps": s["norm_eps"],
           "num_kv_heads": s["kv_heads"], "head_dim": s["head_dim"],
           "qk_norm": True, "mlp_gated": True, "biases": False,
           "layer_plan": plan, "attn_window": s["window"],
           "rope_theta": theta, "rope_fraction": share,
           "rope_yarn": ",".join(repr(x) for x in yarn),
           "window_rope_theta": theta_s, "attn_gate": True,
           "moe_experts": s["router_width"], "moe_top_k": s["top_k"],
           "moe_ffn_dim": s["expert_dim"],
           "moe_first_expert": s["first_expert"],
           "moe_held_experts": s["held_experts"],
           "moe_shared_dim": s["shared_dim"], "moe_scoring": "sigmoid",
           "moe_scale": s["routed_scale"]}
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
                f"cannot run a configuration of the family laguna")
    return out


# ---- parameters -----------------------------------------------------------

def init_params(seed: int, sizes: dict, prng: str = "threefry2x32"):
    """Truncated normal (two sigma) times 0.02 for every matrix, ones for
    the gains. The seed's key splits in two; the first half splits into
    4 + 12 L keys, taken in the order token table, head, then of each
    block q, kv, proj, gate, and mlp_in, mlp_out (a dense layer) or router,
    w1, w2, the shared expert's w1, w2 (a mixture)."""
    d, kv, dh = sizes["d_model"], sizes["kv_heads"], sizes["head_dim"]
    layers, vocab = sizes["num_blocks"], sizes["vocab_size"]
    held, f, fs = (sizes["held_experts"], sizes["expert_dim"],
                   sizes["shared_dim"])
    pkey = jax.random.split(base._key(seed, prng))[0]
    keys = iter(jax.random.split(pkey, 4 + KEYS_PER_LAYER * layers))

    def w(shape):
        return base.INIT_STDDEV * jax.random.truncated_normal(
            next(keys), -2.0, 2.0, shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"tok": w((vocab, d)), "blocks": [], "ln_f": {"g": ones(d)},
              "head": {"w": w((d, vocab))}}
    for heads, mlp in zip(sizes["layer_heads"], sizes["mlp_types"]):
        blk = {"ln1_g": ones(d), "q": w((d, heads, dh)),
               "kv": w((d, 2, kv, dh)), "q_norm_g": ones(dh),
               "k_norm_g": ones(dh), "proj": w((heads * dh, d)),
               "gate": w((d, heads)), "ln2_g": ones(d)}
        if mlp == "dense":
            blk["mlp_in"] = {"w": w((d, 2 * sizes["dense_dim"]))}
            blk["mlp_out"] = {"w": w((sizes["dense_dim"], d))}
        else:
            blk["moe"] = {"router": w((d, sizes["router_width"])),
                          "w1": w((held, d, 2 * f)), "w2": w((held, f, d))}
            blk["shared"] = {"w1": w((d, 2 * fs)), "w2": w((fs, d))}
        params["blocks"].append(blk)
    return params


# ---- the model, one sequence at a time --------------------------------------

def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary_frequencies(dr: int, theta: float, yarn: tuple):
    """(the Dr / 2 inverse frequencies, the factor on cos and sin) of
    equation 2."""
    i = jnp.arange(dr // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * i / dr)
    if not yarn:
        return inv, 1.0
    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def t(beta):
        return dr * math.log(original / (2 * math.pi * beta)) \
            / (2 * math.log(theta))

    lo = max(math.floor(t(beta_fast)), 0)
    hi = min(math.ceil(t(beta_slow)), dr - 1)
    c = 1.0 - jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (1.0 - c) * inv / factor + c * inv, attention_factor


def _rotate(x, pos, form):
    """x (rows, heads, Dh), pos (rows,): rotate-half on the first
    ``share * Dh`` dimensions, the rest passed through."""
    theta, share, yarn = form
    dh = x.shape[-1]
    dr = int(round(share * dh))
    inv, factor = rotary_frequencies(dr, theta, yarn)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = factor * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = factor * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    xr, rest = x[..., :dr], x[..., dr:]
    x1, x2 = xr[..., : dr // 2], xr[..., dr // 2:]
    return jnp.concatenate(
        [xr * cos + jnp.concatenate([-x2, x1], -1) * sin, rest], -1)


def dense_mask(seq: int, window: int):
    """Equation 2's M, (S, S) bool, from the indices; ``window`` 0: the
    full causal mask."""
    i = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    seen = j <= i
    if window:
        seen = seen & (j > i - window)
    return seen


@functools.partial(jax.checkpoint, static_argnums=(8, 9, 10))
def _head(a, wq, wg, wo, gq, k, v, n, form, window, precision, eps):
    """Query head ``n`` of equation 2, from the normalised rows ``a`` to its
    part of ``o Wo``: (rows, d). ``k``, ``v``: its key/value head's (rows,
    Dh), normalised and rotated."""
    rows, dh = a.shape[0], k.shape[-1]
    pos = jnp.arange(rows)
    q = base._linear(a, wq[:, n], precision)                    # (rows, Dh)
    q = _rotate(_rms(q, gq, eps)[:, None], pos, form)[:, 0]
    scores = jnp.dot(q, k.T) / math.sqrt(dh)
    probs = jax.nn.softmax(
        jnp.where(dense_mask(rows, window), scores, -jnp.inf), axis=-1)
    gate = jax.nn.sigmoid(base._linear(a, wg[:, n, None], precision))
    return base._linear(jnp.dot(probs, v) * gate, wo[n], precision)


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
    scores = jax.nn.sigmoid(jnp.dot(b, moe["router"]))
    top_s, top_e = jax.lax.top_k(scores, sizes["top_k"])
    gate = sizes["routed_scale"] * top_s / jnp.sum(top_s, axis=-1,
                                                   keepdims=True)

    def add_expert(y, e):  # a loop: the compiled program holds one expert
        chose = top_e == first + e
        weight = jnp.sum(jnp.where(chose, gate, 0.0), axis=-1)
        return y + _expert(b, moe["w1"], moe["w2"], weight, e, precision), None

    return jax.lax.scan(add_expert, jnp.zeros_like(b), jnp.arange(held))[0]


def by_row_blocks(fn, x):
    """``fn`` (rows, d) -> (rows, ...), which acts on each row alone, over
    blocks of ``ROW_BLOCK`` rows, each rematerialised."""
    rows = x.shape[0]
    if rows <= ROW_BLOCK or rows % ROW_BLOCK:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape(rows // ROW_BLOCK, ROW_BLOCK, *x.shape[1:]))
    return out.reshape(rows, *out.shape[2:])


def feed_forward(b, blk, sizes: dict, precision: str = "f32"):
    """Equation 3 on normalised rows (rows, d)."""
    if "moe" not in blk:
        return _swiglu(b, blk["mlp_in"]["w"], blk["mlp_out"]["w"], precision)
    return (routed_layer(b, blk["moe"], sizes, precision)
            + _swiglu(b, blk["shared"]["w1"], blk["shared"]["w2"], precision))


def _block(h, blk, sliding, sizes_t, precision):
    sizes = dict(sizes_t)
    rows, d = h.shape
    kv, dh, eps = sizes["kv_heads"], sizes["head_dim"], sizes["norm_eps"]
    heads = blk["q"].shape[1]
    form = sizes["rope_sliding"] if sliding else sizes["rope_full"]
    window = sizes["window"] if sliding else 0
    a = _rms(h, blk["ln1_g"], eps)
    kvp = base._linear(a, blk["kv"].reshape(d, 2 * kv * dh), precision)
    k, v = jnp.moveaxis(kvp.reshape(rows, 2, kv, dh), 1, 0)
    k = _rotate(_rms(k, blk["k_norm_g"], eps), jnp.arange(rows), form)
    group = heads // kv
    wo = blk["proj"].reshape(heads, dh, d)

    def add_head(y, n):  # a loop: the compiled program holds one head
        return y + _head(a, blk["q"], blk["gate"], wo, blk["q_norm_g"],
                         k[:, n // group], v[:, n // group], n, form, window,
                         precision, eps), None

    h = jax.lax.scan(add_head, h, jnp.arange(heads))[0]
    return by_row_blocks(
        lambda x: x + feed_forward(_rms(x, blk["ln2_g"], eps), blk, sizes,
                                   precision), h)


def hidden(params, x, sizes_t, precision: str = "f32"):
    """(S,) token ids -> (S, d) after the last block."""
    sizes = dict(sizes_t)
    h = params["tok"][x]
    block = jax.checkpoint(_block, static_argnums=(2, 3, 4))
    for blk, kind in zip(params["blocks"], sizes["layer_types"]):
        h = block(h, blk, kind == "sliding_attention", sizes_t, precision)
    return h


def summed_loss(params, tokens, sizes_t, precision: str = "f32"):
    """One sequence of S + 1 tokens: the sum over its S positions of
    -log p(next token)."""
    sizes = dict(sizes_t)
    x, y = tokens[:-1], tokens[1:]
    h = hidden(params, x, sizes_t, precision)

    def own_log_probability(hy):  # rows of [h ; the next token's id]
        h, y = hy[:, :-1], hy[:, -1].astype(jnp.int32)
        z = base._linear(_rms(h, params["ln_f"]["g"], sizes["norm_eps"]),
                         params["head"]["w"], precision)
        logp = jax.nn.log_softmax(z, axis=-1)
        return jnp.take_along_axis(logp, y[:, None], axis=-1)

    # the id rides beside its row (exact in float32: ids are under 2^24)
    hy = jnp.concatenate([h, y[:, None].astype(h.dtype)], axis=-1)
    return -by_row_blocks(own_log_probability, hy).sum()


# ---- training steps -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sizes_t", "precision"),
                   donate_argnums=(0,))
def _accumulate(acc, params, tokens, inv_count, sizes_t, precision):
    loss, grads = jax.value_and_grad(summed_loss)(
        params, tokens, sizes_t, precision)
    acc_g, acc_l = acc
    return (jax.tree.map(lambda a, g: a + g * inv_count, acc_g, grads),
            acc_l + loss * inv_count)


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
    over rows x S, the rows kept where ``keep_rows`` says so."""
    sizes_t = tuple(sorted(sizes.items()))
    with jax.default_matmul_precision("highest"):
        params = init_params(seed, sizes, prng)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms, extra = [], None, {}
        for step, tokens in enumerate(batches, start=1):
            if keep_rows is not None:
                tokens = tokens[np.asarray(keep_rows)]
            inv = jnp.float32(1.0 / (tokens.shape[0] * (tokens.shape[1] - 1)))
            acc = (jax.tree.map(jnp.zeros_like, params), jnp.float32(0.0))
            for r in range(tokens.shape[0]):
                acc = _accumulate(acc, params, jnp.asarray(tokens[r]), inv,
                                  sizes_t, precision)
            grads, loss = acc
            del acc
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
# the backward pass twice the forward, attention is counted over the pairs
# the layer's mask allows (never over tiles), and nothing that is recomputed
# (``--remat``, the flash backward, the streamed head) is counted twice.

def _layers(sizes: dict):
    return list(zip(sizes["layer_types"], sizes["layer_heads"],
                    sizes["mlp_types"]))


def scope_flops_per_token(sizes: dict) -> dict:
    """``train_flops_per_token`` by the program's scope
    (``telemetry.SCOPES``). ``attn_proj``: q, k, v, the gate and the output
    projection of every layer, 6 operations a parameter. ``attention``: the
    FULL layers' QK^T and PV over the causal half, S / 2 keys a token:
    6 H Dh S a layer. ``attention_window``: the SLIDING layers' over the
    visible pairs, W S - W (W - 1) / 2 a sequence (a query sees min(i + 1,
    W) keys), so W - W (W - 1) / 2 S a token and head, 2 products of 2 Dh
    operations, three times for forward plus backward: 12 H Dh (W - W (W -
    1) / 2 S) a layer. ``mlp``: the dense layers' three matrices.
    ``moe_shared``: the shared expert's three. ``moe_router``: the (d, E)
    product. ``moe_experts``: the EXPECTED count, under uniform routing: a
    row meets top_k x held / E of the experts held here, each 3 d f
    parameters; what a seed's routing really sent is the display row's
    ``moe_rows_per_expert_mean``. ``lm_head``: the (d, V) product.
    ``embed`` is a lookup."""
    d, kv, dh = sizes["d_model"], sizes["kv_heads"], sizes["head_dim"]
    seq, w = sizes["seq_len"], min(sizes["window"], sizes["seq_len"])
    held_per_row = sizes["top_k"] * sizes["held_experts"] / sizes["router_width"]
    out = dict.fromkeys(("attn_proj", "attention", "attention_window", "mlp",
                         "moe_router", "moe_experts", "moe_shared"), 0.0)
    for kind, heads, mlp in _layers(sizes):
        out["attn_proj"] += 6.0 * (2 * d * heads * dh + 2 * d * kv * dh
                                   + d * heads)
        if kind == "sliding_attention":
            out["attention_window"] += 12.0 * heads * dh * (
                w - w * (w - 1) / (2.0 * seq))
        else:
            out["attention"] += 6.0 * heads * dh * seq
        if mlp == "dense":
            out["mlp"] += 6.0 * 3 * d * sizes["dense_dim"]
        else:
            out["moe_router"] += 6.0 * d * sizes["router_width"]
            out["moe_experts"] += 6.0 * 3 * d * sizes["expert_dim"] * held_per_row
            out["moe_shared"] += 6.0 * 3 * d * sizes["shared_dim"]
    out["lm_head"] = 6.0 * d * sizes["vocab_size"]
    out["embed"] = 0.0
    return out


def train_flops_per_token(sizes: dict) -> float:
    return sum(scope_flops_per_token(sizes).values())


def total_params(sizes: dict) -> int:
    d, kv, dh = sizes["d_model"], sizes["kv_heads"], sizes["head_dim"]
    total = 2 * sizes["vocab_size"] * d + d
    for _, heads, mlp in _layers(sizes):
        total += (2 * d * heads * dh + 2 * d * kv * dh + d * heads
                  + 2 * d + 2 * dh)
        if mlp == "dense":
            total += 3 * d * sizes["dense_dim"]
        else:
            total += (d * sizes["router_width"]
                      + sizes["held_experts"] * 3 * d * sizes["expert_dim"]
                      + 3 * d * sizes["shared_dim"])
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
