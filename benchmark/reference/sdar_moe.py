"""The family ``sdar_moe``: a block-diffusion mixture-of-experts decoder
(``model_type: sdar_moe``, https://huggingface.co/JetLM/SDAR-30B-A3B-Chat),
cut to one chip's share of a stated deployment, as
``models/transformer.py:TransformerLM`` trains it under
``--objective masked_diffusion --moe_top_k``. A configuration file names it
(``"family": "sdar_moe"``) and the harness finds here, by the names of
``harness/manifest.py:FAMILY_NAMES``: the sizes and the trainer's flags the
configuration maps to, the plain reference for the first training steps and
the operation counts (at the end of the file).

The equations (tokens ``x0`` of S ids in [0, V - 1); blocks of ``L_b``
positions, ``beta(i) = i // L_b``):

1. *Noise.* For each sequence and block b, ``t_b ~ U(t_min, 1)``; for each
   position ``u_i ~ U(0, 1)``; ``m_i = [u_i < t_beta(i)]``; ``xt_i = MASK if
   m_i else x0_i`` (MASK = V - 1, which the data never draws).
2. *Input.* ``[xt ; x0]``, 2 S rows with position ids ``[0..S-1 ; 0..S-1]``.
3. *Layer.* ``a = rms(h, g1)``; ``q = a Wq`` (H heads of Dh), ``k = a Wk``,
   ``v = a Wv`` (Hkv heads); ``q = rms(q, gq)``, ``k = rms(k, gk)`` over the
   head width; rotary positions of base theta on q and k (rotate-half);
   query head n reads key/value head ``n // (H / Hkv)``;
   ``P = softmax(q k^T / sqrt(Dh) + M)``; ``h += (P v) Wo``. ``b = rms(h,
   g2)``; ``p = softmax(b Wr)`` over all E experts; ``T = top_k(p)``;
   ``w_e = p_e / sum_{e' in T} p_e'``; ``h += sum_{e in T, e held} w_e
   W2_e (silu(W1g_e b) * (W1u_e b))``. ``rms(x, g) = x / sqrt(mean(x^2) +
   eps) g``. Experts that this chip does not hold add nothing.
4. *Mask M* (n = noised half, c = clean half), query i, key j: n->n iff
   ``beta(i) = beta(j)``; n->c iff ``beta(j) < beta(i)``; c->c iff
   ``beta(j) <= beta(i)``; c->n never. Built densely from the indices.
5. *Loss.* ``z = rms(h_n, g_f) W_head`` on the noised half;
   ``loss = 1/(B S) sum_i m_i / t_beta(i) CE(z_i, x0_i)``: no shift, no
   auxiliary router loss.

Everything the trainer does between ``--seed`` and the state after three
steps is written out again in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, with nothing taken from the
program: no import of ``distributed_tensorflow_tpu``, no kernel, no sort.
Attention is dense, one query head at a time (a (2 S)^2 f32 panel is 268 MB
at S = 4,096); the experts are a loop over the held ones, each run on every
row and selected by a boolean; the loss is taken over whole logits, a
sequence at a time; each block and each head and expert inside it is
rematerialised, so that the published widths fit beside the optimizer
state. None of that changes a value. What the two families share (the
procedural tokens, the sampled rows' key chain, the float8 control's
rounding, Adam, the leaves' names and norms) is ``opt_lm``'s, imported.

``first_batches`` returns, for each step, the tokens AND the noise (the
mask and each position's t), drawn from the seed by the program's own key
chain: the step's sampling key (the state's key folded with the sampling
salt) draws the rows; folded once more with the noise salt it splits in
two, the first half draws t (rows x blocks), the second u (rows x S).

``precision="fp8"`` is the control: every linear layer (q, k, v, output,
both expert matrices, the head) rounds its operands and its result to
float8 e4m3; the router stays float32 as the program's does.
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

NOISE_SALT = 0xD1FF
leaf_names = base.leaf_names


# ---- the configuration, as the counts, the reference and the trainer take it

def sizes(config: dict, mix: dict) -> dict:
    held = config["experts_held"]
    if held["count"] != config["num_experts"]:
        raise ValueError("num_experts is the count of experts held")
    return {"d_model": config["hidden_size"],
            "num_heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "num_blocks": config["num_hidden_layers"],
            "router_width": held["router_width"],
            "held_experts": held["count"],
            "first_expert": held["first"],
            "top_k": config["num_experts_per_tok"],
            "expert_dim": config["moe_intermediate_size"],
            "vocab_size": config["vocab_size"],
            "norm_eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "block_length": config["diffusion"]["block_length"],
            "t_min": config["diffusion"]["t_min"],
            "seq_len": mix["seq_len"]}


def trainer_flags(config: dict, mix: dict) -> dict:
    """The model's own flags of ``mnist_dist.py``, each named by its
    mechanism."""
    if not config["norm_topk_prob"] or config["attention_bias"] \
            or config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("the routed layer renormalises its top-k weights, "
                         "its experts are silu-gated, attention has no "
                         "biases and the head is untied")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("every layer is a mixture of experts")
    s = sizes(config, mix)
    out = {"d_model": s["d_model"], "num_heads": s["num_heads"],
            "num_blocks": s["num_blocks"], "vocab_size": s["vocab_size"],
            "norm": "rmsnorm", "norm_eps": s["norm_eps"],
            "rope_theta": s["rope_theta"], "num_kv_heads": s["kv_heads"],
            "head_dim": s["head_dim"], "qk_norm": True, "mlp_gated": True,
            "biases": False, "moe_experts": s["router_width"],
            "moe_top_k": s["top_k"], "moe_ffn_dim": s["expert_dim"],
            "moe_first_expert": s["first_expert"],
            "moe_held_experts": s["held_experts"],
            "objective": "masked_diffusion",
            "diffusion_block": s["block_length"],
            "diffusion_t_min": s["t_min"]}
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
                f"cannot run a configuration of the family sdar_moe")
    return out


# ---- data: the tokens and the noise ----------------------------------------

def first_batches(seed: int, steps: int, sizes: dict, rows_per_shard: int,
                  shards: int, prng: str = "threefry2x32") -> list[tuple]:
    """For each of the first batches: (tokens (rows, S) int32, masked
    (rows, S) bool, t (rows, S) float32). The ids come from the slice of
    the vocabulary less its last id, the mask's."""
    seq, lb = sizes["seq_len"], sizes["block_length"]
    n = base.LM_TRAIN_SEQUENCES
    key = jax.random.split(base._key(seed, prng))[1]
    rows, noise = [], []
    for _ in range(steps):
        samp = jax.random.fold_in(key, base.SAMPLE_SALT)
        keys = [samp] if shards == 1 else [jax.random.fold_in(samp, i)
                                           for i in range(shards)]
        idx, masked, ts = [], [], []
        for k in keys:
            idx.append(np.asarray(jax.random.randint(k, (rows_per_shard,),
                                                     0, n)))
            k_t, k_u = jax.random.split(jax.random.fold_in(k, NOISE_SALT))
            t = jax.random.uniform(k_t, (rows_per_shard, seq // lb),
                                   jnp.float32, sizes["t_min"], 1.0)
            t = jnp.repeat(t, lb, axis=1)
            u = jax.random.uniform(k_u, (rows_per_shard, seq), jnp.float32)
            masked.append(np.asarray(u < t))
            ts.append(np.asarray(t))
        rows.append(np.concatenate(idx))
        noise.append((np.concatenate(masked), np.concatenate(ts)))
        key = jax.random.split(key)[0]
    # the program's split holds seq_len + 1 tokens a row and feeds the first
    # seq_len (the next-token objective's inputs)
    table = base.token_rows(seed, np.concatenate(rows), seq,
                            sizes["vocab_size"] - 1)
    return [(np.stack([table[int(r)][:seq] for r in step]).astype(np.int32),
             m, t) for step, (m, t) in zip(rows, noise)]


# ---- parameters -----------------------------------------------------------

def init_params(seed: int, sizes: dict, prng: str = "threefry2x32"):
    """Truncated normal (two sigma) times 0.02 for every matrix, ones for
    the gains. The seed's key splits in two; the first half splits into
    4 + 8 L keys, taken in the order token table, head, then q, kv, proj,
    router, w1, w2 of each block."""
    d, heads, kv = sizes["d_model"], sizes["num_heads"], sizes["kv_heads"]
    dh, layers, vocab = sizes["head_dim"], sizes["num_blocks"], sizes["vocab_size"]
    held, f = sizes["held_experts"], sizes["expert_dim"]
    pkey = jax.random.split(base._key(seed, prng))[0]
    keys = iter(jax.random.split(pkey, 4 + 8 * layers))

    def w(shape):
        return base.INIT_STDDEV * jax.random.truncated_normal(
            next(keys), -2.0, 2.0, shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"tok": w((vocab, d)), "blocks": [], "ln_f": {"g": ones(d)},
              "head": {"w": w((d, vocab))}}
    for _ in range(layers):
        params["blocks"].append({
            "ln1_g": ones(d), "q": w((d, heads, dh)), "kv": w((d, 2, kv, dh)),
            "q_norm_g": ones(dh), "k_norm_g": ones(dh),
            "proj": w((heads * dh, d)), "ln2_g": ones(d),
            "moe": {"router": w((d, sizes["router_width"])),
                    "w1": w((held, d, 2 * f)), "w2": w((held, f, d))}})
    return params


# ---- the model, one sequence at a time --------------------------------------

def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (rows, heads, Dh), pos (rows,): the rotate-half form."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def dense_mask(seq: int, lb: int):
    """Equation 4, (2 S, 2 S) bool, from the indices."""
    i = jnp.arange(2 * seq)[:, None]
    j = jnp.arange(2 * seq)[None, :]
    q_noised, k_noised = i < seq, j < seq
    bi, bj = (i % seq) // lb, (j % seq) // lb
    return ((q_noised & k_noised & (bi == bj))
            | (q_noised & ~k_noised & (bj < bi))
            | (~q_noised & ~k_noised & (bj <= bi)))


@jax.checkpoint
def _head_attention(q, k, v, mask):
    """One query head against its key/value head: (rows, Dh) each."""
    scores = jnp.dot(q, k.T) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.dot(probs, v)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _expert(b, w1, w2, weight, e, precision):
    """Expert ``e`` on every row, times the row's weight for it (nought
    where the row did not choose it)."""
    f = w2.shape[1]
    up = base._linear(b, w1[e], precision)
    act = jax.nn.silu(up[:, :f]) * up[:, f:]
    return weight[:, None] * base._linear(act, w2[e], precision)


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


def _block(h, blk, pos, mask, sizes_t, precision):
    sizes = dict(sizes_t)
    rows, d = h.shape
    heads, kv, dh = sizes["num_heads"], sizes["kv_heads"], sizes["head_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    a = _rms(h, blk["ln1_g"], eps)
    q = base._linear(a, blk["q"].reshape(d, heads * dh), precision)
    kvp = base._linear(a, blk["kv"].reshape(d, 2 * kv * dh), precision)
    q = q.reshape(rows, heads, dh)
    k, v = jnp.moveaxis(kvp.reshape(rows, 2, kv, dh), 1, 0)
    q = _rope(_rms(q, blk["q_norm_g"], eps), pos, theta)
    k = _rope(_rms(k, blk["k_norm_g"], eps), pos, theta)
    group = heads // kv
    out = jax.lax.map(
        lambda n: _head_attention(q[:, n], k[:, n // group], v[:, n // group],
                                  mask),
        jnp.arange(heads))                                   # (heads, rows, Dh)
    out = jnp.moveaxis(out, 0, 1).reshape(rows, heads * dh)
    h = h + base._linear(out, blk["proj"], precision)
    b = _rms(h, blk["ln2_g"], eps)
    return h + routed_layer(b, blk["moe"], sizes, precision)


def summed_loss(params, tokens, masked, t, sizes_t, precision: str = "f32"):
    """One sequence: the sum over its masked positions of CE / t."""
    sizes = dict(sizes_t)
    seq = tokens.shape[0]
    x = jnp.concatenate([jnp.where(masked, sizes["vocab_size"] - 1, tokens),
                         tokens])
    pos = jnp.concatenate([jnp.arange(seq), jnp.arange(seq)])
    mask = dense_mask(seq, sizes["block_length"])
    h = params["tok"][x]
    block = jax.checkpoint(_block, static_argnums=(4, 5))
    for blk in params["blocks"]:
        h = block(h, blk, pos, mask, sizes_t, precision)
    z = base._linear(_rms(h[:seq], params["ln_f"]["g"], sizes["norm_eps"]),
                     params["head"]["w"], precision)
    logp = jax.nn.log_softmax(z, axis=-1)
    own = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(masked, own / t, 0.0))


# ---- training steps -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sizes_t", "precision"),
                   donate_argnums=(0,))
def _accumulate(acc, params, tokens, masked, t, inv_count, sizes_t, precision):
    loss, grads = jax.value_and_grad(summed_loss)(
        params, tokens, masked, t, sizes_t, precision)
    acc_g, acc_l = acc
    return (jax.tree.map(lambda a, g: a + g * inv_count, acc_g, grads),
            acc_l + loss * inv_count)


def first_steps(seed: int, sizes: dict, batches, learning_rate: float, *,
                config: dict | None = None, mix: dict | None = None,
                precision: str = "f32", keep_rows=None,
                prng: str = "threefry2x32", first_gradient_of_other=None,
                keep_first_gradient: bool = False) -> dict:
    """Drive the reference through ``len(batches)`` Adam steps from the
    seed (``batches`` as ``first_batches`` gives them). Returns each
    step's loss (before its update), the norm of every leaf of the first
    gradient and the norm of every leaf's change over all the steps, and on
    request the norms of (another run's first gradient less this one's),
    or this run's own on the host. The mean is over rows x S, the rows
    kept where ``keep_rows`` says so."""
    sizes_t = tuple(sorted(sizes.items()))
    with jax.default_matmul_precision("highest"):
        params = init_params(seed, sizes, prng)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms, extra = [], None, {}
        for step, (tokens, masked, t) in enumerate(batches, start=1):
            if keep_rows is not None:
                keep = np.asarray(keep_rows)
                tokens, masked, t = tokens[keep], masked[keep], t[keep]
            inv = jnp.float32(1.0 / (tokens.shape[0] * tokens.shape[1]))
            acc = (jax.tree.map(jnp.zeros_like, params), jnp.float32(0.0))
            for r in range(tokens.shape[0]):
                acc = _accumulate(acc, params, jnp.asarray(tokens[r]),
                                  jnp.asarray(masked[r]), jnp.asarray(t[r]),
                                  inv, sizes_t, precision)
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
# Operations and bytes a training step needs for one DATA token (a step's
# count is batch x S of them; each is two rows of the doubled sequence), from
# the sizes alone: a product of an (m, k) by a (k, n) matrix is 2 m k n
# operations, the backward pass twice the forward, attention is counted
# over the pairs the mask allows, and nothing that is recomputed
# (``--remat``, the flash backward, the streamed head) is counted twice.

def scope_flops_per_token(sizes: dict) -> dict:
    """``train_flops_per_token`` by the program's scope
    (``telemetry.SCOPES``). ``attn_proj``: q, k, v and the output
    projection on both rows of a token, 6 operations a parameter and row.
    ``attention``: QK^T and PV over the allowed pairs, S^2 + S L_b a
    sequence (equation 4), so S + L_b a token and head, 2 products of 2 Dh
    operations, three times for forward plus backward:
    12 L H Dh (S + L_b). ``moe_router``: the (d, E) product on both rows.
    ``moe_experts``: the EXPECTED count, under uniform routing: a row
    meets top_k x held / E of the experts held here (one, in the cell),
    each 3 d f parameters; what a seed's routing really sent is the
    display row's ``moe_rows_per_expert_mean``. ``lm_head``: the (d, V)
    product on the noised row alone. ``embed`` is a lookup."""
    d, layers = sizes["d_model"], sizes["num_blocks"]
    heads, kv, dh = sizes["num_heads"], sizes["kv_heads"], sizes["head_dim"]
    proj = d * heads * dh + 2 * d * kv * dh + heads * dh * d
    held_per_row = sizes["top_k"] * sizes["held_experts"] / sizes["router_width"]
    return {"attn_proj": 12.0 * layers * proj,
            "attention": 12.0 * layers * heads * dh
            * (sizes["seq_len"] + sizes["block_length"]),
            "moe_router": 12.0 * layers * d * sizes["router_width"],
            "moe_experts": 12.0 * layers * 3 * d * sizes["expert_dim"]
            * held_per_row,
            "lm_head": 6.0 * d * sizes["vocab_size"],
            "embed": 0.0}


def train_flops_per_token(sizes: dict) -> float:
    return sum(scope_flops_per_token(sizes).values())


def total_params(sizes: dict) -> int:
    d, heads, kv, dh = (sizes["d_model"], sizes["num_heads"],
                        sizes["kv_heads"], sizes["head_dim"])
    per_block = (d * heads * dh + 2 * d * kv * dh + heads * dh * d
                 + 2 * d + 2 * dh + d * sizes["router_width"]
                 + sizes["held_experts"] * 3 * d * sizes["expert_dim"])
    return (sizes["num_blocks"] * per_block + 2 * sizes["vocab_size"] * d + d)


def adam_bytes_per_step(sizes: dict) -> int:
    """f32 master, gradient, m and v read, master, m and v written."""
    return 7 * 4 * total_params(sizes)


def state_bytes(sizes: dict) -> int:
    """f32 master, m and v resident between steps."""
    return 3 * 4 * total_params(sizes)


def allreduce_bytes_per_step(sizes: dict) -> int:
    """f32 gradients of every parameter."""
    return 4 * total_params(sizes)
