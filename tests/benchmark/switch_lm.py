"""The family ``switch_lm``, for the tests alone: the Switch-routed
``TransformerLM`` (``--moe_experts``), a second architecture that is not
OPT-shaped, brought to a root made by ``tiny.make_root`` as files and
entries only (``tiny.add_switch_family`` copies this file to
``benchmark/reference/switch_lm.py`` there). It is no cell of
``BENCHMARK.json``: a Switch layer at OPT's widths is no public model.

It has other leaves than ``opt_lm`` (``moe/router``, ``moe/w1``, ``b1``,
``w2``, ``b2`` with a leading expert axis), other flags, another count (one
expert's MLP a token, plus the router) and a loss with a second term: the
block's MLP is ``num_experts`` ReLU experts with biases behind a softmax
router, top 1 by ``argmax``, the chosen probability as the gate; an expert
takes ``ceil(capacity_factor · T / E)`` of the batch's T tokens in the order
they arrive and later ones are dropped (their MLP output is nought); the
gradient is taken of the cross-entropy plus ``router_aux_loss_coef`` times
the sum over the blocks of ``E · Σ_e f_e · p_e`` (f_e the share of tokens
routed to e, p_e its mean probability). The trainer reports the
cross-entropy alone as a step's loss, and so do ``losses`` here.

Float32 ``jax.numpy`` at ``highest``; it imports nothing of
``distributed_tensorflow_tpu``. What the two families share (the procedural
tokens, the sampled rows, LayerNorm, the linear layer with its float8
control, Adam, the leaves' names and norms) is ``opt_lm``'s, imported and not
repeated. The whole batch goes through at once, since the capacity is the
batch's; at a test's sizes that fits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import opt_lm as base

first_batches = base.first_batches
leaf_names = base.leaf_names


def sizes(config: dict, mix: dict) -> dict:
    return dict(base.sizes(config, mix),
                num_experts=config["num_experts"],
                capacity_factor=config["capacity_factor"],
                aux_coef=config["router_aux_loss_coef"])


def trainer_flags(config: dict, mix: dict) -> dict:
    return dict(base.trainer_flags(config, mix),
                moe_experts=config["num_experts"],
                moe_capacity=config["capacity_factor"],
                moe_aux=config["router_aux_loss_coef"])


def init_params(seed: int, sizes: dict, prng: str = "threefry2x32"):
    """As ``opt_lm.init_params``; a block's keys go to qkv, proj, router,
    w1, w2 in that order."""
    d, heads, layers = sizes["d_model"], sizes["num_heads"], sizes["num_blocks"]
    ffn, vocab, seq = sizes["ffn_dim"], sizes["vocab_size"], sizes["seq_len"]
    experts, dh = sizes["num_experts"], d // heads
    pkey = jax.random.split(base._key(seed, prng))[0]
    keys = iter(jax.random.split(pkey, 4 + 8 * layers))

    def w(shape):
        return base.INIT_STDDEV * jax.random.truncated_normal(
            next(keys), -2.0, 2.0, shape, jnp.float32)

    ones, zeros = (lambda *s: jnp.ones(s, jnp.float32)), \
        (lambda *s: jnp.zeros(s, jnp.float32))
    params = {"tok": w((vocab, d)), "pos": w((seq, d)), "blocks": [],
              "ln_f": {"g": ones(d), "b": zeros(d)},
              "head": {"w": w((d, vocab)), "b": zeros(vocab)}}
    for _ in range(layers):
        params["blocks"].append({
            "ln1_g": ones(d), "ln1_b": zeros(d),
            "qkv": w((d, 3, heads, dh)), "proj": w((heads * dh, d)),
            "ln2_g": ones(d), "ln2_b": zeros(d),
            "moe": {"router": w((d, experts)),
                    "w1": w((experts, d, ffn)), "b1": zeros(experts, ffn),
                    "w2": w((experts, ffn, d)), "b2": zeros(experts, d)}})
    return params


def _attention_half(h, blk, precision):
    rows, seq, d = h.shape
    _, _, heads, dh = blk["qkv"].shape
    y = base._layernorm(h, blk["ln1_g"], blk["ln1_b"])
    qkv = base._linear(y, blk["qkv"].reshape(d, 3 * heads * dh), precision)
    q, k, v = jnp.moveaxis(qkv.reshape(rows, seq, 3, heads, dh), 2, 0)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(rows, seq, heads * dh)
    return h + base._linear(a, blk["proj"], precision)


def _switch_mlp(h, moe, capacity_factor, precision):
    """(the routed experts' output, E · Σ f_e · p_e) of normed ``h``."""
    rows, seq, d = h.shape
    experts = moe["router"].shape[1]
    tokens = rows * seq
    y = h.reshape(tokens, d)
    probs = jax.nn.softmax(jnp.dot(y, moe["router"]), axis=-1)
    chosen = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    routed = jax.nn.one_hot(chosen, experts, dtype=jnp.int32)
    arrival = jnp.cumsum(routed, axis=0) * routed  # 1-based, in its queue
    capacity = max(1, math.ceil(capacity_factor * tokens / experts))
    kept = (routed * (arrival <= capacity)).astype(jnp.float32)
    out = jnp.zeros_like(y)
    for e in range(experts):  # every expert over every token, then masked
        he = jax.nn.relu(base._linear(y, moe["w1"][e], precision)
                         + moe["b1"][e])
        ye = base._linear(he, moe["w2"][e], precision) + moe["b2"][e]
        out = out + (kept[:, e] * gate)[:, None] * ye
    balance = experts * jnp.sum(routed.astype(jnp.float32).mean(axis=0)
                                * probs.mean(axis=0))
    return out.reshape(rows, seq, d), balance


def loss_fn(params, tokens, capacity_factor, aux_coef, precision):
    """(cross-entropy + aux_coef · balance, cross-entropy): the first is
    differentiated, the second is what the trainer reports."""
    x, y = tokens[:, :-1], tokens[:, 1:]
    h = params["tok"][x] + params["pos"][: x.shape[1]]
    balance = jnp.float32(0.0)
    for blk in params["blocks"]:
        h = _attention_half(h, blk, precision)
        out, b = _switch_mlp(
            base._layernorm(h, blk["ln2_g"], blk["ln2_b"]), blk["moe"],
            capacity_factor, precision)
        h, balance = h + out, balance + b
    h = base._layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"])
    logits = base._linear(h, params["head"]["w"], precision) \
        + params["head"]["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()
    return ce + aux_coef * balance, ce


_value_and_grad = functools.partial(
    jax.jit, static_argnames=("capacity_factor", "aux_coef", "precision"))(
        jax.value_and_grad(loss_fn, has_aux=True))


def first_steps(seed: int, sizes: dict, batches, learning_rate: float, *,
                config: dict | None = None, mix: dict | None = None,
                precision: str = "f32", keep_rows=None,
                prng: str = "threefry2x32", first_gradient_of_other=None,
                keep_first_gradient: bool = False) -> dict:
    """``opt_lm.first_steps`` for this family: the same keywords in, the
    same numbers out."""
    if mix is not None and mix["chips"] != 1:
        raise ValueError("this reference routes the batch as one shard; "
                         "across chips every shard routes its own rows")
    with jax.default_matmul_precision("highest"):
        params = init_params(seed, sizes, prng)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms, extra = [], None, {}
        for t, tokens in enumerate(batches, start=1):
            if keep_rows is not None:
                tokens = tokens[np.asarray(keep_rows)]
            (_, ce), grads = _value_and_grad(
                params, jnp.asarray(tokens), sizes["capacity_factor"],
                sizes["aux_coef"], precision)
            losses.append(float(ce))
            if grad_norms is None:
                grad_norms = base.leaf_norms(grads)
                if first_gradient_of_other is not None:
                    others = first_gradient_of_other
                    extra["grad_differences"] = base.leaf_differences(
                        grads, others() if callable(others) else others)
                if keep_first_gradient:
                    extra["first_gradient"] = jax.device_get(
                        jax.tree.leaves(grads))
            treedef = jax.tree.structure(params)
            out = [base._adam_leaf(p, a, b, g, jnp.float32(t),
                                   jnp.float32(learning_rate))
                   for p, a, b, g in zip(*map(jax.tree.leaves,
                                              (params, m, v, grads)))]
            params, m, v = (jax.tree.unflatten(treedef, [o[i] for o in out])
                            for i in range(3))
        start = init_params(seed, sizes, prng)
        change = base.leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, **extra}


# ---- the counts: as opt_lm's, the MLP being one expert's a token and the
# router's product beside it (both run under the program's ``mlp`` scope)

def scope_flops_per_token(sizes: dict) -> dict:
    d, layers = sizes["d_model"], sizes["num_blocks"]
    return dict(base.scope_flops_per_token(sizes),
                mlp=6.0 * layers * (2 * d * sizes["ffn_dim"]
                                    + d * sizes["num_experts"]))


def train_flops_per_token(sizes: dict) -> float:
    return sum(scope_flops_per_token(sizes).values())


def total_params(sizes: dict) -> int:
    d, ffn, experts = sizes["d_model"], sizes["ffn_dim"], sizes["num_experts"]
    dense_mlp = 2 * d * ffn + ffn + d
    switch_mlp = d * experts + experts * dense_mlp
    return base.total_params(sizes) + sizes["num_blocks"] * (switch_mlp
                                                             - dense_mlp)


def adam_bytes_per_step(sizes: dict) -> int:
    return 7 * 4 * total_params(sizes)


def state_bytes(sizes: dict) -> int:
    return 3 * 4 * total_params(sizes)


def allreduce_bytes_per_step(sizes: dict) -> int:
    return 4 * total_params(sizes)
