"""The family ``ouro``: a decoder whose stack of layers is run several times
over the same weights (``model_type: ouro``,
https://huggingface.co/ByteDance/Ouro-2.6B; the looped language model of
arXiv:2510.25741), scored after every pass by the one head and trained on
the passes' losses weighted by a learned exit distribution, as
``models/transformer.py:TransformerLM`` trains it under ``--loop_passes``. A
configuration file names it (``"family": "ouro"``) and the harness finds
here, by the names of ``harness/manifest.py:FAMILY_NAMES``: the sizes and
the trainer's flags the configuration maps to, the plain reference for the
first training steps and the operation counts (at the end of the file).

The equations (S tokens; d the hidden size; H heads of width Dh; L layers; T
passes; ``rms(x, g) = x / sqrt(mean(x^2) + eps) g``). What the published
``config.json`` does not say is marked (A) and listed under ``assumed`` in
the configuration's file.

1. ``h_0 = E[x]`` (no position table).
2. *A layer*, with sandwich norms (A): ``a = h + rms(Attn(rms(h, g1)), g2)``,
   ``h' = a + rms(MLP(rms(a, g3)), g4)``: the second and the fourth gain are
   on the sublayer's OUTPUT, before it is added to the stream.
   ``Attn(u)``: ``q, k, v = u Wq, u Wk, u Wv`` (H heads each, from the one
   matrix ``qkv``); rotary positions (rotate-half, ``inv_i =
   theta^(-2i/Dh)``, the whole head width) on q and k; ``P = softmax(q k^T /
   sqrt(Dh) + M)``, M the causal mask (j <= i) built densely from the
   indices; ``o = P v``; ``Attn = o Wo``. ``MLP(u) = Wd (silu(u Wg) * (u
   Wu))`` (``mlp_in`` holds Wg's columns first, then Wu's). No biases (A).
3. *The loop.* The L layers ``M`` are run T times over the same weights:
   ``h_t = rms(M(h_{t-1}), g_f)`` for t = 1..T: the final norm closes every
   pass, and its output is what the next pass takes in (A).
4. *After every pass*: logits ``z_t = h_t W_head`` (the one head) and an exit
   gate ``lambda_t = sigmoid(h_t . w_g + b_g)``, one linear unit a token on
   the normed output (A).
5. *The exit distribution a token*: ``p(t) = lambda_t prod_{j<t} (1 -
   lambda_j)`` for t < T, ``p(T) = prod_{j<T} (1 - lambda_j)``: it sums to 1,
   and the last pass's own gate is not read.
6. *The loss* (the paper's first-stage objective; beta (A)): the mean over
   the batch's rows and positions of ``sum_t p(t) CE(z_t, y) - beta H(p)``,
   ``H(p) = -sum_t p(t) log p(t)``, y the next token.

It imports nothing of ``distributed_tensorflow_tpu`` and is written for one
sequence at a time, a head at a time (its slice of the one projection, its
dense (S, S) score matrix under the mask, its rows of the output
projection); the (row, pass) pairs of a batch are ONE ``lax.scan`` over a
rematerialised pass whose layers and heads are rematerialised in turn (a
checkpoint a pass around a checkpoint a layer around a checkpoint a head),
and what acts on a row alone (the feed-forward, the head and its loss) runs
over blocks of ``ROW_BLOCK`` rows, so that the published widths fit: 16 B a
parameter (parameters, m, v and the one gradient the scan accumulates) and
the temporaries. The exit distribution is taken from log-sigmoids. None of
that changes a value.
What the families share (the procedural tokens, the sampled rows' key chain,
the float8 control's rounding, Adam, the leaves' names and norms) is
``opt_lm``'s, imported.

``precision="fp8"`` is the control: every linear layer (a head's slice of
q, k and v, its rows of the output projection, both matrices of the
feed-forward, the head) rounds its operands and its result to float8 e4m3,
one scale a tensor (a block of rows where the layer runs over blocks); the
gate's one unit stays float32 as the program's does. ``keep_rows`` plants
the half-batch fault, ``learning_rate=0`` the unchanged state.
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
ROW_BLOCK = 1024  # rows at a time through what acts on a row alone


# ---- the configuration, as the counts, the reference and the trainer take it

def sizes(config: dict, mix: dict) -> dict:
    layers = config["num_hidden_layers"]
    if set(config["layer_types"][:layers]) != {"full_attention"} \
            or config["use_sliding_window"] or config["rope_scaling"]:
        raise ValueError("every layer is full attention under plain rotary "
                         "positions")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("as many key/value heads as query heads")
    return {"d_model": config["hidden_size"],
            "num_heads": config["num_attention_heads"],
            "head_dim": config["head_dim"],
            "num_blocks": layers,
            "ffn_dim": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "norm_eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "passes": config["total_ut_steps"],
            "exit_beta": float(config["trainer"]["loop_exit_beta"]),
            "seq_len": mix["seq_len"]}


def trainer_flags(config: dict, mix: dict) -> dict:
    """The model's own flags of ``mnist_dist.py``, each named by its
    mechanism (``loop_exit_beta`` is the configuration's ``trainer``'s)."""
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("the head is untied and the feed-forward is "
                         "silu-gated")
    if config["early_exit_threshold"] != 1:
        raise ValueError("training runs every pass (early_exit_threshold 1)")
    s = sizes(config, mix)
    out = {"d_model": s["d_model"], "num_heads": s["num_heads"],
           "num_blocks": s["num_blocks"], "vocab_size": s["vocab_size"],
           "norm": "rmsnorm", "norm_eps": s["norm_eps"],
           "head_dim": s["head_dim"], "rope_theta": s["rope_theta"],
           "mlp_gated": True, "mlp_dim": s["ffn_dim"], "biases": False,
           "sandwich_norm": True, "loop_passes": s["passes"]}
    # the trainer's parser passes an unknown flag over in silence: a
    # checkout without these mechanisms would train another model under
    # this configuration's name. ``run.py`` has imported the trainer's
    # entry by now (nothing is imported here); ask it, and fail at once
    trainer = sys.modules.get("mnist_dist")
    if trainer is not None:
        missing = [k for k in (*out, "loop_exit_beta")
                   if not hasattr(trainer.FLAGS, k)]
        if missing:
            raise ValueError(
                f"this checkout's trainer has no flag for {missing}: it "
                f"cannot run a configuration of the family ouro")
    return out


# ---- parameters -----------------------------------------------------------

def init_params(seed: int, sizes: dict, prng: str = "threefry2x32"):
    """Truncated normal (two sigma) times 0.02 for every matrix and the
    gate's weights, ones for the gains, nought for the gate's bias. The
    seed's key splits in two; the first half splits into 4 + 8 L keys, taken
    in the order token table, head, gate, then of each block qkv, proj,
    mlp_in, mlp_out."""
    d, heads, dh = sizes["d_model"], sizes["num_heads"], sizes["head_dim"]
    layers, vocab, ffn = (sizes["num_blocks"], sizes["vocab_size"],
                          sizes["ffn_dim"])
    pkey = jax.random.split(base._key(seed, prng))[0]
    keys = iter(jax.random.split(pkey, 4 + 8 * layers))

    def w(shape):
        return base.INIT_STDDEV * jax.random.truncated_normal(
            next(keys), -2.0, 2.0, shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"tok": w((vocab, d)), "blocks": [], "ln_f": {"g": ones(d)},
              "head": {"w": w((d, vocab))},
              "exit_gate": {"w": w((d, 1)), "b": jnp.zeros((1,), jnp.float32)}}
    for _ in range(layers):
        params["blocks"].append({
            "ln1_g": ones(d), "qkv": w((d, 3, heads, dh)),
            "proj": w((heads * dh, d)), "ln1_post_g": ones(d),
            "ln2_g": ones(d), "mlp_in": {"w": w((d, 2 * ffn))},
            "mlp_out": {"w": w((ffn, d))}, "ln2_post_g": ones(d)})
    return params


# ---- the model, one sequence at a time --------------------------------------

def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate(x, theta):
    """x (rows, Dh) at positions 0..rows-1: rotate-half over the whole
    width."""
    rows, dh = x.shape
    inv = theta ** (-2.0 * jnp.arange(dh // 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(rows, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[:, : dh // 2], x[:, dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.checkpoint, static_argnums=(4, 5))
def _head(u, qkv, wo, n, theta, precision):
    """Head ``n`` of equation 2, from the normalised rows ``u`` to its part
    of ``o Wo``: (rows, d)."""
    rows, dh = u.shape[0], qkv.shape[-1]
    q, k, v = (base._linear(u, qkv[:, i, n], precision) for i in range(3))
    q, k = _rotate(q, theta), _rotate(k, theta)
    scores = jnp.dot(q, k.T) / math.sqrt(dh)
    seen = jnp.arange(rows)[None, :] <= jnp.arange(rows)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return base._linear(jnp.dot(probs, v), wo[n], precision)


def by_row_blocks(fn, x):
    """``fn`` (rows, d) -> (rows, ...), which acts on each row alone, over
    blocks of ``ROW_BLOCK`` rows, each rematerialised."""
    rows = x.shape[0]
    if rows <= ROW_BLOCK or rows % ROW_BLOCK:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape(rows // ROW_BLOCK, ROW_BLOCK, *x.shape[1:]))
    return out.reshape(rows, *out.shape[2:])


def _block(h, blk, sizes_t, precision):
    sizes = dict(sizes_t)
    heads, dh, eps = sizes["num_heads"], sizes["head_dim"], sizes["norm_eps"]
    ffn = sizes["ffn_dim"]
    u = _rms(h, blk["ln1_g"], eps)
    wo = blk["proj"].reshape(heads, dh, -1)

    def add_head(o, n):  # a loop: the compiled program holds one head
        return o + _head(u, blk["qkv"], wo, n, sizes["rope_theta"],
                         precision), None

    attn = jax.lax.scan(add_head, jnp.zeros_like(h), jnp.arange(heads))[0]
    a = h + _rms(attn, blk["ln1_post_g"], eps)

    def feed_forward(x):
        up = base._linear(_rms(x, blk["ln2_g"], eps), blk["mlp_in"]["w"],
                          precision)
        y = base._linear(jax.nn.silu(up[:, :ffn]) * up[:, ffn:],
                         blk["mlp_out"]["w"], precision)
        return x + _rms(y, blk["ln2_post_g"], eps)

    return by_row_blocks(feed_forward, a)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _one_pass(h, blocks, g_f, sizes_t, precision):
    """Equation 3's ``rms(M(h), g_f)``."""
    block = jax.checkpoint(_block, static_argnums=(2, 3))
    for blk in blocks:
        h = block(h, blk, sizes_t, precision)
    return _rms(h, g_f, dict(sizes_t)["norm_eps"])


def pass_outputs(params, x, sizes_t, precision: str = "f32"):
    """(R, S) token ids -> (R, T, S, d): ``h_1 .. h_T`` of every row. ONE
    loop over the R x T (row, pass) pairs, a row's first pass starting from
    its embeddings: its backward pass then holds one accumulator of the
    shared layers' gradients for the whole batch, and not one a row beside
    their sum."""
    passes = dict(sizes_t)["passes"]
    rows, seq = x.shape

    def step(h, i):
        h = jnp.where(i % passes == 0, params["tok"][x[i // passes]], h)
        h = _one_pass(h, params["blocks"], params["ln_f"]["g"], sizes_t,
                      precision)
        return h, h

    start = jnp.zeros((seq, params["tok"].shape[1]), jnp.float32)
    hs = jax.lax.scan(step, start, jnp.arange(rows * passes))[1]
    return hs.reshape(rows, passes, seq, -1)


def exit_log_distribution(gate_logits):
    """Equation 5 in logarithms: (R, T, S) gate logits -> (R, T, S)
    ``log p(t)``."""
    passes = gate_logits.shape[1]
    stayed = jnp.zeros_like(gate_logits[:, 0])  # sum_{j<t} log(1 - lambda_j)
    out = []
    for t in range(passes - 1):
        out.append(jax.nn.log_sigmoid(gate_logits[:, t]) + stayed)
        stayed = stayed + jax.nn.log_sigmoid(-gate_logits[:, t])
    return jnp.stack(out + [stayed], axis=1)


def summed_loss(params, tokens, sizes_t, precision: str = "f32"):
    """(R, S + 1) tokens: the sum over the rows' R x S positions of equation
    6's ``sum_t p(t) CE_t - beta H(p)``."""
    sizes = dict(sizes_t)
    x, y = tokens[:, :-1], tokens[:, 1:]
    hs = pass_outputs(params, x, sizes_t, precision)  # (R, T, S, d)

    def own_cross_entropy(hy):  # rows of [h ; the next token's id]
        h, y = hy[:, :-1], hy[:, -1].astype(jnp.int32)
        logp = jax.nn.log_softmax(
            base._linear(h, params["head"]["w"], precision), axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

    # the id rides beside its row (exact in float32: ids are under 2^24)
    ids = jnp.broadcast_to(y[:, None, :, None], hs.shape[:3] + (1,))
    hy = jnp.concatenate([hs, ids.astype(hs.dtype)], axis=-1)
    ce = by_row_blocks(own_cross_entropy,
                       hy.reshape(-1, hy.shape[-1])).reshape(hs.shape[:3])
    gate = params["exit_gate"]
    log_p = exit_log_distribution(
        jnp.dot(hs, gate["w"][:, 0]) + gate["b"][0])
    p = jnp.exp(log_p)
    return jnp.sum(p * ce) + sizes["exit_beta"] * jnp.sum(p * log_p)


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
# half of the score matrix, and nothing that is recomputed (``--remat``, the
# flash backward, the streamed head) is counted twice. A layer that is run T
# times does T times the work: every count of the layers and of the head
# carries the factor T, and the optimizer's (a parameter is updated once)
# does not.

def scope_flops_per_token(sizes: dict) -> dict:
    """``train_flops_per_token`` by the program's scope
    (``telemetry.SCOPES``), each T times what one walk of the stack costs.
    ``attn_proj``: q, k, v and the output projection, 6 operations a
    parameter. ``attention``: QK^T and PV over the causal half, S / 2 keys a
    token: 6 H Dh S a layer and pass. ``mlp``: the three matrices of the
    gated feed-forward. ``lm_head``: the (d, V) product, once a pass.
    ``loop_exit``: the gate's d weights, once a pass (the exit distribution
    and the weighting are a few operations a token: not counted). ``embed``
    is a lookup."""
    d, heads, dh = sizes["d_model"], sizes["num_heads"], sizes["head_dim"]
    layers, passes = sizes["num_blocks"], sizes["passes"]
    return {"attn_proj": 6.0 * passes * layers * 4 * d * heads * dh,
            "attention": 6.0 * passes * layers * heads * dh * sizes["seq_len"],
            "mlp": 6.0 * passes * layers * 3 * d * sizes["ffn_dim"],
            "lm_head": 6.0 * passes * d * sizes["vocab_size"],
            "loop_exit": 6.0 * passes * d,
            "embed": 0.0}


def train_flops_per_token(sizes: dict) -> float:
    return sum(scope_flops_per_token(sizes).values())


def total_params(sizes: dict) -> int:
    d, heads, dh = sizes["d_model"], sizes["num_heads"], sizes["head_dim"]
    per_layer = 4 * d * heads * dh + 3 * d * sizes["ffn_dim"] + 4 * d
    return (sizes["num_blocks"] * per_layer + 2 * sizes["vocab_size"] * d
            + d + d + 1)


def adam_bytes_per_step(sizes: dict) -> int:
    """f32 master, gradient, m and v read, master, m and v written."""
    return 7 * 4 * total_params(sizes)


def state_bytes(sizes: dict) -> int:
    """f32 master, m and v resident between steps."""
    return 3 * 4 * total_params(sizes)


def allreduce_bytes_per_step(sizes: dict) -> int:
    """f32 gradients of every parameter."""
    return 4 * total_params(sizes)
