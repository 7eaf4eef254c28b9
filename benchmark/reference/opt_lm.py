"""The family ``opt_lm``: the OPT-shaped causal LM (pre-LN decoder, learned
positions, full multi-head attention, ReLU MLP of 4 x d, untied head), as
``models/transformer.py:TransformerLM`` runs it. A configuration file names
it (``"family": "opt_lm"``) and the harness finds here, by the names of
``harness/manifest.py:FAMILY_NAMES``: the sizes and the trainer's flags the
configuration maps to, the plain reference for the first training steps, and
the operation counts (at the end of the file).

The reference is everything the trainer does between ``--seed`` and the
state after three steps, written out again in ``jax.numpy`` float32 with
nothing taken from the program: the procedural token split, the parameters drawn from the
seed, the batch rows sampled from the step's key, a pre-LN decoder with
dense causal attention and a whole-logits mean cross-entropy, its gradient
and the Adam update. Matrix products run under
``jax.default_matmul_precision("highest")``: on a TPU a float32 product is
otherwise made of bfloat16 passes.

It imports nothing of ``distributed_tensorflow_tpu``. It agrees with the
program only because both follow the same published recipe from the same
seed; the departures of that recipe from the published OPT are listed under
``assumed`` in each configuration file.

``precision`` selects the arithmetic of the linear layers: ``"f32"`` is the
reference; ``"fp8"`` is the control, the step below the configuration's
bfloat16. Where the program rounds to bfloat16 around a linear layer (both
operands and the result), the control rounds to float8 e4m3 with one scale
per tensor; products accumulate in float32, the residual stream, LayerNorm,
attention and the loss stay float32, gradients pass straight through the
rounding. (Rounding the operands alone reads no higher than bfloat16 does:
its error averages out over a 2,048-long dot product, bfloat16's rounding
of every result does not. Measured, PERF.md.) ``keep_rows`` plants the
half-batch fault: the mean is taken over those rows of the batch alone.

The gradient is accumulated over blocks of batch rows and each block of the
model is rematerialised in the backward pass, so that the published widths
fit beside the optimizer state on one chip. Neither changes a value.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# ---- the configuration, as the counts, the reference and the trainer take it

def sizes(config: dict, mix: dict) -> dict:
    """The published ``config.json`` and the mix under the trainer's names."""
    return {"d_model": config["hidden_size"],
            "num_heads": config["num_attention_heads"],
            "num_blocks": config["num_hidden_layers"],
            "ffn_dim": config["ffn_dim"],
            "vocab_size": config["vocab_size"],
            "seq_len": mix["seq_len"]}


def trainer_flags(config: dict, mix: dict) -> dict:
    """The model's own flags of ``mnist_dist.py``."""
    if config["ffn_dim"] != 4 * config["hidden_size"]:
        raise ValueError("TransformerLM's MLP is 4 x d_model wide; "
                         f"ffn_dim {config['ffn_dim']} is not")
    return {"d_model": config["hidden_size"],
            "num_heads": config["num_attention_heads"],
            "num_blocks": config["num_hidden_layers"],
            "vocab_size": config["vocab_size"]}


LM_TRAIN_SEQUENCES = 4096
SAMPLE_SALT = 0x5EED
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LN_EPS = 1e-5
INIT_STDDEV = 0.02
FP8_MAX = 448.0  # largest finite float8_e4m3fn


# ---- data ---------------------------------------------------------------

def token_rows(seed: int, rows, seq_len: int, vocab_size: int,
               n: int = LM_TRAIN_SEQUENCES) -> dict[int, np.ndarray]:
    """The ``rows`` of the procedural training split, each ``seq_len + 1``
    tokens: a walk along a permutation of the vocabulary that is drawn
    afresh for every sequence (the argsort of uniform noise), from a first
    token drawn after all the noise. Only the rows asked for are sorted;
    the noise of the others is drawn and dropped, a block at a time."""
    rows = sorted({int(r) for r in rows})
    rng = np.random.default_rng(seed)
    perms = {}
    block = 256
    for start in range(0, n, block):
        stop = min(n, start + block)
        noise = rng.random((stop - start, vocab_size))
        for r in rows:
            if start <= r < stop:
                perms[r] = np.argsort(noise[r - start])
    first = rng.integers(0, vocab_size, n)
    out = {}
    for r in rows:
        walk = np.empty(seq_len + 1, dtype=np.int64)
        walk[0] = first[r]
        perm = perms[r]
        for t in range(seq_len):
            walk[t + 1] = perm[walk[t]]
        out[r] = walk
    return out


def _key(seed: int, prng: str):
    return jax.random.key(seed, impl=prng)


def sampled_rows(seed: int, steps: int, rows_per_shard: int, shards: int,
                 prng: str = "threefry2x32",
                 n: int = LM_TRAIN_SEQUENCES) -> list[np.ndarray]:
    """Row indices of each of the first ``steps`` batches: the state's key
    is the second half of the seed's split; each step folds the salt (and,
    across chips, the shard's index) into it, draws its rows with
    replacement and moves on to the first half of the key's split."""
    key = jax.random.split(_key(seed, prng))[1]
    out = []
    for _ in range(steps):
        samp = jax.random.fold_in(key, SAMPLE_SALT)
        if shards == 1:
            idx = [jax.random.randint(samp, (rows_per_shard,), 0, n)]
        else:
            idx = [jax.random.randint(jax.random.fold_in(samp, i),
                                      (rows_per_shard,), 0, n)
                   for i in range(shards)]
        out.append(np.concatenate([np.asarray(i) for i in idx]))
        key = jax.random.split(key)[0]
    return out


def first_batches(seed: int, steps: int, sizes: dict, rows_per_shard: int,
                  shards: int,
                  prng: str = "threefry2x32") -> list[np.ndarray]:
    """(rows, seq_len + 1) int32 tokens of each of the first batches."""
    idx = sampled_rows(seed, steps, rows_per_shard, shards, prng)
    table = token_rows(seed, np.concatenate(idx), sizes["seq_len"],
                       sizes["vocab_size"])
    return [np.stack([table[int(r)] for r in step]).astype(np.int32)
            for step in idx]


# ---- parameters -----------------------------------------------------------

def init_params(seed: int, sizes: dict, prng: str = "threefry2x32"):
    """Truncated normal (two sigma) times 0.02 for every matrix, ones and
    zeros for LayerNorm, zero biases. The seed's key splits in two; the
    first half splits into 4 + 8 L keys, taken in the order token table,
    positions, head, then qkv, proj, mlp_in, mlp_out of each block."""
    d, heads, layers = sizes["d_model"], sizes["num_heads"], sizes["num_blocks"]
    ffn, vocab, seq = sizes["ffn_dim"], sizes["vocab_size"], sizes["seq_len"]
    dh = d // heads
    pkey = jax.random.split(_key(seed, prng))[0]
    keys = iter(jax.random.split(pkey, 4 + 8 * layers))

    def w(shape):
        return INIT_STDDEV * jax.random.truncated_normal(
            next(keys), -2.0, 2.0, shape, jnp.float32)

    ones, zeros = (lambda n: jnp.ones((n,), jnp.float32)), \
        (lambda n: jnp.zeros((n,), jnp.float32))
    params = {"tok": w((vocab, d)), "pos": w((seq, d)), "blocks": [],
              "ln_f": {"g": ones(d), "b": zeros(d)},
              "head": {"w": w((d, vocab)), "b": zeros(vocab)}}
    for _ in range(layers):
        params["blocks"].append({
            "ln1_g": ones(d), "ln1_b": zeros(d),
            "qkv": w((d, 3, heads, dh)), "proj": w((heads * dh, d)),
            "ln2_g": ones(d), "ln2_b": zeros(d),
            "mlp_in": {"w": w((d, ffn)), "b": zeros(ffn)},
            "mlp_out": {"w": w((ffn, d)), "b": zeros(d)}})
    return params


# ---- the model ------------------------------------------------------------

def _fake_fp8(x):
    """Round to float8 e4m3 with one scale for the tensor; the gradient
    passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _linear(x, w, precision: str):
    if precision == "fp8":
        return _fake_fp8(jnp.dot(_fake_fp8(x), _fake_fp8(w)))
    if precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.dot(x, w)


def _layernorm(x, g, b):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _block(h, blk, precision: str):
    rows, seq, d = h.shape
    _, _, heads, dh = blk["qkv"].shape
    y = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
    qkv = _linear(y, blk["qkv"].reshape(d, 3 * heads * dh), precision)
    q, k, v = jnp.moveaxis(qkv.reshape(rows, seq, 3, heads, dh), 2, 0)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(rows, seq, heads * dh)
    h = h + _linear(a, blk["proj"], precision)
    y = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
    y = jax.nn.relu(_linear(y, blk["mlp_in"]["w"], precision)
                    + blk["mlp_in"]["b"])
    return h + _linear(y, blk["mlp_out"]["w"], precision) + blk["mlp_out"]["b"]


def logits_fn(params, x, precision: str = "f32"):
    """(rows, S) token ids -> (rows, S, V) float32 logits."""
    h = params["tok"][x] + params["pos"][: x.shape[1]]
    block = jax.checkpoint(_block, static_argnums=(2,))
    for blk in params["blocks"]:
        h = block(h, blk, precision)
    h = _layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"])
    return _linear(h, params["head"]["w"], precision) + params["head"]["b"]


def summed_cross_entropy(params, tokens, precision: str = "f32"):
    """Sum over the rows' positions of -log p(next token)."""
    x, y = tokens[:, :-1], tokens[:, 1:]
    logp = jax.nn.log_softmax(logits_fn(params, x, precision), axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1).sum()


# ---- training steps -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",), donate_argnums=(0,))
def _accumulate(acc, params, tokens, inv_count, precision):
    loss, grads = jax.value_and_grad(summed_cross_entropy)(
        params, tokens, precision)
    acc_g, acc_l = acc
    return (jax.tree.map(lambda a, g: a + g * inv_count, acc_g, grads),
            acc_l + loss * inv_count)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam_leaf(p, m, v, g, t, lr):
    m = ADAM_B1 * m + (1 - ADAM_B1) * g
    v = ADAM_B2 * v + (1 - ADAM_B2) * g * g
    scale = lr * jnp.sqrt(1 - ADAM_B2 ** t) / (1 - ADAM_B1 ** t)
    return p - scale * m / (jnp.sqrt(v) + ADAM_EPS), m, v


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def leaf_names(tree) -> list[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in paths]


def leaf_norms(tree) -> dict[str, float]:
    leaves = jax.tree.leaves(tree)
    return dict(zip(leaf_names(tree), (float(_norm(x)) for x in leaves)))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


def leaf_differences(grads, others) -> dict[str, float]:
    """Norm of (other - reference) for every leaf of the reference's
    gradient; ``others`` are host arrays in the order of the leaves, put on
    the device one at a time."""
    out = {}
    for name, g, other in zip(leaf_names(grads), jax.tree.leaves(grads),
                              others):
        out[name] = float(_diff_norm(jnp.asarray(other, jnp.float32), g))
    return out


def first_steps(seed: int, sizes: dict, batches, learning_rate: float, *,
                config: dict | None = None, mix: dict | None = None,
                precision: str = "f32", keep_rows=None, row_block: int = 1,
                prng: str = "threefry2x32", first_gradient_of_other=None,
                keep_first_gradient: bool = False) -> dict:
    """Drive the reference through ``len(batches)`` Adam steps from the
    seed (``config`` and ``mix`` are handed to every family; this one needs
    no more than ``sizes``). Returns each step's loss (before its update), the norm of every
    leaf of the first gradient and the norm of every leaf's change over all
    the steps. ``first_gradient_of_other`` (host arrays, or a function that
    hands them over) is another run's first gradient: the norm of its
    difference from this one's is returned leaf by leaf.
    ``keep_first_gradient`` returns this run's own on the host."""
    with jax.default_matmul_precision("highest"):
        params = init_params(seed, sizes, prng)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms, extra = [], None, {}
        for t, tokens in enumerate(batches, start=1):
            if keep_rows is not None:
                tokens = tokens[np.asarray(keep_rows)]
            inv = 1.0 / (tokens.shape[0] * (tokens.shape[1] - 1))
            acc = (jax.tree.map(jnp.zeros_like, params), jnp.float32(0.0))
            for start in range(0, tokens.shape[0], row_block):
                acc = _accumulate(acc, params,
                                  jnp.asarray(tokens[start:start + row_block]),
                                  jnp.float32(inv), precision)
            grads, loss = acc
            del acc
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = leaf_norms(grads)
                if first_gradient_of_other is not None:
                    others = first_gradient_of_other
                    extra["grad_differences"] = leaf_differences(
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
                out.append(_adam_leaf(flat_p.pop(0), flat_m.pop(0),
                                      flat_v.pop(0), flat_g.pop(0),
                                      jnp.float32(t),
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
            change[name] = float(_norm(flat_new.pop(0) - flat_old.pop(0)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, **extra}


# ---- the counts -----------------------------------------------------------
#
# Operations and bytes a training step needs, from the sizes. A function of
# the configuration's sizes and the mix's shapes, never of the
# implementation: a product of an (m, k) by a (k, n) matrix is 2 m k n
# operations, the backward pass twice the forward, attention is counted over
# the causal half of the score matrix only, and nothing that is recomputed
# (``--remat``, the flash backward, the streamed head) is counted twice.

def scope_flops_per_token(sizes: dict) -> dict:
    """``train_flops_per_token`` by the program's scope
    (``telemetry.SCOPES``): 6 operations per matmul parameter and token (2
    forward, 4 backward) for q, k, v and the output projection
    (``attn_proj``), the two MLP matrices (``mlp``) and the output head
    (``lm_head``); the token and position tables are looked up, not
    multiplied. ``attention`` is QK^T and PV over the causal half: a token
    attends to S/2 keys on average, 2 products of 2·d operations each,
    times three for forward plus backward: 6·L·d·S."""
    d, ffn, layers = sizes["d_model"], sizes["ffn_dim"], sizes["num_blocks"]
    return {"attn_proj": 6.0 * layers * 4 * d * d,
            "attention": 6.0 * layers * d * sizes["seq_len"],
            "mlp": 6.0 * layers * 2 * d * ffn,
            "lm_head": 6.0 * d * sizes["vocab_size"]}


def train_flops_per_token(sizes: dict) -> float:
    return sum(scope_flops_per_token(sizes).values())


def total_params(sizes: dict) -> int:
    d, ffn, vocab = sizes["d_model"], sizes["ffn_dim"], sizes["vocab_size"]
    per_block = 4 * d * d + 2 * d * ffn + ffn + d + 4 * d
    return (sizes["num_blocks"] * per_block + vocab * d
            + sizes["seq_len"] * d + 2 * d + d * vocab + vocab)


def adam_bytes_per_step(sizes: dict) -> int:
    """f32 master, gradient, m and v read, master, m and v written."""
    return 7 * 4 * total_params(sizes)


def state_bytes(sizes: dict) -> int:
    """f32 master, m and v resident between steps."""
    return 3 * 4 * total_params(sizes)


def allreduce_bytes_per_step(sizes: dict) -> int:
    """f32 gradients of every parameter."""
    return 4 * total_params(sizes)
