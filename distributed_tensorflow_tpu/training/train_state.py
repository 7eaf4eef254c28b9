"""Train state + the single compiled train step.

The reference's per-step work is a client-driven partitioned graph: pull
params from ps over gRPC, forward+backward on the worker, push grads back,
``ApplyGradientDescent`` runs on the ps (``MNISTDist.py:148-149,188``). The
TPU-native equivalent collapses all of that into ONE jitted function over a
resident-on-device state pytree: forward, backward, optimizer update and
global-step increment compile to a single XLA executable; nothing crosses
the host boundary per step but the input batch.

``global_step`` lives inside the state (device-side) exactly like the
reference's shared ``global_step`` Variable (``MNISTDist.py:147``), and the
loop's termination test reads it (``:173``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.ops import nn
from distributed_tensorflow_tpu.utils.profiling import scoped


class TrainState(NamedTuple):
    """Pytree: params + optimizer slots + shared global step + dropout rng
    + non-gradient model state (e.g. batch-norm running statistics)."""

    params: Any
    opt_state: Any
    step: jnp.ndarray  # scalar int32, the reference's global_step Variable
    rng: jnp.ndarray  # PRNG key threaded through dropout
    model_state: Any = ()  # EMA stats etc; () for stateless models


class Optimizer(NamedTuple):
    # update: (grads, opt_state, params, step=None) -> (updates, opt_state).
    # ``step`` is the global step BEFORE this update (TrainState.step);
    # schedule-carrying optimizers evaluate their learning rate on it, so
    # the opt_state layout never depends on whether a schedule is set and
    # checkpoints stay compatible across --lr_schedule toggles. Plain
    # float-lr optimizers ignore it (and tolerate it being omitted).
    init: Callable[[Any], Any]
    update: Callable[..., tuple[Any, Any]]


def _lr_at(learning_rate, step):
    """Resolve a float-or-Schedule learning rate at ``step`` (the global
    step before the update). A schedule with no step is a caller bug —
    fail loudly rather than silently training at the wrong rate."""
    if not callable(learning_rate):
        return learning_rate
    if step is None:
        raise ValueError(
            "scheduled learning rate needs the global step: call "
            "optimizer.update(grads, opt_state, params, step)"
        )
    return learning_rate(step)


def _check_wd(weight_decay) -> float:
    """Weight decay must be non-negative — a negative value would be
    anti-regularization (weights actively grown every step), never what a
    sign typo meant. Callers keep their original update lambdas on the
    zero path: ``0.0*p`` is not foldable under IEEE semantics (0*inf=nan),
    so it would both cost an elementwise pass and NaN-poison a diverged
    leaf."""
    wd = float(weight_decay)
    if wd < 0:
        raise ValueError(f"weight_decay must be >= 0, got {wd}")
    return wd


def sgd(learning_rate, weight_decay: float = 0.0) -> Optimizer:
    """Vanilla SGD — parity with ``GradientDescentOptimizer`` (MNISTDist.py:149).

    ``learning_rate`` is a float (reference behavior) or a
    ``schedules.Schedule`` callable evaluated on the global step; either
    way the opt_state is the empty tuple (the schedule reads
    ``TrainState.step``, which checkpoints already carry).
    ``weight_decay`` adds decoupled decay ``-lr*wd*param`` to the update
    (for plain SGD this coincides with classic L2 regularization)."""
    wd = _check_wd(weight_decay)

    def init(params):
        return ()

    @scoped("optimizer")
    def update(grads, opt_state, params, step=None):
        lr = _lr_at(learning_rate, step)
        if wd:
            updates = jax.tree.map(lambda g, p: -lr * (g + wd * p),
                                   grads, params)
        else:
            updates = jax.tree.map(lambda g: -lr * g, grads)
        return updates, opt_state

    return Optimizer(init, update)


def momentum(learning_rate, beta: float = 0.9,
             weight_decay: float = 0.0) -> Optimizer:
    """SGD with momentum; opt_state is the bare velocity tree regardless
    of whether ``learning_rate`` is a float or a schedule. Weight decay is
    DECOUPLED (applied to the update, not fed through the velocity) so its
    strength doesn't compound with ``beta``."""
    wd = _check_wd(weight_decay)

    def init(params):
        return jax.tree.map(jnp.zeros_like, params)

    @scoped("optimizer")
    def update(grads, vel, params, step=None):
        lr = _lr_at(learning_rate, step)
        vel = jax.tree.map(lambda v, g: beta * v + g, vel, grads)
        if wd:
            updates = jax.tree.map(lambda v, p: -lr * (v + wd * p),
                                   vel, params)
        else:
            updates = jax.tree.map(lambda v: -lr * v, vel)
        return updates, vel

    return Optimizer(init, update)


def adam(learning_rate, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         weight_decay: float = 0.0) -> Optimizer:
    """Adam — not in the reference (SGD only); provided because the
    <60s-to-99% target wants a faster optimizer than SGD@0.001.
    ``learning_rate`` may be a float or a schedule callable (evaluated on
    the global step like the other optimizers; the ``t`` slot stays what
    it always was — the bias-correction count). Nonzero ``weight_decay``
    makes this AdamW: decay decoupled from the moment estimates."""
    wd = _check_wd(weight_decay)

    def init(params):
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)
        return {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.int32)}

    @scoped("optimizer")
    def update(grads, st, params, step=None):
        lr = _lr_at(learning_rate, step)
        t = st["t"] + 1
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, st["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, st["v"], grads)
        tf_ = t.astype(jnp.float32)
        scale = lr * jnp.sqrt(1 - b2**tf_) / (1 - b1**tf_)
        if wd:
            updates = jax.tree.map(
                lambda m_, v_, p: -(scale * m_ / (jnp.sqrt(v_) + eps)
                                    + lr * wd * p),
                m, v, params)
        else:
            updates = jax.tree.map(
                lambda m_, v_: -scale * m_ / (jnp.sqrt(v_) + eps), m, v)
        return updates, {"m": m, "v": v, "t": t}

    return Optimizer(init, update)


_OPTIMIZERS = {"sgd": sgd, "momentum": momentum, "adam": adam}


def get_optimizer(name: str, learning_rate, weight_decay: float = 0.0) -> Optimizer:
    try:
        factory = _OPTIMIZERS[name]
    except KeyError:
        raise ValueError(f"unknown optimizer {name!r}; available: {sorted(_OPTIMIZERS)}") from None
    return factory(learning_rate, weight_decay=weight_decay)


@scoped("optimizer")
def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)


def clip_by_global_norm(max_norm: float, *, axis: str | None = None,
                        sharded_leaf=None):
    """Gradient transform: scale the whole grad pytree so its global L2
    norm is at most ``max_norm`` (the classic tf.clip_by_global_norm).

    Not in the reference (vanilla SGD, MNISTDist.py:149), but the flagship
    CNN's first steps can spike (observed: loss 6 -> 86 in one adam step at
    lr 1e-2, frying the ReLUs into a dead plateau); one clip makes every
    optimizer robust to that. Composes with DP/TP: it runs on the
    already-aggregated grads, and under GSPMD the norm reduction is
    partitioned by XLA like any other reduction.

    ``axis`` makes the clip AXIS-AWARE for ``shard_map`` steps whose grad
    pytree is SPLIT over a mesh axis (pipeline stages, expert shards): the
    transform computes a per-device squared-norm PARTIAL — sharded leaves
    (``sharded_leaf(path)`` True) contribute their full square (each
    device holds a distinct shard, so local squares are exact partials of
    the global sum), replicated leaves contribute ``1/axis_size`` of
    theirs (every device holds the full copy; the psum must count it
    once) — ``psum``s the partials over ``axis``, and only then scales.
    The resulting norm (and therefore the scale) is IDENTICAL on every
    device of the axis, so replicated leaves stay bit-identical — the
    stage-local-norm divergence the plain form had under PP/EP."""
    max_norm = float(max_norm)

    @scoped("optimizer")
    def transform(grads):
        if axis is None:
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(grads))
        else:
            inv = 1.0 / lax.axis_size(axis)

            def partial_sq(path, g):
                s = jnp.sum(jnp.square(g.astype(jnp.float32)))
                if sharded_leaf is not None and sharded_leaf(path):
                    return s  # distinct shard: exact partial
                return s * inv  # replicated: count once across the axis

            parts = jax.tree_util.tree_map_with_path(partial_sq, grads)
            sq = lax.psum(sum(jax.tree.leaves(parts)), axis)
        norm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
        return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)

    return transform


def create_train_state(model, optimizer: Optimizer, seed: int = 0) -> TrainState:
    # old-style raw uint32 keys: a plain array, so the whole TrainState
    # (rng included) serializes through the numpy checkpoint path
    key = jax.random.PRNGKey(seed)
    pkey, dkey = jax.random.split(key)
    variables = model.init(pkey)
    if getattr(model, "stateful", False):
        params, model_state = variables["params"], variables["state"]
    else:
        params, model_state = variables, ()
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
        rng=dkey,
        model_state=model_state,
    )


def loss_and_metrics(model, params, batch, *, keep_prob=1.0, rng=None,
                     train=False, model_state=()):
    """Returns (loss, aux) with aux = {"metrics": ..., "model_state": ...}.

    For stateful models in train mode the forward pass also produces the
    updated state collection (batch-norm EMAs); it rides through grad's
    has_aux channel so the compiled step threads it into the next
    TrainState without a second forward pass."""
    x, y = batch
    if getattr(model, "wants_loss_hook", False):
        # models owning their loss (TransformerLM ce_block: streamed CE
        # so the (B, S, V) logits never materialize; moe_experts: the
        # load-balance aux term) — one hook covers train, eval
        # (make_eval_step) and evaluate()
        loss, metrics = model.loss_with_metrics(
            params, x, y, keep_prob=keep_prob, rng=rng, train=train)
        return loss, {"metrics": metrics, "model_state": model_state}
    if getattr(model, "stateful", False):
        if train:
            logits, new_state = model.apply(
                params, x, keep_prob=keep_prob, rng=rng, train=True,
                state=model_state,
            )
        else:
            logits = model.apply(params, x, keep_prob=keep_prob, rng=rng,
                                 train=False, state=model_state)
            new_state = model_state
    else:
        logits = model.apply(params, x, keep_prob=keep_prob, rng=rng, train=train)
        new_state = model_state
    loss = nn.softmax_cross_entropy(logits, y)
    acc = nn.accuracy(logits, y)
    return loss, {"metrics": {"loss": loss, "accuracy": acc},
                  "model_state": new_state}


def compute_grads(model, params, batch, *, keep_prob, rng, model_state,
                  accum_steps: int = 1):
    """(grads, metrics, new_model_state) for one optimizer update.

    ``accum_steps > 1`` is gradient accumulation: the batch is split into
    that many equal microbatches, ``lax.scan`` runs one backward pass per
    microbatch (so live activation memory is one microbatch's worth — the
    point of accumulation), gradients and metrics are averaged (equal
    microbatch sizes make the mean of means the full-batch mean), and a
    stateful model's state threads sequentially through the microbatches.
    Dropout draws a distinct key per microbatch. Not in the reference
    (single-batch SGD, MNISTDist.py:149,188); standard large-batch
    machinery."""

    def loss_for(p, b, key, ms):
        return loss_and_metrics(model, p, b, keep_prob=keep_prob, rng=key,
                                train=True, model_state=ms)

    if accum_steps <= 1:
        grads, aux = jax.grad(loss_for, has_aux=True)(
            params, batch, rng, model_state)
        return grads, aux["metrics"], aux["model_state"]

    x, y = batch
    n = x.shape[0]
    if n % accum_steps:
        raise ValueError(
            f"batch of {n} examples does not split into "
            f"{accum_steps} equal microbatches"
        )
    micro = n // accum_steps
    xm = x.reshape(accum_steps, micro, *x.shape[1:])
    ym = y.reshape(accum_steps, micro, *y.shape[1:])

    def body(carry, inp):
        g_acc, m_acc, ms = carry
        i, xb, yb = inp
        key = None if rng is None else jax.random.fold_in(rng, i)
        g, aux = jax.grad(loss_for, has_aux=True)(params, (xb, yb), key, ms)
        g_acc = jax.tree.map(jnp.add, g_acc, g)
        m_acc = jax.tree.map(jnp.add, m_acc, aux["metrics"])
        return (g_acc, m_acc, aux["model_state"]), None

    g0 = jax.tree.map(jnp.zeros_like, params)
    # derive the metrics carry from loss_and_metrics itself so this stays
    # in lockstep if it ever gains a key or changes a dtype
    _, aux_shape = jax.eval_shape(loss_for, params, (xm[0], ym[0]), rng,
                                  model_state)
    m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                      aux_shape["metrics"])
    (g_sum, m_sum, model_state), _ = lax.scan(
        body, (g0, m0, model_state),
        (jnp.arange(accum_steps), xm, ym),
    )
    inv = 1.0 / accum_steps
    grads = jax.tree.map(lambda g: g * inv, g_sum)
    metrics = jax.tree.map(lambda m: m * inv, m_sum)
    return grads, metrics, model_state


_AUG_SALT = 0xA06  # folds the augmentation stream away from dropout's


def apply_augment(augment_fn, batch, key_base, shard_index=None):
    """Augment the images of ``batch`` with a key derived by salted fold —
    the existing dropout/sampling key evolution is untouched, so enabling
    augmentation does not perturb any other random stream. ``shard_index``
    (a traced ``lax.axis_index``) decorrelates data shards."""
    if augment_fn is None:
        return batch
    key = jax.random.fold_in(key_base, _AUG_SALT)
    if shard_index is not None:
        key = jax.random.fold_in(key, shard_index)
    x, y = batch
    return augment_fn(x, key), y


def make_train_step(
    model,
    optimizer: Optimizer,
    keep_prob: float = 1.0,
    grad_transform: Callable[[Any], Any] | None = None,
    metrics_transform: Callable[[Any], Any] | None = None,
    donate: bool = True,
    accum_steps: int = 1,
    augment_fn: Callable | None = None,
):
    """Build the compiled train step: (state, batch) -> (state, metrics).

    ``grad_transform`` is the hook where a parallelism mode injects its
    gradient collective (e.g. ``lax.pmean`` over the 'data' mesh axis for
    sync DP) — the step itself is parallelism-agnostic.
    ``metrics_transform`` is the separate hook for aggregating the metrics
    dict across shards (``pmean``); it must NOT be a sum-collective or a
    clipping transform, which would corrupt reported loss/accuracy.
    ``accum_steps`` splits the batch into microbatches and accumulates
    gradients before the single optimizer update (``compute_grads``).
    ``augment_fn`` ((images, rng) -> images, e.g. ``ops.augment``) runs
    inside the compiled step before the forward pass — train only.
    """

    def step_fn(state: TrainState, batch):
        rng, sub = jax.random.split(state.rng)
        batch = apply_augment(augment_fn, batch, state.rng)
        grads, metrics, model_state = compute_grads(
            model, state.params, batch, keep_prob=keep_prob, rng=sub,
            model_state=state.model_state, accum_steps=accum_steps,
        )
        if grad_transform is not None:
            grads = grad_transform(grads)
        if metrics_transform is not None:
            metrics = metrics_transform(metrics)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params, state.step)
        params = apply_updates(state.params, updates)
        return (
            TrainState(params, opt_state, state.step + 1, rng, model_state),
            metrics,
        )

    if donate:
        return jax.jit(step_fn, donate_argnums=(0,))
    return jax.jit(step_fn)


_EVAL_NOISE_SALT = 0xE7A1  # the eval's noise key: fold_in(key(seed), this)


def make_eval_step(model):
    """(params, batch, model_state=()) -> metrics, dropout off — the
    reference's eval run (``MNISTDist.py:181-182``) but usable on the *test*
    set too (the reference never evaluates on test data; the build's
    targets require it). A model whose objective noises its batch
    (``noise_batch``: masked diffusion) is evaluated under one key, made
    here on the host as ``fold_in(PRNGKey(noise_seed), _EVAL_NOISE_SALT)``
    and passed to every call: two evals of one state on one batch agree,
    and the program does not depend on the seed, so a run on a new seed
    loads it from the persistent compile cache. A model without
    ``noise_batch`` gets the program without that argument."""
    noise_fn = getattr(model, "noise_batch", None)

    @jax.jit
    def eval_fn(params, batch, model_state=(), *, noise_key=None):
        if noise_key is not None:
            batch = noise_fn(batch, noise_key)
        _, aux = loss_and_metrics(model, params, batch, train=False,
                                  model_state=model_state)
        return aux["metrics"]

    if noise_fn is None:
        return eval_fn
    # a host array: every process of a multi-host mesh passes the same one
    noise_key = jax.device_get(jax.random.fold_in(
        jax.random.PRNGKey(model.noise_seed), _EVAL_NOISE_SALT))
    return functools.partial(eval_fn, noise_key=noise_key)


def evaluate(model, params, dataset, batch_size: int = 1000, eval_fn=None,
             model_state=()) -> dict[str, float]:
    """Full-split evaluation (weighted over remainder batch).

    The jitted eval fn is cached ON the model instance so repeated
    evaluation (every ``display_step``) reuses the compiled executable
    without a global registry pinning dead models."""
    if eval_fn is None:
        eval_fn = getattr(model, "_cached_eval_fn", None)
        if eval_fn is None:
            eval_fn = make_eval_step(model)
            try:
                model._cached_eval_fn = eval_fn
            except AttributeError:
                pass  # exotic model object without attribute support
    n = dataset.num_examples
    images, labels = dataset.images, dataset.labels
    total = {"loss": 0.0, "accuracy": 0.0}
    seen = 0
    for i in range(0, n, batch_size):
        xs, ys = images[i : i + batch_size], labels[i : i + batch_size]
        m = eval_fn(params, (xs, ys), model_state)
        w = len(xs)
        total = {k: total[k] + float(m[k]) * w for k in total}
        seen += w
    return {k: v / max(seen, 1) for k, v in total.items()}
