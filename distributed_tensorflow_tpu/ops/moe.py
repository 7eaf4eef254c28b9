"""The two mixture-of-experts feed-forwards: ``switch_moe``, the Switch-style
top-1 layer that is the EP compute core (described first, below), and
``routed_experts``, the dropless top-k layer of the routed block
(``models/transformer.py``): the pairs of held experts sorted into a buffer,
two Pallas grouped products over the pairs that arrived, and a dispatch
(gather, f32 add-back, their gradients) that runs over the buffer's live row
tiles alone, in loops whose trip count is the routing's (its own docstring
has the rest).

``switch_moe``. The reference has no MoE (SURVEY.md §2c: expert parallelism
ABSENT); this is the build's fifth parallelism family, designed XLA-first: all
static shapes, routing + dispatch as one-hot EINSUMS (the Switch
Transformer formulation), no gather loops, so the MXU sees three big
batched matmuls per expert group and the compiler fuses the rest.

Routing: per token, softmax over E router logits, top-1 expert, the
chosen probability as the gate. Capacity C = ceil(cf * T / E) tokens
per expert — positions beyond C are DROPPED (the token's MoE output is
zero; its residual stream passes through unchanged), which is what
keeps every shape static. The load-balance auxiliary loss is the
Switch one: E * sum_e(fraction_of_tokens_e * mean_router_prob_e),
minimized at uniform routing; the model adds it to the training loss
scaled by ``moe_aux``.

EXPERT PARALLELISM: pass ``axis_name`` inside ``shard_map`` with the
expert leaves sharded on their leading E axis — every device routes
ALL tokens identically (router params replicated, h replicated over
the axis), slices ITS experts' dispatch columns, computes only those,
and one ``psum`` combines the partial outputs. Gradient accounting
(the trap family sequence_parallel/pipeline_parallel document): the
caller differentiates loss/P per device; the psum transpose then
delivers UNSCALED cotangents, so expert-shard grads are exact partials
(no reduction) and replicated-leaf grads total under one psum over the
axis — parallel/expert_parallel.py owns that derivation; this op just
takes the axis.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax


def moe_capacity(tokens: int, num_experts: int,
                 capacity_factor: float) -> int:
    """Static per-expert token capacity (>=1)."""
    return max(1, math.ceil(capacity_factor * tokens / num_experts))


def switch_moe(h, params, *, capacity_factor: float = 1.25,
               axis_name: str | None = None, compute_dtype=None):
    """(B, S, d) -> ((B, S, d), aux_dict).

    ``params``: {"router": (d, E), "w1": (E, d, m), "b1": (E, m),
    "w2": (E, m, d), "b2": (E, d)} — under ``axis_name`` the expert
    leaves are the LOCAL (E/P, ...) shards. ``aux``: {"lb_loss"
    (scalar, identical on every device), "dropped_frac"}."""
    b, s, d = h.shape
    t = b * s
    hf = h.reshape(t, d)
    cd = compute_dtype
    router = params["router"]
    e_local = params["w1"].shape[0]
    if axis_name is None:
        e_total = e_local
        e_start = 0
    else:
        e_total = e_local * lax.axis_size(axis_name)
        e_start = lax.axis_index(axis_name) * e_local
    cap = moe_capacity(t, e_total, capacity_factor)

    # routing in f32 — identical on every device (replicated inputs)
    logits = jnp.dot(hf.astype(jnp.float32), router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)          # (T, E)
    expert = jnp.argmax(probs, axis=-1)              # (T,)
    gate = jnp.max(probs, axis=-1)                   # (T,)
    # 1-based arrival position of each token in its expert's queue,
    # computed with an INT32 cumsum — an f32 cumsum loses integer
    # exactness past 2^24 tokens/shard and would silently corrupt
    # dispatch slots; tokens past the capacity are dropped (static
    # shapes). The f32 assignment matrix is a cast of the same one_hot.
    assign_i = jax.nn.one_hot(expert, e_total, dtype=jnp.int32)
    assign = assign_i.astype(jnp.float32)
    pos = jnp.cumsum(assign_i, axis=0) * assign_i    # (T, E) int32
    keep = assign * (pos <= cap)
    slot = jax.nn.one_hot(pos - 1, cap,
                          dtype=jnp.float32) * keep[..., None]  # (T,E,C)

    # load balance (Switch): E * sum_e f_e * p_e — from the FULL
    # assignment, so it is identical on every device
    f_e = jnp.mean(assign, axis=0)
    p_e = jnp.mean(probs, axis=0)
    lb_loss = e_total * jnp.sum(f_e * p_e)
    dropped = 1.0 - jnp.sum(keep) / jnp.maximum(jnp.sum(assign), 1.0)

    # this device's experts only
    local = lax.dynamic_slice_in_dim(slot, e_start, e_local, axis=1)
    if cd is not None:
        xe = jnp.einsum("tec,td->ecd", local.astype(cd), hf.astype(cd))
        he = jax.nn.relu(
            jnp.einsum("ecd,edm->ecm", xe, params["w1"].astype(cd))
            + params["b1"].astype(cd)[:, None, :])
        ye = (jnp.einsum("ecm,emd->ecd", he, params["w2"].astype(cd))
              + params["b2"].astype(cd)[:, None, :])
        comb = (local * gate[:, None, None]).astype(cd)
        y = jnp.einsum("tec,ecd->td", comb, ye).astype(h.dtype)
    else:
        xe = jnp.einsum("tec,td->ecd", local, hf)
        he = jax.nn.relu(
            jnp.einsum("ecd,edm->ecm", xe, params["w1"])
            + params["b1"][:, None, :])
        ye = (jnp.einsum("ecm,emd->ecd", he, params["w2"])
              + params["b2"][:, None, :])
        y = jnp.einsum("tec,ecd->td", local * gate[:, None, None], ye)
        y = y.astype(h.dtype)
    if axis_name is not None:
        y = lax.psum(y, axis_name)
    return y.reshape(b, s, d), {"lb_loss": lb_loss,
                                "dropped_frac": dropped}


# ---------------------------------------------------------------------------
# The dropless routed layer: top-k of a softmax, grouped products over the
# experts this chip holds.

# largest tiles (rows, contraction, columns) of the Pallas grouped product,
# from the chip's sweep at 40,960 x 2,048 x 1,536 and x 768 x 2,048 (PERF.md
# §6, PR 29: 1,024 rows a tile 25 % ahead of 512; a 1,536-wide column tile
# does not fit VMEM)
GROUPED_TILING = (1024, 1024, 1024)
ROW_TILE = GROUPED_TILING[0]


# what a product's tiles may take of the kernel's fast memory, by the
# reckoning of ``_tiling``: the compiler's scoped limit is 16 MiB and a
# (1024, 1024, 1024) tiling (16 MiB here) asked 18.4 of it; (1024, 1024, 768)
# and (1024, 768, 1024), 13 and 14 MiB here, fit (described-chip compiles)
GROUPED_VMEM_BYTES = 15 * 1024 * 1024


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Tiles for an (m, k) x (k, n) grouped product: the largest allowed,
    the contraction and column tiles divisors of k and n in whole lanes
    where they have one (a ragged last tile is masked in f32 and costs the
    kernel its VMEM), the column tile narrowed until two buffers of each
    bf16 tile and the f32 accumulator fit the kernel's fast memory."""
    def divisor(size, most):
        tile = min(most, size)
        while tile > 128 and (size % tile or tile % 128):
            tile -= 128
        return tile

    tm, tk = min(GROUPED_TILING[0], m), divisor(k, GROUPED_TILING[1])
    tn = divisor(n, GROUPED_TILING[2])
    while tn > 128 and (4 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
                        > GROUPED_VMEM_BYTES):
        tn = divisor(n, tn - 128)
    return tm, tk, tn


def routed_capacity(tokens: int, top_k: int, held: int, experts: int,
                    capacity_factor: float) -> int:
    """Rows of the sorted buffer: ``capacity_factor`` times the (row,
    expert) pairs that uniform routing sends to the ``held`` of
    ``experts`` experts, rounded up to the grouped product's row tile, and
    never more than every pair there is. The rows are memory, and room
    before an overflow; the sort costs every pair there is, and the grouped
    products and the dispatch (gather, add-back, their gradients) cost the
    row tiles the pairs that arrive reach into, not these rows."""
    expected = tokens * top_k * held / experts
    rows = min(math.ceil(capacity_factor * expected), tokens * min(top_k, held))
    return max(ROW_TILE, -(-rows // ROW_TILE) * ROW_TILE)


# how the router's logits become the scores it ranks and weights by
SCORINGS = ("softmax", "sigmoid")


def _megablox():
    """The module of the Pallas grouped products (the package's ``gmm``
    attribute is the function of that name, which shadows it)."""
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _grouped_pallas(lhs, rhs, sizes, transpose_rhs=False):
    backend = _megablox()

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = _tiling(m, k, n)
    if transpose_rhs:  # the kernel turns the tile too: half the rows fit
        tiling = (min(tiling[0], 512),) + tiling[1:]
    # bf16 operands take the MXU's one native pass whatever the process-wide
    # jax_default_matmul_precision says (Mosaic refuses an f32-precision
    # contraction of bf16 vectors, as in ops/flash_attention.py)
    with jax.default_matmul_precision("bfloat16"):
        return backend.gmm(lhs, rhs, sizes, lhs.dtype, tiling,
                           transpose_rhs=transpose_rhs)


def _grouped_pallas_rhs_grad(lhs, grad, sizes, groups, dtype):
    backend = _megablox()

    m, k = lhs.shape
    n = grad.shape[1]
    with jax.default_matmul_precision("bfloat16"):
        return backend.tgmm(lhs.swapaxes(0, 1), grad, sizes, dtype,
                            _tiling(m, k, n), num_actual_groups=groups)


def _ragged(lhs, rhs, sizes):
    return lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=lhs.dtype)


def _ragged_grads(lhs, rhs, sizes, g):
    return jax.vjp(lambda a, b: _ragged(a, b, sizes), lhs, rhs)[1](g)


def _pallas_grads(lhs, rhs, sizes, g):
    return (_grouped_pallas(g, rhs, sizes, transpose_rhs=True),
            _grouped_pallas_rhs_grad(lhs, g, sizes, rhs.shape[0], rhs.dtype))


def _path_marked(fn, path, pass_name):
    from distributed_tensorflow_tpu.utils.profiling import lowering_instant

    def run(lhs, *rest):
        lhs = lowering_instant("moe_path", lhs, path=path, rows=lhs.shape[0],
                               dtype=lhs.dtype.name, **{"pass": pass_name})
        return fn(lhs, *rest)

    return run


def _pallas_takes(lhs, rhs) -> bool:
    """Whether the Pallas grouped product takes these operands: bf16, a
    row count its row tile divides, lane-aligned widths."""
    return (lhs.dtype == rhs.dtype == jnp.bfloat16
            and lhs.shape[0] % min(ROW_TILE, lhs.shape[0]) == 0
            and lhs.shape[0] % 128 == 0
            and rhs.shape[1] % 128 == 0 and rhs.shape[2] % 128 == 0)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, sizes):
    """(m, k) rows sorted by group times the (g, k, n) matrix of each
    row's group: rows ``sum(sizes[:i]) .. sum(sizes[:i+1])`` meet
    ``rhs[i]``. ``sum(sizes)`` may be less than m. The rows past it belong
    to no group: the gradient with respect to ``rhs[i]`` sums over group
    i's rows alone whatever ``lhs`` and the incoming gradient hold there,
    and what the result and the gradient with respect to ``lhs`` hold there
    is NOT DEFINED: the caller selects it away (``jnp.where``, never a
    product: it may be a NaN), as ``routed_experts`` does. On a TPU, for
    bf16 at aligned shapes, the Pallas grouped product
    (``jax.experimental.pallas.ops.tpu.megablox``) visits only the row tiles
    a group reaches into and leaves the others unwritten, so its cost
    follows ``sizes`` and not m; elsewhere ``lax.ragged_dot``. Which was
    lowered is the ``moe_path`` instant."""
    return _grouped_forward(lhs, rhs, sizes)


def _grouped_forward(lhs, rhs, sizes):
    plain = _path_marked(_ragged, "ragged_dot", "forward")
    if not _pallas_takes(lhs, rhs):
        return plain(lhs, rhs, sizes)
    # both are traced, one is lowered (as ops/attention.py picks its kernels)
    return lax.platform_dependent(
        lhs, rhs, sizes, default=plain,
        tpu=_path_marked(_grouped_pallas, "pallas_gmm", "forward"))


def _grouped_fwd(lhs, rhs, sizes):
    return _grouped_forward(lhs, rhs, sizes), (lhs, rhs, sizes)


def _grouped_bwd(res, g):
    lhs, rhs, sizes = res
    g = g.astype(lhs.dtype)
    plain = _path_marked(_ragged_grads, "ragged_dot", "backward")
    if not _pallas_takes(lhs, rhs):
        dl, dr = plain(lhs, rhs, sizes, g)
    else:
        dl, dr = lax.platform_dependent(
            lhs, rhs, sizes, g, default=plain,
            tpu=_path_marked(_pallas_grads, "pallas_gmm", "backward"))
    import numpy as np

    from jax.dtypes import float0

    return dl, dr, np.zeros(sizes.shape, float0)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def row_tiles_run(sizes, tile: int):
    """Row-tile visits of the forward grouped product over groups of
    ``sizes`` rows at ``tile`` rows a tile: the tiles each non-empty group
    reaches into, summed (a tile two groups share is visited by both), which
    is ``num_active_tiles`` of ``megablox``'s ``make_group_metadata``."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    return jnp.sum(
        jnp.where(sizes > 0, -(-ends // tile) - starts // tile, 0))


def _tile(a, start):
    """``ROW_TILE`` rows of ``a`` from row ``start`` on."""
    return lax.dynamic_slice_in_dim(a, start, ROW_TILE)


def _live_tiles(pairs):
    """The row tiles that ``pairs`` rows from the buffer's start reach into."""
    return -(-pairs // ROW_TILE)


def _over_live_tiles(pairs, carry, tile_fn):
    """``carry = tile_fn(start, live, carry)`` for the row tiles of the
    sorted buffer that its first ``pairs`` rows reach into: ``start`` is a
    tile's first row, ``live`` says which of its rows are among the pairs
    (all but in the last tile). ``pairs`` is traced (the routing's), so this
    is a ``while`` whose trip count is not a constant: a pass costs the
    tiles the pairs reach, and reverse mode cannot go through it (the passes
    below are ``custom_vjp``s whose two halves are each one such loop). The
    carries are updated in place."""
    def body(i, carry):
        start = i * ROW_TILE
        return tile_fn(start, start + jnp.arange(ROW_TILE) < pairs, carry)

    return lax.fori_loop(0, _live_tiles(pairs), body, carry)


def _unwritten(shape, dtype):
    """What a loop's carry holds before a tile is written, and past the last
    live tile for good: nothing reads it. Zeros, at 0.8 ms a 512 MB buffer:
    the scheduler hoists an operand-less ``lax.empty`` of every layer and
    pass to the step's start (+3.35 GB in the routed cell's described-chip
    compile)."""
    return jnp.zeros(shape, dtype)


@jax.custom_vjp
def _gather_live(hf, row, pairs):
    """``hf[row]`` over the row tiles the buffer's first ``pairs`` rows
    reach into (the tiles past them are never written), whose gradient adds
    back those rows' alone: the others go to an index past ``hf``, which
    the scatter-add drops unread (what a grouped product left unwritten
    there is no number to add)."""
    return _gather_live_fwd(hf, row, pairs)[0]


def _gather_live_fwd(hf, row, pairs):
    def tile(start, live, xs):
        return lax.dynamic_update_slice_in_dim(
            xs, hf[_tile(row, start)], start, 0)

    xs = _over_live_tiles(
        pairs, _unwritten((row.shape[0], hf.shape[1]), hf.dtype), tile)
    return xs, (hf, row, pairs)


def _gather_live_bwd(res, g):
    hf, row, pairs = res

    def tile(start, live, acc):
        to = jnp.where(live, _tile(row, start), hf.shape[0])
        return acc.at[to].add(_tile(g, start), mode="drop")

    return _over_live_tiles(pairs, jnp.zeros_like(hf), tile), None, None


_gather_live.defvjp(_gather_live_fwd, _gather_live_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _add_back_live(t, dtype, ys, weight, row, pairs):
    """(t, d) of ``dtype``, summed in f32: row ``row[i]`` gains ``ys[i] *
    weight[i]`` for the buffer's first ``pairs`` rows; the row tiles past
    them are never read, and neither pass writes their part of the gradient
    with respect to ``ys``. A dead row of the last live tile ends in a
    select, after the product: what stands in ``ys`` there may be a NaN."""
    return _add_back_live_fwd(t, dtype, ys, weight, row, pairs)[0]


def _add_back_live_fwd(t, dtype, ys, weight, row, pairs):
    def tile(start, live, y):
        part = (_tile(ys, start).astype(jnp.float32)
                * _tile(weight, start)[:, None])
        return y.at[_tile(row, start)].add(jnp.where(live[:, None], part, 0))

    y = _over_live_tiles(
        pairs, jnp.zeros((t, ys.shape[1]), jnp.float32), tile)
    return y.astype(dtype), (ys, weight, row, pairs)


def _add_back_live_bwd(t, dtype, res, g):
    ys, weight, row, pairs = res

    def tile(start, live, grads):
        d_ys, d_weight = grads
        keep = live[:, None]
        g_rows = g[_tile(row, start)].astype(jnp.float32)
        d_ys = lax.dynamic_update_slice_in_dim(
            d_ys, jnp.where(keep, g_rows * _tile(weight, start)[:, None],
                            0).astype(ys.dtype), start, 0)
        # the weights' gradient goes on to the router: exact row sums
        d_weight = lax.dynamic_update_slice_in_dim(
            d_weight, jnp.sum(jnp.where(
                keep, g_rows * _tile(ys, start).astype(jnp.float32), 0),
                axis=-1), start, 0)
        return d_ys, d_weight

    d_ys, d_weight = _over_live_tiles(
        pairs, (_unwritten(ys.shape, ys.dtype), jnp.zeros_like(weight)), tile)
    return d_ys, d_weight, None, None


_add_back_live.defvjp(_add_back_live_fwd, _add_back_live_bwd)


def routed_experts(h, params, *, top_k: int, first_expert: int = 0,
                   capacity_factor: float = 1.25, compute_dtype=None,
                   scoring: str = "softmax", scale: float = 1.0):
    """(B, S, d) -> ((B, S, d) f32-accumulated in h's dtype, aux): the
    dropless top-k mixture of gated experts, of which this chip holds
    ``params["w1"].shape[0]``, numbered from ``first_expert`` among the
    ``params["router"].shape[1]`` the router chooses between.

    ``params``: ``router`` (d, E), ``w1`` (held, d, 2 f: the gate's
    columns, then the up projection's), ``w2`` (held, f, d). A row's
    weights are its top-k scores renormalised to one and multiplied by
    ``scale``; the scores are the softmax of the router's logits over all
    experts, or (``scoring="sigmoid"``) each logit's own sigmoid; the
    experts not held add nothing (their part is another chip's), and no
    code stands in for them.

    Rows are not dropped: the (row, expert) pairs whose expert is held
    are sorted by expert into a buffer of ``routed_capacity`` rows (static
    shapes), the experts run as two grouped products over it, and the
    results are scatter-added back in f32. The groups end at the last
    pair: the buffer's rows past it (pairs of experts not held, at weight
    nought) go through no expert. What the products leave there, and all
    that is computed from it, ends in a select (``jnp.where``) inside the
    passes that read it (the gate, the weighting) or is dropped by index
    (``_gather_live``), never in a product with a weight of nought: no pass
    over the buffer is added for it. The dispatch stops at the last pair
    too: the gather, the weighting with its add-back, and the gradients of
    both are loops over the buffer's first ``ceil(pairs / ROW_TILE)`` row
    tiles (``_over_live_tiles``: a ``while`` with a traced trip count, its
    carries updated in place), and no pass writes or reads the tiles past
    them. So the buffer's size (``capacity_factor``) is memory, the sort is
    static, and products and dispatch cost the pairs the routing sent: a
    step's time follows the routing. ``aux``: the
    held experts' rows (``rows_per_expert_max`` / ``_mean``),
    ``buffer_fill`` (pairs over the buffer's rows), ``tiles_run_frac``
    (``row_tiles_run`` of the forward product over the buffer's row tiles:
    the share of the buffer the products compute, a tile that two groups
    share counted twice), ``dispatch_tiles_frac`` (the live row tiles over
    the buffer's: the share the dispatch's loops run, 1 when the early stop
    saved nothing), ``unrouted_frac``, and ``overflow_rows``, the
    pairs the buffer could not take: the caller fails the step when it is
    not 0 (``TransformerLM`` makes the loss NaN), never a silent drop."""
    from distributed_tensorflow_tpu.utils.profiling import scope

    b, s, d = h.shape
    t = b * s
    hf = h.reshape(t, d)
    cd = compute_dtype
    e_total = params["router"].shape[1]
    held, _, two_f = params["w1"].shape
    f = two_f // 2
    if not 0 <= first_expert <= e_total - held:
        raise ValueError(f"experts {first_expert}..{first_expert + held - 1} "
                         f"are not among the router's {e_total}")
    if scoring not in SCORINGS:
        raise ValueError(f"the router scores by one of {SCORINGS}, not by "
                         f"{scoring!r}")
    cap = routed_capacity(t, top_k, held, e_total, capacity_factor)

    with scope("moe_router"):
        # f32 operands and products: the choice of experts is a
        # discontinuous function of these logits
        logits = jnp.dot(hf.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        probs = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))
        top_p, top_e = lax.top_k(probs, top_k)                  # (T, k)
        gate = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        if scale != 1.0:
            gate = gate * scale
        local = top_e - first_expert
        is_held = jnp.logical_and(local >= 0, local < held)
        # the pairs of held experts first, by expert, in row order
        key = jnp.where(is_held, local, held).reshape(t * top_k)
        order = jnp.argsort(key, stable=True)
        # (a buffer with more room than there are pairs: the rest is
        # never valid)
        order = jnp.pad(order, (0, max(0, cap - t * top_k)))[:cap]
        counts = jnp.sum(
            jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)  # (held,)
        total = jnp.sum(counts)
        # the last group ends at the last pair (at the buffer's end when
        # it overflows): the products leave the rows past it alone
        ends = jnp.minimum(jnp.cumsum(counts), cap)
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        live = jnp.arange(cap) < ends[-1]
        weight = jnp.where(live, gate.reshape(t * top_k)[order], 0.0)
        row = order // top_k
        # the passes over the buffer (this gather, the add-back, their
        # gradients) stop at the last tile the pairs reach into
        xs = _gather_live(hf if cd is None else hf.astype(cd), row,
                          ends[-1])                             # (cap, d)

    with scope("moe_experts"):
        w1, w2 = params["w1"], params["w2"]
        if cd is not None:
            w1, w2 = w1.astype(cd), w2.astype(cd)
        # what a product left past the last pair (it may be a NaN) is
        # selected away inside the pass that reads it: after the gate's
        # slices and after the weighting, where XLA fuses the select and
        # its transpose into the passes there were (a select on the whole
        # of ``up``, or on ``ys`` before its weight, is a pass of its own)
        keep = live[:, None]
        up = grouped_matmul(xs, w1, sizes)                      # (cap, 2 f)
        act = (jax.nn.silu(jnp.where(keep, up[:, :f], 0))
               * jnp.where(keep, up[:, f:], 0))
        ys = grouped_matmul(act, w2, sizes)                     # (cap, d)

    with scope("moe_router"):
        y = _add_back_live(t, h.dtype, ys, weight, row, ends[-1])
        aux = {
            "rows_per_expert_max": jnp.max(counts).astype(jnp.float32),
            "rows_per_expert_mean": jnp.mean(counts.astype(jnp.float32)),
            "buffer_fill": total.astype(jnp.float32) / cap,
            "tiles_run_frac": row_tiles_run(sizes, ROW_TILE).astype(
                jnp.float32) / (cap // ROW_TILE),
            "dispatch_tiles_frac": (_live_tiles(ends[-1]) * ROW_TILE).astype(
                jnp.float32) / cap,
            "overflow_rows": (total - ends[-1]).astype(jnp.float32),
            "unrouted_frac": 1.0 - jnp.mean(
                jnp.any(is_held, axis=-1).astype(jnp.float32)),
        }
    return y.reshape(b, s, d), aux
