"""Masked flash attention as two Pallas TPU kernels: the (key tile, query
tile) probability panel lives in VMEM and never crosses HBM.

``ops/attention.py:blockwise_attention`` runs these on a TPU where the
shapes allow (its ``fusable``); its ``lax.scan`` form is the same mathematics
and the reference the tests hold the kernels to. Only q, k, v, o, one f32
logsumexp a row and the three gradients cross HBM.

The mask is a static description (``ops/attention.py:Mask``: causal, the
causal window, or block diffusion over a doubled sequence) that gives the
kernels the three-way test of a tile (it runs unmasked, masked, or not at
all), the mask inside a tile, the index maps that make a skipped tile move
no bytes, and the grid's inner dimension: every tile of the other side
(causal and block diffusion: the maps skip), or under a window the BAND of
tiles one tile reaches (``window // tile + 1`` or so: at S = 8,192 with
512-wide tiles 2 steps a query tile and not 16, of which 31 of 32 run; a
skipped step costs about 0.35 us, PERF.md section 6, and 225 of them a
head would cost more than the 31 tiles that run). Key/value heads may be fewer than query heads: the k/v index map
sends a group's query heads to its one key/value head, and the backward
writes dk, dv a query head, summed over the group outside the kernel.

- **Layout.** The kernels take (heads * batch, Dh, S): the sequence on
  the 128 lanes, the head width on sublanes, heads outermost. That is the
  layout XLA itself gives the q/k/v projection's result and wants for its
  gradient on a v5e (read off the compiled step: ``{2,4,1,3,0}`` of
  ``[3,B,S,H,Dh]``), so the ``bshd->hbds`` the wrappers ask for costs
  nothing for q, k, v and the three gradients; what remains is one copy of
  ``out`` a layer (PERF.md §6, PR 26). Both kernels work on the
  TRANSPOSED panel (keys on sublanes, queries on lanes): every row
  statistic — running maximum, denominator, the saved logsumexp,
  ``D = sum(do * o)`` — is one row of f32 a query tile that broadcasts
  along sublanes, its reductions run down the sublanes on the VPU, and no
  panel is ever transposed.
- **Forward** (``flash_forward``): grid (heads * batch, query tiles, key
  tiles), the key axis innermost. A query tile visits only the key tiles
  at or under its diagonal (the index map of k and v stops at the last one
  it needs, so a skipped step moves no bytes); QK^T and P.V run on the MXU
  with bf16 operands and f32 accumulation; the running maximum,
  denominator and numerator are f32 VMEM scratch; the mask is applied only
  in tiles the diagonal crosses.
- **Backward** (``flash_backward``): one kernel, grid (heads * batch, key
  tiles, query tiles), the query axis innermost. ``p`` is recomputed from
  the logsumexp; dk and dv accumulate in f32 scratch over a key tile's
  query tiles; dq accumulates in an f32 scratch that holds the whole
  sequence of one (batch, head) and is written once, so no partial sums go
  to HBM and every matmul of the flash backward runs once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# largest query tile taken from the shapes: the chip sweep's choice
# (PERF.md §6, PR 26)
MAX_QUERY_TILE = 512
# a finite stand-in for -inf: exp(MASK - m) is an exact 0 and no
# inf - inf can arise
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
# of a v5e core's 128 MiB; the default scoped limit (16 MiB) is too small
# for 1024 tiles and for dq's whole-sequence scratch at long S
VMEM_LIMIT_BYTES = 96 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def query_tile(seq_len: int, mask=None) -> int:
    """The query tile: the largest multiple of 128 that divides
    ``seq_len`` (each half of it under a block-diffusion mask, whose tiles
    lie within one half) and is at most ``MAX_QUERY_TILE``."""
    if mask is not None and mask.kind == "block_diffusion":
        seq_len = mask.half
    tq = min(seq_len, MAX_QUERY_TILE)
    while tq > LANES and seq_len % tq:
        tq -= LANES
    return tq


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    # DEFAULT, not the process-wide jax_default_matmul_precision: bf16
    # operands take the MXU's one native pass (Mosaic refuses an f32-
    # precision contraction of bf16 vectors), accumulated in f32
    return lax.dot_general(a, b, dims, precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _scores(kt, qt, scale, i, j, tq, tk, masked, mask):
    """(scaled scores (tk, tq) f32, the q tile for the dk product, the
    factor that product still owes). Where 1/sqrt(Dh) is a power of two
    (Dh = 64, 256) it is put on the small q tile, which is exact in bf16
    and gives the same bits as scaling the panel; otherwise the panel is
    scaled in f32."""
    exact = math.frexp(scale)[0] == 0.5
    if exact:
        qt = (qt.astype(jnp.float32) * scale).astype(qt.dtype)
    st = _dot(kt, qt, _TN)
    if not exact:
        st = st * scale
    if masked:
        keys = j * tk + lax.broadcasted_iota(jnp.int32, st.shape, 0)
        queries = i * tq + lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st = jnp.where(mask.allowed(queries, keys), st, MASK)
    return st, qt, (None if exact else scale)


def _when_tile_runs(i, j, tq, tk, mask, fold, inside=None):
    """``fold(masked)`` for tile (query tile i, key tile j): unmasked
    where every pair attends (causal: its last key <= its first query),
    masked where only some do (the diagonal crosses it), not at all where
    none does (its first key > its last query), nor where a banded grid's
    step lies outside the sequence (``inside`` false)."""
    visible, runs = mask.tile(i * tq, (i + 1) * tq - 1,
                              j * tk, (j + 1) * tk - 1)
    if inside is not None:
        visible = jnp.logical_and(visible, inside)
        runs = jnp.logical_and(runs, inside)
    pl.when(visible)(lambda: fold(False))
    pl.when(jnp.logical_and(runs, jnp.logical_not(visible)))(
        lambda: fold(True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, tq, tk, mask, steps):
    i, step = pl.program_id(1), pl.program_id(2)
    # the key tile of this step: the step itself, or its place in the band
    j = mask.key_tile(i, step, steps, tq, tk)
    inside = j >= 0 if mask.banded else None

    @pl.when(step == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def fold(masked):
        vt = v_ref[...]  # (Dh, tk)
        st, _, _ = _scores(k_ref[...], q_ref[...], scale, i, j, tq, tk,
                           masked, mask)
        m_prev = m_sc[...]  # (1, tq)
        m_next = jnp.maximum(m_prev, st.max(axis=0, keepdims=True))
        pt = jnp.exp(st - m_next)
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + pt.sum(axis=0, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + _dot(vt, pt.astype(vt.dtype))
        m_sc[...] = m_next

    _when_tile_runs(i, j, tq, tk, mask, fold, inside)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


def _heads_first(x):
    """(B, S, H, Dh) -> (H * B, Dh, S)."""
    b, s, h, dh = x.shape
    return jnp.einsum("bshd->hbds", x).reshape(h * b, dh, s)


def _heads_last(x, like):
    """(H * B, Dh, S) -> (B, S, H, Dh), the shape of ``like``."""
    b, s, h, dh = like.shape
    return jnp.einsum("hbds->bshd", x.reshape(h, b, dh, s))


def _kv_head(g, batch: int, group: int):
    """The row of the (Hkv * B, Dh, S) key/value operands that query row
    ``g`` of (H * B, Dh, S) reads: head g // B of the queries is head
    (g // B) // group of the keys."""
    if group == 1:
        return g
    return (g // batch) // group * batch + g % batch


def _causal():
    from distributed_tensorflow_tpu.ops.attention import CAUSAL

    return CAUSAL


# jitted: a model's layers share one trace and one lowering of each kernel
@functools.partial(jax.jit, static_argnames=("block_size", "mask"))
def flash_forward(q, k, v, block_size: int, mask=None):
    """(out, lse) of masked attention (``mask``: a ``Mask``, causal if
    None). q: (B, S, H, Dh) bf16, k and v: (B, S, Hkv, Dh); out like q;
    lse (B, H, S) f32, the logsumexp of each row's scores."""
    b, s, h, dh = q.shape
    mask = _causal() if mask is None else mask
    group = h // k.shape[2]
    tk = block_size
    tq = query_tile(s, mask)

    steps = mask.key_steps(s, tq, tk)

    def kv_map(g, i, step):
        # stop at the query tile's last key tile (causal), hold the next
        # tile that runs: a repeated block index is not fetched again, so
        # the skipped steps move nothing
        j = mask.key_tile(i, step, steps, tq, tk)
        return _kv_head(g, b, group), 0, mask.next_key_tile(i, j, tq, tk)

    row = pltpu.VMEM((1, tq), jnp.float32)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=dh ** -0.5, tq=tq, tk=tk,
                          mask=mask, steps=steps),
        grid=(h * b, s // tq, steps),
        in_specs=[pl.BlockSpec((None, dh, tq), lambda g, i, j: (g, 0, i)),
                  pl.BlockSpec((None, dh, tk), kv_map),
                  pl.BlockSpec((None, dh, tk), kv_map)],
        out_specs=[pl.BlockSpec((None, dh, tq), lambda g, i, j: (g, 0, i)),
                   pl.BlockSpec((None, 1, tq), lambda g, i, j: (g, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((h * b, dh, s), q.dtype),
                   jax.ShapeDtypeStruct((h * b, 1, s), jnp.float32)],
        scratch_shapes=[row, row, pltpu.VMEM((dh, tq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="flash_attention_fwd",
    )(_heads_first(q), _heads_first(k), _heads_first(v))
    return (_heads_last(out, q),
            jnp.einsum("hbs->bhs", lse.reshape(h, b, s)))


def _bwd_kernel(q_ref, do_ref, lse_ref, d_ref, k_ref, v_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *,
                scale, tq, tk, mask):
    j, step = pl.program_id(1), pl.program_id(2)
    last_step = pl.num_programs(2) - 1
    # the query tile of this step: the step itself, or its place in the band
    i = mask.query_tile(j, step, tq, tk)
    inside = i < dq_sc.shape[0] if mask.banded else None

    @pl.when(jnp.logical_and(j == 0, step == 0))
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(step == 0)
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def fold(masked):
        dot_, kt, vt = do_ref[...], k_ref[...], v_ref[...]  # (Dh, tile)
        st, qt, owed = _scores(kt, q_ref[...], scale, i, j, tq, tk, masked,
                               mask)
        pt = jnp.exp(st - lse_ref[...])  # lse: (1, tq) down the sublanes
        dv_sc[...] += _dot(dot_, pt.astype(dot_.dtype), _NT)
        dpt = _dot(vt, dot_, _TN)
        dst = (pt * (dpt - d_ref[...])).astype(qt.dtype)
        dk = _dot(qt, dst, _NT)
        dk_sc[...] += dk if owed is None else dk * owed
        dq_sc[i] += _dot(kt, dst)

    _when_tile_runs(i, j, tq, tk, mask, fold, inside)

    @pl.when(step == last_step)
    def _():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(j == pl.num_programs(1) - 1,
                             step == last_step))
    def _():
        for n in range(dq_sc.shape[0]):
            dq_ref[:, n * tq:(n + 1) * tq] = (dq_sc[n] * scale).astype(
                dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "mask"))
def flash_backward(q, k, v, out, lse, g, block_size: int, mask=None):
    """(dq, dk, dv) of ``flash_forward`` from its operands, its two
    results and the cotangent ``g`` of ``out``; dq like q, dk and dv like
    k and v."""
    b, s, h, dh = q.shape
    mask = _causal() if mask is None else mask
    group = h // k.shape[2]
    tk = block_size
    tq = query_tile(s, mask)
    # D_i = sum_d do_i * o_i, f32, one row a (head, batch) like lse
    dd = jnp.einsum("bshd,bshd->hbs", g.astype(jnp.float32),
                    out.astype(jnp.float32))

    def q_map(g_, j, step):
        # start at the key tile's first query tile (see kv_map above)
        i = mask.query_tile(j, step, tq, tk)
        return g_, 0, mask.next_query_tile(j, i, tq, tk, s // tq)

    def kv_map(g_, j, i):
        return g_, 0, j

    def kv_in_map(g_, j, i):
        return _kv_head(g_, b, group), 0, j

    grads = jax.ShapeDtypeStruct((h * b, dh, s), q.dtype)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=dh ** -0.5, tq=tq, tk=tk,
                          mask=mask),
        grid=(h * b, s // tk, mask.query_steps(s, tq, tk)),
        in_specs=[pl.BlockSpec((None, dh, tq), q_map),
                  pl.BlockSpec((None, dh, tq), q_map),
                  pl.BlockSpec((None, 1, tq), q_map),
                  pl.BlockSpec((None, 1, tq), q_map),
                  pl.BlockSpec((None, dh, tk), kv_in_map),
                  pl.BlockSpec((None, dh, tk), kv_in_map)],
        out_specs=[pl.BlockSpec((None, dh, s), lambda g_, j, i: (g_, 0, 0)),
                   pl.BlockSpec((None, dh, tk), kv_map),
                   pl.BlockSpec((None, dh, tk), kv_map)],
        out_shape=[grads, grads, grads],
        scratch_shapes=[pltpu.VMEM((s // tq, dh, tq), jnp.float32),
                        pltpu.VMEM((dh, tk), jnp.float32),
                        pltpu.VMEM((dh, tk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="flash_attention_bwd",
    )(_heads_first(q), _heads_first(g.astype(q.dtype)),
      jnp.einsum("bhs->hbs", lse).reshape(h * b, 1, s),
      dd.reshape(h * b, 1, s), _heads_first(k), _heads_first(v))
    if group > 1:
        # a key/value head's gradient is the sum over the query heads
        # that read it; each was accumulated in f32 over its query tiles
        dk, dv = (x.reshape(h // group, group, b, dh, s).astype(jnp.float32)
                  .sum(axis=1).astype(q.dtype).reshape(-1, dh, s)
                  for x in (dk, dv))
    return _heads_last(dq, q), _heads_last(dk, k), _heads_last(dv, k)
