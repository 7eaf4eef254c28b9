"""Masked flash attention as two Pallas TPU kernels: the (key tile, query
tile) probability panel lives in VMEM and never crosses HBM.

``ops/attention.py:blockwise_attention`` runs these on a TPU where the
shapes allow (its ``fusable``); its ``lax.scan`` form is the same mathematics
and the reference the tests hold the kernels to. Only q, k, v, o, one f32
logsumexp a row and the three gradients cross HBM.

The mask is a static description (``ops/attention.py:Mask``: causal, the
causal window, or block diffusion over a doubled sequence) that gives the
kernels the mask inside a tile and their SCHEDULE: ``Mask.live_tiles``, a
small int32 table made on the host of the (query tile, key tile) pairs
that run, in the order they fold, each flagged masked or not and first /
last of its row (forward) or column (backward). The table goes in by scalar
prefetch; the grid is (heads * batch, live tiles): the index maps read a
step's tiles from it, the body its flags, and no step runs nothing, whatever
the mask (causal at S = 8,192 with 512-wide tiles: 136 steps a head and
not 256; block diffusion: 80; a window of 512: 31. A step that ran nothing
cost 0.35 us in the forward, PERF.md section 6). Key/value heads may be
fewer than query heads: the k/v index map
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
- **Forward** (``flash_forward``): the table query tile major, a row's key
  tiles ascending. QK^T and P.V run on the MXU with bf16 operands and f32
  accumulation; the running maximum, denominator and numerator are f32
  VMEM scratch, reset at a row's first step and written out (``out``,
  the logsumexp) at its last; the mask is applied only in the tiles
  flagged masked (the diagonal crosses them).
- **Backward** (``flash_backward``): one kernel, the table key tile major,
  a column's query tiles ascending. ``p`` is recomputed from the
  logsumexp; dk and dv accumulate in f32 scratch over a key tile's query
  tiles (reset at the column's first step, written at its last); dq
  accumulates in an f32 scratch that holds the whole sequence of one
  (batch, head) and is written once, so no partial sums go to HBM and
  every matmul of the flash backward runs once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.ops.attention import (
    CAUSAL,
    FIRST,
    LAST,
    MASKED,
)

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


def _fold_live(flags, fold):
    """``fold(masked)`` for the step's tile: every step of the grid is a
    tile that runs, under the mask where only some of its pairs attend
    (the diagonal crosses it), else unmasked."""
    pl.when(flags & MASKED == 0)(lambda: fold(False))
    pl.when(flags & MASKED != 0)(lambda: fold(True))


def _fwd_kernel(tiles_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                acc_sc, *, scale, tq, tk, mask):
    n = pl.program_id(1)
    i, j, flags = tiles_ref[0, n], tiles_ref[1, n], tiles_ref[2, n]

    @pl.when(flags & FIRST != 0)
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

    _fold_live(flags, fold)

    @pl.when(flags & LAST != 0)
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


# jitted: a model's layers share one trace and one lowering of each kernel
@functools.partial(jax.jit, static_argnames=("block_size", "mask"))
def flash_forward(q, k, v, block_size: int, mask=None):
    """(out, lse) of masked attention (``mask``: a ``Mask``, causal if
    None). q: (B, S, H, Dh) bf16, k and v: (B, S, Hkv, Dh); out like q;
    lse (B, H, S) f32, the logsumexp of each row's scores."""
    b, s, h, dh = q.shape
    mask = CAUSAL if mask is None else mask
    group = h // k.shape[2]
    tk = block_size
    tq = query_tile(s, mask)
    tiles = mask.live_tiles(s, tq, tk)

    def q_map(g, n, tiles):
        return g, 0, tiles[0, n]

    def kv_map(g, n, tiles):
        return _kv_head(g, b, group), 0, tiles[1, n]

    row = pltpu.VMEM((1, tq), jnp.float32)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=dh ** -0.5, tq=tq, tk=tk,
                          mask=mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h * b, tiles.shape[1]),
            in_specs=[pl.BlockSpec((None, dh, tq), q_map),
                      pl.BlockSpec((None, dh, tk), kv_map),
                      pl.BlockSpec((None, dh, tk), kv_map)],
            out_specs=[pl.BlockSpec((None, dh, tq), q_map),
                       pl.BlockSpec((None, 1, tq), q_map)],
            scratch_shapes=[row, row, pltpu.VMEM((dh, tq), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((h * b, dh, s), q.dtype),
                   jax.ShapeDtypeStruct((h * b, 1, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="flash_attention_fwd",
    )(tiles, _heads_first(q), _heads_first(k), _heads_first(v))
    return (_heads_last(out, q),
            jnp.einsum("hbs->bhs", lse.reshape(h, b, s)))


def _bwd_kernel(tiles_ref, q_ref, do_ref, lse_ref, d_ref, k_ref, v_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *,
                scale, tq, tk, mask):
    n = pl.program_id(1)
    i, j, flags = tiles_ref[0, n], tiles_ref[1, n], tiles_ref[2, n]

    @pl.when(n == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(flags & FIRST != 0)
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

    _fold_live(flags, fold)

    @pl.when(flags & LAST != 0)
    def _():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        for t in range(dq_sc.shape[0]):
            dq_ref[:, t * tq:(t + 1) * tq] = (dq_sc[t] * scale).astype(
                dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "mask"))
def flash_backward(q, k, v, out, lse, g, block_size: int, mask=None):
    """(dq, dk, dv) of ``flash_forward`` from its operands, its two
    results and the cotangent ``g`` of ``out``; dq like q, dk and dv like
    k and v."""
    b, s, h, dh = q.shape
    mask = CAUSAL if mask is None else mask
    group = h // k.shape[2]
    tk = block_size
    tq = query_tile(s, mask)
    # D_i = sum_d do_i * o_i, f32, one row a (head, batch) like lse
    dd = jnp.einsum("bshd,bshd->hbs", g.astype(jnp.float32),
                    out.astype(jnp.float32))

    tiles = mask.live_tiles(s, tq, tk, key_major=True)

    def q_map(g_, n, tiles):
        return g_, 0, tiles[0, n]

    def kv_map(g_, n, tiles):
        return g_, 0, tiles[1, n]

    def kv_in_map(g_, n, tiles):
        return _kv_head(g_, b, group), 0, tiles[1, n]

    grads = jax.ShapeDtypeStruct((h * b, dh, s), q.dtype)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=dh ** -0.5, tq=tq, tk=tk,
                          mask=mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h * b, tiles.shape[1]),
            in_specs=[pl.BlockSpec((None, dh, tq), q_map),
                      pl.BlockSpec((None, dh, tq), q_map),
                      pl.BlockSpec((None, 1, tq), q_map),
                      pl.BlockSpec((None, 1, tq), q_map),
                      pl.BlockSpec((None, dh, tk), kv_in_map),
                      pl.BlockSpec((None, dh, tk), kv_in_map)],
            out_specs=[pl.BlockSpec((None, dh, s),
                                    lambda g_, n, tiles: (g_, 0, 0)),
                       pl.BlockSpec((None, dh, tk), kv_map),
                       pl.BlockSpec((None, dh, tk), kv_map)],
            scratch_shapes=[pltpu.VMEM((s // tq, dh, tq), jnp.float32),
                            pltpu.VMEM((dh, tk), jnp.float32),
                            pltpu.VMEM((dh, tk), jnp.float32)]),
        out_shape=[grads, grads, grads],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="flash_attention_bwd",
    )(tiles, _heads_first(q), _heads_first(g.astype(q.dtype)),
      jnp.einsum("bhs->hbs", lse).reshape(h * b, 1, s),
      dd.reshape(h * b, 1, s), _heads_first(k), _heads_first(v))
    if group > 1:
        # a key/value head's gradient is the sum over the query heads
        # that read it; each was accumulated in f32 over its query tiles
        dk, dv = (x.reshape(h // group, group, b, dh, s).astype(jnp.float32)
                  .sum(axis=1).astype(q.dtype).reshape(-1, dh, s)
                  for x in (dk, dv))
    return _heads_last(dq, q), _heads_last(dk, k), _heads_last(dv, k)
