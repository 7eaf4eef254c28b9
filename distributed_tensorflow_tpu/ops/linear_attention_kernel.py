"""The chunked gated delta rule's forward as one Pallas TPU kernel: every
chunk's C x C work, its ``(I + A)^-1`` and the state carried from chunk to
chunk live in VMEM, and only q, k, v, the gates and o cross HBM.

``ops/linear_attention.py:gated_delta_rule`` runs this on a TPU where the
widths allow (its ``kernel_fits``); its ``lax.scan`` form (``_chunked``)
is the same mathematics, the reference the tests hold the kernel to, and
what the op's backward differentiates.

- **Grid** (batch x value heads, sequence slabs): a slab is
  ``chunks_per_step`` chunks of C tokens, walked two at a time by an
  in-kernel loop; the slab axis is ``"arbitrary"`` and the state S (dk, dv)
  f32 is VMEM scratch, zeroed at a head's first slab and never written to
  HBM.
- **Layout.** q, k (B, S, H dk), v and o (B, S, H dv): the (B, S, H, d)
  arrays as they are, a head's slab read and written through the index
  maps, so nothing is transposed. The gates (B, S, H) come whole a slab
  (the H columns on lanes) and a head takes its column with a mask; the
  cumulative decay G of each chunk is summed outside, by the scan form's
  own ``cumsum``.
- **A pair of chunks**: the decay exp(G_i - G_j), masked BEFORE the
  ``exp``, and A = strictly_lower((beta k) k^T * decay) of both chunks as
  the diagonal blocks of one 2C x 2C panel; T = (I + A)^-1 by recursive
  doubling of the diagonal blocks: with X the inverse of the blocks of
  size b on the diagonal and E the part of A in the off-diagonal b-blocks
  of the 2b-blocks, X <- X - X E X is the inverse of the 2b-blocks (block
  forward substitution; C = 64 takes five doublings and no division). The
  two chunks' X sit side by side, [X_a | X_b] (C, 2C), so each product of
  a doubling fills the MXU's 128 lanes with both chunks, against
  block-diagonal right sides. Then U = T (beta v), W = T (beta exp(G) k)
  for both, and chunk by chunk V' = U - W S,
  O = (exp(G) q) S + lower_incl(q k^T * decay) V' and
  S <- exp(G_C) S + (exp(G_C - G) k)^T V'.

Every number is float32 and every product runs at ``Precision.HIGHEST``,
as in the scan form: a bfloat16 pass here would be an error the state
carries over thousands of tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# chunks a grid step walks, at most (the largest even divisor of a head's
# chunks up to this)
CHUNKS_PER_STEP = 16
# whether the in-kernel loop over a step's pairs of chunks is unrolled
# (Mosaic takes a whole unroll or none)
UNROLL = True

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def chunks_per_step(seq_len: int, chunk: int) -> int:
    """The chunks a grid step walks: the largest even divisor of the
    sequence's chunks that is at most ``CHUNKS_PER_STEP``."""
    n = seq_len // chunk
    return max(d for d in range(2, min(n, CHUNKS_PER_STEP) + 1, 2)
               if n % d == 0)


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _block_diagonal(packed, left):
    """blockdiag(P_a, P_b) (2C, 2C) of the packed [P_a | P_b] (C, 2C)."""
    return jnp.concatenate([jnp.where(left, packed, 0.0),
                            jnp.where(left, 0.0, packed)], axis=0)


def _packed_inverse(packed, xor, left):
    """[(I + A_a)^-1 | (I + A_b)^-1] of the packed [A_a | A_b] (C, 2C) of
    two strictly lower C x C matrices; ``xor`` is i ^ (j mod C) of the
    packed form's (row, column) iotas: within a chunk the 2b-block of a
    pair is its b-block's sibling where b <= i ^ j < 2b. Each doubling is
    two products of the packed form by a block-diagonal (2C, 2C): one pass
    of the 128-lane MXU for both chunks."""
    chunk = packed.shape[0]
    x = (jnp.where(xor == 0, 1.0, 0.0)
         - jnp.where(xor == 1, packed, 0.0))  # the 2 x 2 blocks' inverses
    b = 2
    while b < chunk:
        e = jnp.where((xor >= b) & (xor < 2 * b), packed, 0.0)
        x = x - _dot(_dot(x, _block_diagonal(e, left)),
                     _block_diagonal(x, left))
        b *= 2
    return x


def _column(block, head):
    """Column ``head`` of a (rows, H) block of a gate, as (rows, 1)."""
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1,
                   keepdims=True)


def _kernel(gam_ref, beta_ref, q_ref, k_ref, v_ref, o_ref, state, *,
            heads, chunk, pairs):
    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    head = pl.program_id(0) % heads
    c2 = 2 * chunk
    # a pair's (2C, 2C) panels: its two chunks are the diagonal C-blocks,
    # where i ^ j < C
    i = lax.broadcasted_iota(jnp.int32, (c2, c2), 0)
    j = lax.broadcasted_iota(jnp.int32, (c2, c2), 1)
    causal = ((i ^ j) < chunk) & (i >= j)
    # the packed (C, 2C) form [X_a | X_b]
    lane = lax.broadcasted_iota(jnp.int32, (chunk, c2), 1)
    xor = lax.broadcasted_iota(jnp.int32, (chunk, c2), 0) ^ (
        lane & (chunk - 1))
    left = lane < chunk

    def one_pair(n, carry):
        start = pl.multiple_of(n * c2, c2)
        rows = pl.ds(start, c2)
        g = _column(gam_ref[rows, :], head)                   # (2C, 1)
        beta = _column(beta_ref[rows, :], head)
        q, k, v = q_ref[rows, :], k_ref[rows, :], v_ref[rows, :]
        g_row = jnp.sum(jnp.where(i == j, g, 0.0), axis=0,
                        keepdims=True)                        # (1, 2C)
        decay = jnp.exp(jnp.where(causal, g - g_row, 0.0))
        kb = k * beta
        a = jnp.where(causal & (i > j), _dot(kb, k, _NT) * decay, 0.0)
        t = _block_diagonal(_packed_inverse(
            jnp.where(left, a[:chunk], a[chunk:]), xor, left), left)
        qk = jnp.where(causal, _dot(q, k, _NT) * decay, 0.0)
        eg = jnp.exp(g)
        u = _dot(t, v * beta)
        w = _dot(t, kb * eg)
        qg = q * eg
        s = state[...]
        for half in range(2):
            r = slice(half * chunk, (half + 1) * chunk)
            end = start + (half + 1) * chunk
            last = _column(gam_ref[pl.ds(end - 1, 1), :], head)
            fresh = u[r] - _dot(w[r], s)
            # qk's rows of this chunk are nought outside its columns
            zero = jnp.zeros_like(fresh)
            fresh_rows = jnp.concatenate(
                [fresh, zero] if half == 0 else [zero, fresh], axis=0)
            o_ref[pl.ds(end - chunk, chunk), :] = (
                _dot(qg[r], s) + _dot(qk[r], fresh_rows))
            s = jnp.exp(last) * s + _dot(k[r] * jnp.exp(last - g[r]), fresh,
                                         _TN)
        state[...] = s
        return carry

    lax.fori_loop(0, pairs, one_pair, 0, unroll=UNROLL)


# jitted: a model's layers share one trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnames=("chunk",))
def chunk_forward(q, k, v, g, beta, chunk: int):
    """o (B, S, H, dv) f32 of the gated delta rule, as
    ``linear_attention.gated_delta_rule`` takes its operands;
    ``linear_attention.kernel_fits`` must hold and C divide S."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    per_step = chunks_per_step(s, chunk)
    slab = per_step * chunk
    # G, cumulative within each chunk: the scan form's own sum
    gam = jnp.cumsum(g.astype(f32).reshape(b, s // chunk, chunk, h),
                     axis=2).reshape(b, s, h)

    def head_map(n, t):
        return n // h, t, n % h

    def gate_map(n, t):
        return n // h, t, 0

    gate = pl.BlockSpec((None, slab, h), gate_map)
    o = pl.pallas_call(
        functools.partial(_kernel, heads=h, chunk=chunk, pairs=per_step // 2),
        grid=(b * h, s // slab),
        in_specs=[gate, gate,
                  pl.BlockSpec((None, slab, dk), head_map),
                  pl.BlockSpec((None, slab, dk), head_map),
                  pl.BlockSpec((None, slab, dv), head_map)],
        out_specs=pl.BlockSpec((None, slab, dv), head_map),
        out_shape=jax.ShapeDtypeStruct((b, s, h * dv), f32),
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="gated_delta_rule_fwd",
    )(gam, beta.astype(f32), q.astype(f32).reshape(b, s, h * dk),
      k.astype(f32).reshape(b, s, h * dk),
      v.astype(f32).reshape(b, s, h * dv))
    return o.reshape(b, s, h, dv)
