"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): linear
attention whose state is a matrix a head, decayed and corrected by a delta
rule at every token. Per value head, with state ``S`` (dk, dv), ``S_0 = 0``:

    S <- exp(g_t) S
    u_t = beta_t (v_t - S^T k_t)
    S <- S + k_t u_t^T
    o_t = S^T q_t

computed here in its CHUNKED form (the ``transformers`` library's
``torch_chunk_gated_delta_rule``): the tokens of a chunk of C interact
through C x C matrices, and the state crosses from chunk to chunk, never
once a token. With ``G`` the cumulative sum of ``g`` inside a chunk:

    A = strictly_lower((beta k) k^T * exp(G_i - G_j))
    [U W] = (I + A)^-1 [beta v, beta exp(G) k]     (a triangular solve)
    V' = U - W S
    O = (exp(G) q) S + lower_incl(q k^T * exp(G_i - G_j)) V'
    S <- exp(G_C) S + (exp(G_C - G) k)^T V'

Every number is float32 and every product of the op runs at the highest
precision: the gates, the decays and the solve are what the recurrence is
made of, and a bfloat16 pass there would be an error that the state carries
over thousands of tokens. An ``exp`` of ``G_i - G_j`` is taken only where
``i >= j`` (the difference is <= 0 there): above the diagonal it is masked
BEFORE the ``exp``.

Two implementations of the forward, chosen by what the code can observe:

- **chunk_kernel** -- on a TPU (a program lowered for one), where dk and dv
  are multiples of 128, C a power of two and the chunks even in number
  (``kernel_fits``): the Pallas kernel of ``ops/linear_attention_kernel.py``.
  A grid step walks a slab of chunks of one (batch, value head), two at a
  time; each chunk's C x C matrices, its ``(I + A)^-1`` (by recursive
  doubling of the diagonal blocks: block forward substitution as matrix
  products) and the state S, carried from slab to slab in VMEM scratch,
  never cross HBM.
- **chunked_scan** -- everything else (any other backend, other widths):
  ``_chunked``, the equations above in ``jnp`` under one ``lax.scan`` over
  the chunks, ``(I + A)^-1`` one triangular solve a chunk against the
  identity. It is the form the tests hold the kernel to.

Each program records which one it was lowered with (the
``linear_attention_path`` instant). The backward is the scan form's on
every platform (a ``custom_vjp``: the residuals are the op's five inputs,
and its rule is ``jax.vjp`` of ``_chunked``, which is checkpointed: it
recomputes the chunks and runs the scan's own backward, keeping the state
of every chunk, dk dv f32 a head); nothing of the chunks outlives the op.
A Pallas backward is not written yet.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.utils.profiling import lowering_instant

CHUNK = 64
IMPLEMENTATION = "chunked_scan"
KERNEL_IMPLEMENTATION = "chunk_kernel"

_einsum = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)


def _by_chunk(x, chunks: int, c: int):
    """(B, S, H, ...) -> (B, H, chunks, C, ...), in float32."""
    b, _, h = x.shape[:3]
    x = x.astype(jnp.float32).reshape(b, chunks, c, h, *x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k (B, S, H, dk): l2-normalised, q already scaled, as many heads as
    ``v``; v (B, S, H, dv); g (B, S, H): the log of each token's decay (<=
    0); beta (B, S, H): the write strength in (0, 1). Returns o (B, S, H, dv)
    in float32. ``chunk`` must divide S."""
    s = q.shape[1]
    if s % chunk:
        raise ValueError(f"the gated delta rule runs in chunks of {chunk} "
                         f"tokens: the sequence of {s} does not divide into "
                         f"them")
    return _rule(q, k, v, g, beta, chunk)


def kernel_fits(seq_len: int, dk: int, dv: int, chunk: int) -> bool:
    """Whether the kernel's tiling takes these shapes: dk and dv multiples
    of the 128 lanes, C a power of two of at least 8 (a whole number of
    sublane tiles, halved by the kernel's inverse) and an even number of
    chunks (the kernel takes them in pairs)."""
    return (dk % 128 == 0 and dv % 128 == 0 and chunk >= 8
            and chunk & (chunk - 1) == 0 and (seq_len // chunk) % 2 == 0)


def _by_platform(kernel, scan, *args):
    """``kernel(*args)`` in a program lowered for a TPU, ``scan(*args)`` in
    one lowered for anything else (both are traced, one is lowered)."""
    return lax.platform_dependent(*args, tpu=kernel, default=scan)


def _forward(q, k, v, g, beta, chunk):
    """o by the kernel or the scan, marked with the ``linear_attention_path``
    instant of the implementation the program was lowered with."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    note = {"chunk": chunk, "chunks": s // chunk,
            "state_bytes_per_head": dk * dv * 4}

    def scan(q, *xs):
        q = lowering_instant("linear_attention_path", q,
                             implementation=IMPLEMENTATION, **note)
        return _chunked(q, *xs, chunk)

    if not kernel_fits(s, dk, dv, chunk):
        return scan(q, k, v, g, beta)
    # imports Pallas: only a program whose widths the kernel takes pays
    from distributed_tensorflow_tpu.ops import linear_attention_kernel as lk

    def kernel(q, *xs):
        per_step = lk.chunks_per_step(s, chunk)
        q = lowering_instant("linear_attention_path", q,
                             implementation=KERNEL_IMPLEMENTATION,
                             grid_steps=s // chunk // per_step,
                             chunks_per_step=per_step, **note)
        return lk.chunk_forward(q, *xs, chunk=chunk)

    return _by_platform(kernel, scan, q, k, v, g, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, chunk):
    return _forward(q, k, v, g, beta, chunk)


def _rule_fwd(q, k, v, g, beta, chunk):
    return _forward(q, k, v, g, beta, chunk), (q, k, v, g, beta)


def _rule_bwd(chunk, inputs, do):
    # the scan form's own backward, on every platform: it recomputes the
    # chunks from the five inputs (``_chunked`` is checkpointed)
    _, vjp = jax.vjp(lambda *xs: _chunked(*xs, chunk), *inputs)
    return vjp(do)


_rule.defvjp(_rule_fwd, _rule_bwd)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _chunked(q, k, v, g, beta, chunk):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n, c = s // chunk, chunk
    q, k, v = (_by_chunk(x, n, c) for x in (q, k, v))
    g, beta = (_by_chunk(x, n, c) for x in (g, beta))
    gam = jnp.cumsum(g, axis=-1)                              # (B, H, n, C)
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    diff = gam[..., :, None] - gam[..., None, :]              # (.., C, C)
    strict = jnp.exp(jnp.where(i > j, diff, -jnp.inf))
    incl = jnp.exp(jnp.where(i >= j, diff, -jnp.inf))
    kb = k * beta[..., None]
    a = _einsum("...id,...jd->...ij", kb, k) * strict
    t = lax.linalg.triangular_solve(
        a, jnp.broadcast_to(jnp.eye(c, dtype=jnp.float32), a.shape),
        left_side=True, lower=True, unit_diagonal=True)
    u = _einsum("...ij,...je->...ie", t, v * beta[..., None])
    w = _einsum("...ij,...jd->...id", t, kb * jnp.exp(gam)[..., None])
    qk = _einsum("...id,...jd->...ij", q, k) * incl
    qg = q * jnp.exp(gam)[..., None]
    kd = k * jnp.exp(gam[..., -1:] - gam)[..., None]
    last = jnp.exp(gam[..., -1])                              # (B, H, n)

    def one_chunk(state, xs):
        u_c, w_c, qg_c, kd_c, qk_c, last_c = xs
        fresh = u_c - _einsum("bhcd,bhde->bhce", w_c, state)
        out = (_einsum("bhcd,bhde->bhce", qg_c, state)
               + _einsum("bhij,bhje->bhie", qk_c, fresh))
        state = (last_c[..., None, None] * state
                 + _einsum("bhcd,bhce->bhde", kd_c, fresh))
        return state, out

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, qg, kd, qk, last))
    state = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = lax.scan(one_chunk, state, xs)                     # (n, B, H, C, dv)
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, s, h, dv)

