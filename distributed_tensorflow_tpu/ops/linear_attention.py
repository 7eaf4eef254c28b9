"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): linear
attention whose state is a matrix a head, decayed and corrected by a delta
rule at every token. Per value head, with state ``S`` (dk, dv), ``S_0 = 0``:

    S <- exp(g_t) S
    u_t = beta_t (v_t - S^T k_t)
    S <- S + k_t u_t^T
    o_t = S^T q_t

computed here in its CHUNKED form (the ``transformers`` library's
``torch_chunk_gated_delta_rule``): the tokens of a chunk of C interact
through C x C matrices, and the state crosses from chunk to chunk as the
carry of one ``lax.scan``, never once a token. With ``G`` the cumulative sum
of ``g`` inside a chunk:

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
BEFORE the ``exp``. ``(I + A)^-1`` is one triangular solve a chunk against
the identity, T, and U and W are products with T. The backward pass
recomputes the op from its five inputs (``jax.checkpoint``) and runs the
scan's own backward (JAX differentiates it; the state of every chunk, dk dv
f32 a head, is what it keeps): nothing of the chunks outlives the op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.utils import telemetry

CHUNK = 64
IMPLEMENTATION = "chunked_scan"

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
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if s % chunk:
        raise ValueError(f"the gated delta rule runs in chunks of {chunk} "
                         f"tokens: the sequence of {s} does not divide into "
                         f"them")
    telemetry.get_tracer().record_instant(
        "linear_attention_path", implementation=IMPLEMENTATION, chunk=chunk,
        chunks=s // chunk, state_bytes_per_head=dk * dv * 4)
    return _chunked(q, k, v, g, beta, chunk)


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

