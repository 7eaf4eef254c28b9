"""Pallas TPU kernels for the hot path.

XLA already fuses most of this model well; the one op worth a hand kernel
is the dominant FC layer (wd1: 3136x1024 — ~85% of the deep CNN's FLOPs,
reference MNISTDist.py:83-84) where fusing bias+ReLU into the matmul
epilogue keeps the activation write out of HBM round-trips.

``fused_dense_relu`` computes relu(x @ w + b) as one MXU kernel:
- grid over (M/TM, N/TN) output tiles, full K per tile in VMEM
- f32 accumulation via preferred_element_type (hardware-native for bf16)
- custom VJP: the backward is plain XLA (dx = g@wT etc.) — the fusion win
  is in the forward epilogue; XLA handles the transposed matmuls well
- caller-side zero-padding when shapes miss the (8,128) tile grid
- ``interpret=True`` runs the same kernel on CPU (used by tests)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 128
TILE_N = 128


def _kernel(x_ref, w_ref, b_ref, o_ref):
    # bf16 operands take the MXU's native pass whatever
    # jax_default_matmul_precision says (the tests set "highest"): Mosaic
    # refuses an fp32-precision contraction of bf16 vectors ("Bad lhs type")
    precision = (jax.lax.Precision.DEFAULT
                 if x_ref.dtype == jnp.bfloat16 else None)
    acc = jnp.dot(x_ref[:], w_ref[:], precision=precision,
                  preferred_element_type=jnp.float32)
    acc = acc + b_ref[:].astype(jnp.float32)  # b block is (1, TILE_N)
    o_ref[:] = jnp.maximum(acc, 0.0).astype(o_ref.dtype)


def _pad_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(x, w, b, interpret: bool = False):
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and b.shape == (N,)
    Mp, Kp, Np = _pad_to(M, TILE_M), _pad_to(K, 128), _pad_to(N, TILE_N)
    xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    # bias as (1, Np): 1-D operands trip Mosaic/XLA layout mismatches
    bp = jnp.pad(b, (0, Np - N)).reshape(1, Np)

    def spec(shape, index_map):
        if interpret:
            return pl.BlockSpec(shape, index_map)
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        _kernel,
        grid=(Mp // TILE_M, Np // TILE_N),
        in_specs=[
            spec((TILE_M, Kp), lambda i, j: (i, 0)),
            spec((Kp, TILE_N), lambda i, j: (0, j)),
            spec((1, TILE_N), lambda i, j: (0, j)),
        ],
        out_specs=spec((TILE_M, TILE_N), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        interpret=interpret,
    )(xp, wp, bp)
    return out[:M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_dense_relu(x, w, b, interpret: bool = False):
    """relu(x @ w + b) as a single fused Pallas TPU kernel."""
    return _forward(x, w, b, interpret)


def _fwd(x, w, b, interpret):
    y = _forward(x, w, b, interpret)
    return y, (x, w, y)


def _bwd(interpret, res, g):
    x, w, y = res
    g = jnp.where(y > 0, g, 0.0).astype(x.dtype)
    dx = jnp.dot(g, w.T)
    dw = jnp.dot(x.T, g)
    db = jnp.sum(g, axis=0).astype(x.dtype)
    return dx, dw, db


fused_dense_relu.defvjp(_fwd, _bwd)
