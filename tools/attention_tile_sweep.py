"""Tile sweep of the fused causal attention on the attached TPU.

For each shape (batch, S, heads, head_dim) and each (query tile, key tile)
it times the forward kernel and forward + backward of
``ops/attention.py:blockwise_attention`` (the fused path), and once a
shape the ``lax.scan`` path at the key tile 512 for comparison, and prints
one JSON line each:

  {"shape": [8, 2048, 32, 64], "path": "fused", "q_tile": 512,
   "k_tile": 512, "fwd_ms": ..., "fwd_bwd_ms": ..., "fwd_tflops": ...,
   "fwd_bwd_tflops": ..., "grad_rel_err_vs_scan": ...}

FLOP/s count the causal half only (the tiles' useful work): 2 matmuls
forward, 5 more backward, each 2 * B * H * S * S/2 * Dh. Times are host
clock around ``block_until_ready`` over ``--iters`` calls after a warm-up.
It fails off the TPU: a CPU time is no device number.

Usage: python tools/attention_tile_sweep.py [--iters 20]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# sys.path[0] is tools/, the package root is one level up
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from distributed_tensorflow_tpu.ops import (  # noqa: E402
    attention,
    flash_attention,
)

# the benchmark's two cells: opt-1.3b and opt-125m at 8 x 2,048 tokens
SHAPES = ((8, 2048, 32, 64), (8, 2048, 12, 64))
TILES = (256, 512, 1024)


def _timed(fn, args, iters: int) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def measure(shape, k_tile: int, iters: int):
    """(fwd ms, fwd+bwd ms, the three gradients) at one key tile."""
    b, s, h, dh = shape
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32)
                  .astype(jnp.bfloat16) for kk in keys)

    def fwd(q, k, v):
        return attention.blockwise_attention(q, k, v, k_tile, causal=True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32)
                       * g.astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, (0, 1, 2)))
    return (_timed(jax.jit(fwd), (q, k, v), iters),
            _timed(grad, (q, k, v), iters), grad(q, k, v))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print(f"needs a TPU, found {jax.devices()}", file=sys.stderr)
        return 3
    # twenty one-off programs (the scan's is 285 MB) would push the
    # trainer's out of a size-capped persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    by_platform = attention._by_platform
    for shape in SHAPES:
        b, s, h, dh = shape
        half = 2 * b * h * s * s // 2 * dh  # one causal matmul
        # the scan is what every platform but the TPU lowers
        attention._by_platform = lambda fused, scan, *a: scan(*a)
        fwd_ms, both_ms, ref = measure(shape, 512, args.iters)
        attention._by_platform = by_platform
        print(json.dumps({"shape": shape, "path": "scan", "k_tile": 512,
                          "fwd_ms": fwd_ms, "fwd_bwd_ms": both_ms}),
              flush=True)
        for tq, tk in itertools.product(TILES, TILES):
            flash_attention.MAX_QUERY_TILE = tq
            fwd_ms, both_ms, grads = measure(shape, tk, args.iters)
            print(json.dumps({
                "shape": shape, "path": "fused", "q_tile": tq, "k_tile": tk,
                "fwd_ms": fwd_ms, "fwd_bwd_ms": both_ms,
                "fwd_tflops": 2 * half / fwd_ms / 1e9,
                "fwd_bwd_tflops": 7 * half / both_ms / 1e9,
                "grad_rel_err_vs_scan": max(map(_rel, grads, ref))}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
