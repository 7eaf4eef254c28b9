"""The gated delta rule's forward on the attached TPU: the Pallas kernel
(``ops/linear_attention_kernel.py``) over the chunks a grid step walks,
against the ``lax.scan`` form, at the linear layers' shape of the
``qwen3-next-80b-a3b`` cell (1 x 16,384 tokens, 32 value heads of 128).

For each ``CHUNKS_PER_STEP`` and ``UNROLL`` of ``STEPS`` it prints one
JSON line: the forward's milliseconds a call and o's relative distance
from the scan form's; then one line for the scan form's forward, one for
forward + backward of the op as a step runs it by either forward (a loss
that reads o, so neither forward is dead code), and the five gradients'
distances (the backward is the scan form's either way). Times are host
clock around ``block_until_ready`` over ``--iters`` calls after a
warm-up. It fails off the TPU: a CPU time is no device number.

Usage: python tools/delta_rule_sweep.py [--iters 10]
"""

from __future__ import annotations

import argparse
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
    linear_attention,
    linear_attention_kernel,
)

SHAPE = (1, 16384, 32, 128)  # batch, tokens, value heads, dk = dv
# (chunks a grid step walks, whether its loop is unrolled)
STEPS = ((4, False), (8, False), (16, False), (32, False), (4, True),
         (8, True), (16, True))


def _timed(fn, args, iters: int) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _inputs(decay: float, seed: int = 0):
    """q, k l2-normalised (q over sqrt(dk)), v normal, g = -decay x
    softplus(normal), beta = sigmoid(normal): exp(g) about 0.1 at decay 3,
    0.99 at 0.01."""
    b, s, h, d = SHAPE
    k = jax.random.split(jax.random.key(seed), 5)

    def l2(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (l2(jax.random.normal(k[0], (b, s, h, d))) * d ** -0.5,
            l2(jax.random.normal(k[1], (b, s, h, d))),
            jax.random.normal(k[2], (b, s, h, d)),
            -decay * jax.nn.softplus(jax.random.normal(k[3], (b, s, h))),
            jax.nn.sigmoid(jax.random.normal(k[4], (b, s, h))))


def _sweep(chunk: int, device: str, iters: int) -> None:
    x = _inputs(1.0)
    scan = jax.jit(lambda *a: linear_attention._chunked(*a, chunk))
    want = scan(*x)
    default = (linear_attention_kernel.CHUNKS_PER_STEP,
               linear_attention_kernel.UNROLL)
    for per_step, unroll in STEPS:
        linear_attention_kernel.CHUNKS_PER_STEP = per_step
        linear_attention_kernel.UNROLL = unroll
        linear_attention_kernel.chunk_forward.clear_cache()
        kernel = jax.jit(lambda *a: linear_attention_kernel.chunk_forward(
            *a, chunk=chunk))
        print(json.dumps({"device": device, "shape": SHAPE, "path": "kernel",
                          "chunks_per_step": per_step, "unroll": unroll,
                          "fwd_ms": _timed(kernel, x, iters),
                          "rel_err_vs_scan": _rel(kernel(*x), want)}),
              flush=True)
    (linear_attention_kernel.CHUNKS_PER_STEP,
     linear_attention_kernel.UNROLL) = default
    linear_attention_kernel.chunk_forward.clear_cache()
    print(json.dumps({"device": device, "shape": SHAPE, "path": "scan",
                      "fwd_ms": _timed(scan, x, iters)}), flush=True)
    # forward and backward as a step runs the op: the kernel's custom
    # VJP, against the checkpointed scan form differentiated
    grads = {}
    for path, op in (("kernel", linear_attention.gated_delta_rule),
                     ("scan", lambda *a: linear_attention._chunked(*a,
                                                                   chunk))):
        both = jax.jit(jax.grad(lambda *a, op=op: jnp.sum(op(*a) ** 2) / 2,
                                argnums=(0, 1, 2, 3, 4)))
        grads[path] = both(*x)
        print(json.dumps({"device": device, "shape": SHAPE, "path": path,
                          "fwd_bwd_ms": _timed(both, x, iters)}),
              flush=True)
    print(json.dumps({"grad_rel_err_kernel_vs_scan": [
        _rel(a, b) for a, b in zip(grads["kernel"], grads["scan"])]}),
        flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("tools/delta_rule_sweep.py times the chip: no TPU here",
              file=sys.stderr)
        return 2
    device = jax.devices()[0].device_kind
    with jax.default_matmul_precision("highest"):
        _sweep(linear_attention.CHUNK, device, args.iters)
    return 0


if __name__ == "__main__":
    sys.exit(main())
