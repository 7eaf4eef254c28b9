"""The reference's deep CNN, rebuilt as a pure-JAX functional model.

Architecture parity with ``conv_net`` (``/root/reference/.idea/MNISTDist.py:66-90``)
and its parameter dicts (``:117-141``):

    reshape [B,784] -> [B,28,28,1]
    conv 5x5x1x32  + bias + relu -> maxpool 2x2  -> [B,14,14,32]
    conv 5x5x32x64 + bias + relu -> maxpool 2x2  -> [B,7,7,64]
    flatten 3136 -> dense 1024 + relu -> dropout -> dense 10 logits

≈3.27 M parameters (wd1 = 3136x1024 dominates). Init parity with
``weight_variable``/``bias_variable`` (``MNISTDist.py:42-49``): truncated
normal σ=0.1, biases constant 0.1.

The model is a pytree-of-arrays + pure ``apply`` — no layers/objects — so it
jits, shards, vmaps and grads like any JAX function. Params keep the
reference's exact names (wc1, wc2, wd1, out / bc1, bc2, bd1, out) so
checkpoints are self-describing against the reference.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.registry import register_model
from distributed_tensorflow_tpu.ops import nn


def truncated_normal_init(key, shape, stddev=0.1, dtype=jnp.float32):
    """TF ``tf.truncated_normal`` parity (MNISTDist.py:43): normal truncated
    to ±2σ. jax.random.truncated_normal samples the truncated distribution
    directly (TF redraws, same distribution)."""
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def constant_init(shape, value=0.1, dtype=jnp.float32):
    """TF ``bias_variable`` parity (MNISTDist.py:47-49)."""
    return jnp.full(shape, value, dtype)


@register_model("deep_cnn")
class DeepCNN:
    """2×conv + 2×dense MNIST classifier (the reference's only model).

    Generalised just enough for the Fashion-MNIST drop-in (identical graph)
    and other square grayscale inputs: image_size and num_classes are
    parameters with reference defaults (MNISTDist.py:33-39).
    """

    def __init__(
        self,
        image_size: int = 28,
        channels: int = 1,
        num_classes: int = 10,
        hidden_units: int = 1024,
        compute_dtype: Any = None,
        use_pallas: bool = False,
        pallas_interpret: bool = False,
    ):
        self.image_size = image_size
        self.channels = channels
        self.num_classes = num_classes
        self.hidden_units = hidden_units
        self.compute_dtype = compute_dtype
        self.use_pallas = use_pallas
        # the Pallas interpreter is how the CPU tests run the kernel, and
        # only they ask for it: no entry point does, so --pallas compiles
        # the Mosaic kernel or fails where it cannot
        self.pallas_interpret = pallas_interpret
        # two 2x2 stride-2 SAME pools => ceil(size/4)
        self.pooled = math.ceil(math.ceil(image_size / 2) / 2)
        self.flat_dim = self.pooled * self.pooled * 64

    def init(self, key, dtype=jnp.float32):
        """Parameter pytree with the reference's names/shapes (MNISTDist.py:117-141)."""
        ks = jax.random.split(key, 4)
        weights = {
            "wc1": truncated_normal_init(ks[0], (5, 5, self.channels, 32), dtype=dtype),
            "wc2": truncated_normal_init(ks[1], (5, 5, 32, 64), dtype=dtype),
            "wd1": truncated_normal_init(ks[2], (self.flat_dim, self.hidden_units), dtype=dtype),
            "out": truncated_normal_init(ks[3], (self.hidden_units, self.num_classes), dtype=dtype),
        }
        biases = {
            "bc1": constant_init((32,), dtype=dtype),
            "bc2": constant_init((64,), dtype=dtype),
            "bd1": constant_init((self.hidden_units,), dtype=dtype),
            "out": constant_init((self.num_classes,), dtype=dtype),
        }
        return {"weights": weights, "biases": biases}

    def apply(self, params, x, *, keep_prob=1.0, rng=None, train: bool = False):
        """Forward pass -> logits (reference ``conv_net``, MNISTDist.py:66-90).

        ``keep_prob`` mirrors the reference's dropout placeholder
        (MNISTDist.py:115). Note the reference *disables* dropout by feeding
        1.0 during training (MNISTDist.py:179, a known defect); here dropout
        is actually applied when ``train=True`` and an rng is given.
        """
        w, b = params["weights"], params["biases"]
        cd = self.compute_dtype
        x = nn.normalize_if_u8(x, cd)
        x = x.reshape(-1, self.image_size, self.image_size, self.channels)

        x = nn.conv2d(x, w["wc1"], b["bc1"], compute_dtype=cd)
        x = nn.maxpool2d(x, k=2)
        x = nn.conv2d(x, w["wc2"], b["bc2"], compute_dtype=cd)
        x = nn.maxpool2d(x, k=2)

        x = x.reshape(-1, self.flat_dim)
        if self.use_pallas:
            # fused matmul+bias+relu Pallas kernel on the dominant FC layer
            from distributed_tensorflow_tpu.ops import pallas_ops

            interpret = self.pallas_interpret
            if cd is not None:
                x = pallas_ops.fused_dense_relu(
                    x.astype(cd), w["wd1"].astype(cd), b["bd1"].astype(cd),
                    interpret,
                ).astype(jnp.float32)
            else:
                x = pallas_ops.fused_dense_relu(x, w["wd1"], b["bd1"], interpret)
        else:
            x = jax.nn.relu(nn.dense(x, w["wd1"], b["bd1"], compute_dtype=cd))
        x = nn.dropout(x, keep_prob, rng, deterministic=not train)
        logits = nn.dense(x, w["out"], b["out"], compute_dtype=cd)
        return logits

    def num_params(self, params=None):
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.key(0)))
        return sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
