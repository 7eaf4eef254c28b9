"""The main path's kernels and steps, compiled for a described TPU v5e.

No chip is attached here: the TPU compiler is given a described
``v5e:2x2`` topology and refuses what the chip's compiler would refuse —
a kernel slice that misses the tiling, too much fast memory, a program
past the device's 16 GB. Nothing runs, so these say nothing about
results or times (``chip_smoke.py`` on the chip does).

All of these live in THIS file and describe the topology inside a
module-scoped fixture: loading the TPU compiler takes a per-process lock,
so no module may do it while it is imported, and a second file could land
on a second xdist worker whose fixture would then skip. The persistent
compile cache is off around them — such a compile can be written to it
but not read back without a chip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_tensorflow_tpu.models import DeepCNN
from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.ops.pallas_ops import fused_dense_relu
from distributed_tensorflow_tpu.training import (
    adam,
    create_train_state,
    make_train_step,
)
from distributed_tensorflow_tpu.training.device_step import (
    make_device_train_step,
)

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """The shapes of ``tree`` placed on the described device."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


# the deep CNN's dominant FC layer (3136 x 1024, K padded to 3200) at the
# smoke's batch, at one M tile, and at an M the wrapper has to pad
@pytest.mark.parametrize("m", [2048, 128, 200])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_dense_relu_compiles_to_a_mosaic_kernel(one_chip, m, dtype):
    args = _on(one_chip, (jax.ShapeDtypeStruct((m, 3136), dtype),
                          jax.ShapeDtypeStruct((3136, 1024), dtype),
                          jax.ShapeDtypeStruct((1024,), dtype)))
    compiled = jax.jit(fused_dense_relu).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_pallas", [True, False])
def test_cnn_device_chunk_compiles_at_the_smoke_size(one_chip, use_pallas):
    """``--device_data --bf16 --batch_size 2048 --device_chunk 50`` with
    adam, the MNIST split resident: the Mosaic kernel is in the compiled
    chunk exactly when ``--pallas`` asks for it."""
    from distributed_tensorflow_tpu.data.device_data import DeviceData

    model = DeepCNN(compute_dtype=jnp.bfloat16, use_pallas=use_pallas)
    opt = adam(1e-3)
    state = jax.eval_shape(lambda: create_train_state(model, opt, seed=0))
    data = DeviceData(jax.ShapeDtypeStruct((55000, 784), jnp.uint8),
                      jax.ShapeDtypeStruct((55000,), jnp.int32))
    step = make_device_train_step(model, opt, 2048, keep_prob=0.75, chunk=50)
    compiled = step.lower(*_on(one_chip, (state, data))).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_lm_train_step_compiles_at_4k_context(one_chip):
    """The long-context LM step (S = 4096, blockwise attention at 512,
    bf16, adam, batch 8) fits one chip with room to spare."""
    model = TransformerLM(vocab_size=64, seq_len=4096, d_model=256,
                          num_heads=4, num_blocks=4, attn_block=512,
                          compute_dtype=jnp.bfloat16)
    opt = adam(1e-3)
    state = jax.eval_shape(lambda: create_train_state(model, opt, seed=0))
    batch = (jax.ShapeDtypeStruct((8, 4096), jnp.int32),
             jax.ShapeDtypeStruct((8, 4096), jnp.int32))
    step = make_train_step(model, opt, keep_prob=1.0)
    compiled = step.lower(*_on(one_chip, (state, batch))).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES // 2
