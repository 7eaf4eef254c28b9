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

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_tensorflow_tpu.models import DeepCNN
from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.ops.attention import blockwise_attention
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


# the benchmark's two cells (benchmark/configs/opt-*.json, traffic
# train-s2048): heads, width, blocks, remat
CELLS = {"opt-1.3b": (32, 2048, 8, True), "opt-125m": (12, 768, 12, False)}


def _kernels_under_attention(hlo: str) -> list[str]:
    """Names of the Mosaic kernels whose ``op_name`` path holds the
    ``attention`` scope, bare or inside ``jvp(...)`` / ``transpose(...)``
    (what ``attention_device_pct`` reads)."""
    paths = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo)
    return [re.search(r"flash_attention_\w+", p).group(0) for p in paths
            if "attention" in re.findall(r"[A-Za-z_]\w*", p)]


def _attention_grids(notes) -> list[tuple]:
    """(pass, mask, path, tiles that run, grid steps, masked tiles) of the
    ``attention_path`` instants among ``notes``, sorted."""
    return sorted((n["pass"], n.get("mask", "causal"), n["path"],
                   n["tiles_run"], n["grid_steps"], n["masked_tiles"])
                  for n in notes if n["name"] == "attention_path")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_fused_attention_compiles_at_the_cells_shapes(one_chip, cell):
    """Forward and backward of the cells' attention (8 x 2,048 tokens,
    head width 64, 512-key tile, bf16) lower to the two flash kernels
    under the ``attention`` scope, their grids the 10 tiles of 16 that run,
    and nothing panel-shaped is left."""
    from distributed_tensorflow_tpu.utils import telemetry

    heads = CELLS[cell][0]
    qkv = _on(one_chip, (jax.ShapeDtypeStruct((8, 2048, heads, 64),
                                              jnp.bfloat16),) * 3)

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, 512, causal=True)
        return out.astype(jnp.float32).sum()

    telemetry.get_tracer().clear()
    hlo = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*qkv).compile().as_text()
    assert _attention_grids(telemetry.last_spans(100)) == [
        ("backward", "causal", "fused", 10, 10, 4),
        ("forward", "causal", "fused", 10, 10, 4)]
    assert sorted(_kernels_under_attention(hlo)) == [
        "flash_attention_bwd", "flash_attention_fwd"]
    assert f"[8,{heads},2048,512]" not in hlo


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_lm_device_step_compiles_with_the_fused_attention(one_chip, cell):
    """The benchmark cells' whole step (``make_device_train_step``, batch
    8, chunk 1, adam on f32 masters, streamed head) for the described
    chip: the flash kernels are in it under the ``attention`` scope, no
    (B, H, S, block) panel is, and it fits the chip. Under ``--remat`` the
    forward kernel is still there once a block: the block's checkpoint
    keeps its ``out`` and logsumexp (``models/transformer.py:_remat``), at
    67 + 2 MB a block of ``opt-1.3b``."""
    from distributed_tensorflow_tpu.data.device_data import DeviceData

    heads, d_model, blocks, remat = CELLS[cell]
    model = TransformerLM(vocab_size=50272, seq_len=2048, d_model=d_model,
                          num_heads=heads, num_blocks=blocks, attn_block=512,
                          ce_block=2048, remat=remat,
                          compute_dtype=jnp.bfloat16)
    opt = adam(1e-4)
    state = jax.eval_shape(lambda: create_train_state(model, opt, seed=0))
    data = DeviceData(jax.ShapeDtypeStruct((4096, 2048), jnp.uint16),
                      jax.ShapeDtypeStruct((4096, 2048), jnp.uint16))
    step = make_device_train_step(model, opt, 8, keep_prob=1.0, chunk=1)
    compiled = step.lower(*_on(one_chip, (state, data))).compile()
    hlo = compiled.as_text()
    kernels = _kernels_under_attention(hlo)
    # with remat or without: the backward pass runs no forward kernel
    assert kernels.count("flash_attention_bwd") == blocks
    assert kernels.count("flash_attention_fwd") == blocks
    assert f"[8,{heads},2048,512]" not in hlo
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    if cell == "opt-1.3b":  # 9.78 GB before the two were kept, 10.22 GB now
        assert _device_bytes(compiled) < 11.5e9


@pytest.mark.parametrize("mode", ["dp", "tp"])
def test_sharded_steps_run_the_fused_attention_on_local_shapes(topo, mode):
    """Across the described 2x2 chips the kernel runs on each shard's own
    shapes: inside sync DP's ``shard_map`` as it stands, and in the
    global-view TP step through ``tensor_parallel.shard_attention`` (batch
    over "data", heads over "model") — GSPMD refuses to partition a Mosaic
    kernel, so without it this compile raises."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu.parallel.data_parallel import (
        make_dp_train_step,
    )
    from distributed_tensorflow_tpu.parallel.mesh import MeshSpec, make_mesh
    from distributed_tensorflow_tpu.parallel.tensor_parallel import (
        make_tp_train_step,
        tp_state_sharding,
    )

    model = TransformerLM(vocab_size=512, seq_len=512, d_model=256,
                          num_heads=4, num_blocks=2, attn_block=128,
                          ce_block=128, compute_dtype=jnp.bfloat16)
    opt = adam(1e-3)
    state = jax.eval_shape(lambda: create_train_state(model, opt, seed=0))
    if mode == "dp":
        mesh = make_mesh(MeshSpec(data=4, model=1), devices=topo.devices)
        shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
        step = make_dp_train_step(model, opt, mesh, donate=False)
        local = (8 // 4) * 4  # rows a shard x heads a shard
    else:
        mesh = make_mesh(MeshSpec(data=2, model=2), devices=topo.devices)
        shardings = tp_state_sharding(state, mesh)
        step = make_tp_train_step(model, opt, mesh, donate=False)
        local = (8 // 2) * (4 // 2)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, shardings)
    rows = NamedSharding(mesh, P("data"))
    batch = (jax.ShapeDtypeStruct((8, 512), jnp.int32, sharding=rows),) * 2
    hlo = step.lower(state, batch).compile().as_text()
    assert sorted(_kernels_under_attention(hlo)) == (
        ["flash_attention_bwd"] * 2 + ["flash_attention_fwd"] * 2)
    assert f"bf16[{local},64,512]" in hlo  # the kernels' (H*B, Dh, S)


def test_block_diffusion_attention_compiles_at_the_routed_cells_shapes(one_chip):
    """``sdar-30b-a3b.train-s4096``: 4 doubled sequences of 8,192 rows, 32
    query heads reading 4 key/value heads of width 128, the block-diffusion
    mask at block 4, a 512-key tile: forward and backward lower to the two
    flash kernels under the ``attention`` scope over the 80 of 256 tiles
    that run (24 of them under the mask), dk and dv come back at the
    key/value heads' shape, and no (B, H, S, block) panel is left."""
    from distributed_tensorflow_tpu.ops.attention import Mask
    from distributed_tensorflow_tpu.utils import telemetry

    q = jax.ShapeDtypeStruct((4, 8192, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((4, 8192, 4, 128), jnp.bfloat16)
    mask = Mask("block_diffusion", 4096, 4)

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, 512, mask=mask)
        return out.astype(jnp.float32).sum()

    telemetry.get_tracer().clear()
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        *_on(one_chip, (q, kv, kv))).compile()
    assert _attention_grids(telemetry.last_spans(100)) == [
        ("backward", "block_diffusion", "fused", 80, 80, 24),
        ("forward", "block_diffusion", "fused", 80, 80, 24)]
    hlo = compiled.as_text()
    assert sorted(_kernels_under_attention(hlo)) == [
        "flash_attention_bwd", "flash_attention_fwd"]
    assert "[4,32,8192,512]" not in hlo
    assert [o.shape for o in compiled.out_info] == [
        (4, 8192, 32, 128), (4, 8192, 4, 128), (4, 8192, 4, 128)]


def test_routed_layer_compiles_at_the_cells_widths(one_chip):
    """The routed cell's expert layer, forward and backward, at its
    published widths and tiles, on a quarter of its rows (8,192, and a
    buffer of 4 x: 32,768 rows; the whole takes 45 s to compile):
    the four grouped products of a pass are Mosaic kernels under the
    ``moe_experts`` scope (a tile that does not fit VMEM fails here, as
    the transposed product's 1,024-row tile did; the ``moe_path`` instants
    say the same), the routing, the gather and the add-back are under
    ``moe_router``. The products' groups end at the last pair, and so does
    the dispatch: the gather, the add-back and the gradients of both are
    four ``while`` loops under ``moe_router`` whose trip count the compiler
    does not know (the routing's live row tiles), and no body copies a
    carry: not the buffer's (32,768 rows), not an accumulator's (8,192 rows
    of the width). Outside the loops three fusions still write an array of
    the buffer's rows, all the gate's (its product, its backward, the sum of
    its two gradients: the selects that drop what the products left
    unwritten are fused into them), and they are the only fusions that read
    one. The whole (1.31 GB) takes no more than 0.1 GB over what it took
    (1.335 GB) when every pass ran the whole buffer."""
    from distributed_tensorflow_tpu.ops.moe import routed_experts
    from distributed_tensorflow_tpu.utils import telemetry

    h = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
    params = {"router": jax.ShapeDtypeStruct((2048, 128), jnp.float32),
              "w1": jax.ShapeDtypeStruct((16, 2048, 1536), jnp.float32),
              "w2": jax.ShapeDtypeStruct((16, 768, 2048), jnp.float32)}

    def loss(h, params):
        y, aux = routed_experts(h, params, top_k=8, capacity_factor=4.0,
                                compute_dtype=jnp.bfloat16)
        return y.astype(jnp.float32).sum() + aux["overflow_rows"]

    telemetry.get_tracer().clear()
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        *_on(one_chip, (h, params))).compile()
    hlo = compiled.as_text()
    lowered = [(r["path"], r["pass"]) for r in telemetry.last_spans(100)
               if r["name"] == "moe_path"]
    assert sorted(lowered) == ([("pallas_gmm", "backward")] * 2
                               + [("pallas_gmm", "forward")] * 2)
    paths = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo)
    # forward: 2 products; backward: 2 for the rows, 2 for the matrices
    assert len(paths) == 6 and all("moe_experts" in p for p in paths)
    assert sum("tgmm" in p for p in paths) == 2
    assert "bf16[32768,2048]" in hlo and "moe_router" in hlo
    computations = dict(re.findall(
        r"^(?:ENTRY )?(%[\w.-]+) \(.*?\n((?:  .*\n)+)", hlo, flags=re.M))
    entry = hlo[hlo.index("ENTRY"):]
    loops = [line for line in entry.splitlines()
             if " while(" in line and "moe_router" in line]
    assert len(loops) == 4, loops
    assert not any("known_trip_count" in line for line in loops)
    carry = re.compile(r"\[(?:32768,\d+|8192,2048)\]")
    for line in loops:
        body = computations[re.search(r"body=(%[\w.-]+)", line).group(1)]
        copies = [op for op in body.splitlines() if re.search(
            r"= \S+ copy(?:-start)?\(", op) and carry.search(op)]
        assert not copies, copies
    buffer = re.compile(r"\[32768,\d+\]")
    types = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.-]+) = (\(.*?\)|\S+) ", entry, flags=re.M))
    fusions = re.findall(
        r"^\s*(?:ROOT )?%[\w.-]+ = (\(.*?\)|\S+) fusion\((.*?)\), kind=",
        entry, flags=re.M)
    writes = [ty for ty, _ in fusions if buffer.search(ty)]
    touches = [ty for ty, args in fusions if buffer.search(ty) or any(
        buffer.search(types.get(a, "")) for a in re.findall(r"%[\w.-]+", args))]
    assert (len(writes), len(touches)) == (3, 3), touches
    assert _device_bytes(compiled) < 1.335e9 + 0.1e9


def test_the_planned_cells_step_compiles_with_both_masks_fused(one_chip):
    """``laguna-xs2.train-s8192``, built from its configuration's own file
    through its family's flags: the whole step (2 x 8,192 tokens, five
    layers of two kinds at the published widths, remat, adam on f32
    masters) for the described chip. The two full layers' attention lowers
    to the flash kernels under ``attention`` (136 of 256 tiles a head), the
    three window layers' to the same kernels under ``attention_window`` (31
    tiles), each grid as long as its list of live tiles, one forward a layer
    under remat;
    the experts' products are Mosaic kernels whose tiles fit VMEM (a
    (1024, 1024, 1024) tiling of the (2048, 1024) expert matrix did not);
    and ``memory_analysis()`` holds the arguments the configuration's
    ``bytes`` records and no more temporaries than it records."""
    from benchmark.harness import manifest
    from distributed_tensorflow_tpu.data.device_data import DeviceData
    from distributed_tensorflow_tpu.utils import telemetry

    cell = manifest.load_cell("laguna-xs2.train-s8192")
    trainer = cell.config["trainer"]
    seq, batch = cell.mix["seq_len"], cell.mix["batch_per_chip"]
    model = TransformerLM(
        seq_len=seq, compute_dtype=jnp.bfloat16,
        attn_block=trainer["attn_block"], ce_block=trainer["ce_block"],
        remat=trainer["remat"], moe_capacity=trainer["moe_capacity"],
        **cell.family().trainer_flags(cell.config, cell.mix))
    opt = adam(trainer["learning_rate"])
    state = jax.eval_shape(lambda: create_train_state(model, opt, seed=0))
    data = DeviceData(jax.ShapeDtypeStruct((4096, seq), jnp.uint16),
                      jax.ShapeDtypeStruct((4096, seq), jnp.uint16))
    step = make_device_train_step(model, opt, batch, keep_prob=1.0, chunk=1)
    telemetry.get_tracer().clear()
    compiled = step.lower(*_on(one_chip, (state, data))).compile()
    assert _attention_grids(telemetry.last_spans(200)) == [
        ("backward", "causal", "fused", 136, 136, 16),
        ("backward", "window", "fused", 31, 31, 31),
        ("forward", "causal", "fused", 136, 136, 16),
        ("forward", "window", "fused", 31, 31, 31)]
    hlo = compiled.as_text()
    paths = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo)
    kernels = {}
    for p in paths:
        scope = [e for e in re.findall(r"[A-Za-z_]\w*", p)
                 if e in telemetry.SCOPES][-1]
        name = re.search(r"flash_attention_\w+|tgmm|gmm", p).group(0)
        kernels[scope, name] = kernels.get((scope, name), 0) + 1
    assert kernels == {
        ("attention", "flash_attention_fwd"): 2,
        ("attention", "flash_attention_bwd"): 2,
        ("attention_window", "flash_attention_fwd"): 3,
        ("attention_window", "flash_attention_bwd"): 3,
        # a routed layer: two products forward, again under remat, two for
        # the rows and two for the matrices backward
        ("moe_experts", "gmm"): 4 * 6, ("moe_experts", "tgmm"): 4 * 2}
    assert "[2,48,8192,512]" not in hlo and "[2,64,8192,512]" not in hlo
    assert _device_bytes(compiled) < 0.65 * V5E_HBM_BYTES
    ma = compiled.memory_analysis()
    recorded = cell.config["bytes"]["compiled_step_for_described_v5e"]
    assert ma.argument_size_in_bytes == recorded["arguments"]
    # the file records PR 33's program (3.457 GB of temporaries); since the
    # dispatch runs the live row tiles alone it is 3.317 GB (no f32 gather of
    # the whole buffer), and a PR that is not the benchmark's may not edit it
    assert 0.94 * recorded["temp"] < ma.temp_size_in_bytes <= recorded["temp"]
    assert cell.config["bytes"]["parameters"] == model.num_params() \
        == cell.family().total_params(cell.sizes)


def test_the_looped_cells_step_holds_one_accumulator_of_the_shared_gradients(
        one_chip):
    """``ouro-2.6b.train-b2-s4096``, built from its configuration's own file
    through its family's flags: the whole step (2 x 4,096 tokens, eight
    layers at the published widths run four times, remat, adam on f32
    masters) for the described chip. The passes are ONE loop of four trips
    forward and one backward, each holding the eight layers once: the
    backward loop carries one f32 accumulator a shared matrix (not four),
    the attention kernels are in the loops' bodies once a layer and the
    remat pass runs none; and ``memory_analysis()`` holds the arguments the
    configuration's ``bytes`` records and no more temporaries than it
    records."""
    from benchmark.harness import manifest
    from distributed_tensorflow_tpu.data.device_data import DeviceData
    from distributed_tensorflow_tpu.utils import telemetry

    cell = manifest.load_cell("ouro-2.6b.train-b2-s4096")
    trainer = cell.config["trainer"]
    seq, batch = cell.mix["seq_len"], cell.mix["batch_per_chip"]
    model = TransformerLM(
        seq_len=seq, compute_dtype=jnp.bfloat16,
        attn_block=trainer["attn_block"], ce_block=trainer["ce_block"],
        remat=trainer["remat"], loop_exit_beta=trainer["loop_exit_beta"],
        **cell.family().trainer_flags(cell.config, cell.mix))
    opt = adam(trainer["learning_rate"])
    state = jax.eval_shape(lambda: create_train_state(model, opt, seed=0))
    data = DeviceData(jax.ShapeDtypeStruct((4096, seq), jnp.uint16),
                      jax.ShapeDtypeStruct((4096, seq), jnp.uint16))
    step = make_device_train_step(model, opt, batch, keep_prob=1.0, chunk=1)
    telemetry.get_tracer().clear()
    compiled = step.lower(*_on(one_chip, (state, data))).compile()
    notes = {r["name"]: r for r in telemetry.last_spans(200)}
    plan = notes["loop_plan"]
    assert (plan["passes"], plan["layers"], plan["lowered"]) == (4, 8, "scan")
    # a pass keeps 8 x (the block's input + out + logsumexp): 541 MB
    assert plan["kept_bytes_per_pass"] == 8 * (
        2 * 4096 * 2048 * 2 + notes["remat_saved"]["bytes_per_block"]) \
        == 541_065_216
    assert _attention_grids([notes["attention_path"]])[0][3:] == (36, 36, 8)
    hlo = compiled.as_text()
    kernels = {}
    for p in re.findall(
            r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo):
        assert "/attention" in p and "loop_exit" not in p
        key = (re.search(r"flash_attention_\w+", p).group(0),
               "rematted_computation" in p)
        kernels[key] = kernels.get(key, 0) + 1
    assert kernels == {("flash_attention_fwd", False): 8,
                       ("flash_attention_bwd", False): 8}
    entry = hlo[hlo.index("ENTRY"):]
    loops = {re.search(r'op_name="([^"]*)"', line).group(1).rsplit(
        "closed_call/", 1)[1]: re.match(
            r"\s*%[\w.-]+ = (\(.*?\)) while\(", line).group(1)
        for line in entry.splitlines()
        if " while(" in line and "lm_head" not in line}
    # the scan over passes is under no scope of the catalog
    assert set(loops) == {"jvp()/while", "transpose(jvp())/while"}
    forward, backward = loops["jvp()/while"], loops["transpose(jvp())/while"]
    for shape in ("2048,11264", "5632,2048", "2048,2048", "2048,3,16,128"):
        assert len(re.findall(rf"f32\[{shape}\]", backward)) == 8, shape
        assert not re.findall(rf"f32\[{shape}\]", forward)
    # the passes' outputs stacked for the head: one (T, B, S, d) of bf16
    assert "bf16[4,2,4096,2048]" in forward
    assert "f32[4,2,4096,2048]" not in forward
    ma = compiled.memory_analysis()
    recorded = cell.config["bytes"]["compiled_step_for_described_v5e"]
    assert ma.argument_size_in_bytes == recorded["arguments"]
    # 225,792 bytes past the record since the attention kernels take their
    # tables of live tiles (PR 37; the two kernels alone compile to the
    # temporaries they had): the file is a benchmark PR's to correct
    assert 0.97 * recorded["temp"] < ma.temp_size_in_bytes \
        <= recorded["temp"] + 2 ** 20
    assert _device_bytes(compiled) < 0.9 * V5E_HBM_BYTES
    assert cell.config["bytes"]["parameters"] == model.num_params() \
        == cell.family().total_params(cell.sizes)


def test_the_linear_cells_step_compiles_with_the_chunked_rule_and_dh_256_fused(
        one_chip):
    """``qwen3-next-80b-a3b.train-b1-s16384``, built from its configuration's
    own file through its family's flags: the whole step (1 x 16,384 tokens,
    three linear layers and a gated full-attention layer at the published
    widths, remat, adam on f32 masters) for the described chip. The full
    layer's attention lowers to the flash kernels at a head width of 256
    under ``attention`` (528 of 1,024 tiles a head); each linear layer's
    core is under ``linear_attention``, in chunks of 64 (256 a sequence), its
    ``(I + A)^-1`` the compiler's triangular inverse, not a kernel of ours;
    the experts' products are Mosaic kernels; and ``memory_analysis()``
    holds the arguments the configuration's ``bytes`` records and no more
    temporaries than it records."""
    from benchmark.harness import manifest
    from distributed_tensorflow_tpu.data.device_data import DeviceData
    from distributed_tensorflow_tpu.utils import telemetry

    cell = manifest.load_cell("qwen3-next-80b-a3b.train-b1-s16384")
    trainer = cell.config["trainer"]
    seq, batch = cell.mix["seq_len"], cell.mix["batch_per_chip"]
    model = TransformerLM(
        seq_len=seq, compute_dtype=jnp.bfloat16,
        attn_block=trainer["attn_block"], ce_block=trainer["ce_block"],
        remat=trainer["remat"], moe_capacity=trainer["moe_capacity"],
        **cell.family().trainer_flags(cell.config, cell.mix))
    opt = adam(trainer["learning_rate"])
    state = jax.eval_shape(lambda: create_train_state(model, opt, seed=0))
    data = DeviceData(jax.ShapeDtypeStruct((4096, seq), jnp.uint16),
                      jax.ShapeDtypeStruct((4096, seq), jnp.uint16))
    step = make_device_train_step(model, opt, batch, keep_prob=1.0, chunk=1)
    telemetry.get_tracer().clear()
    compiled = step.lower(*_on(one_chip, (state, data))).compile()
    notes = telemetry.last_spans(200)
    assert _attention_grids(notes) == [
        ("backward", "causal", "fused", 528, 528, 32),
        ("forward", "causal", "fused", 528, 528, 32)]
    path = {r["name"]: r for r in notes}["linear_attention_path"]
    assert (path["chunk"], path["chunks"], path["state_bytes_per_head"]) == (
        64, 256, 128 * 128 * 4)
    hlo = compiled.as_text()
    kernels = {}
    for p in re.findall(
            r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo):
        scope = [e for e in re.findall(r"[A-Za-z_]\w*", p)
                 if e in telemetry.SCOPES][-1]
        name = re.search(r"flash_attention_\w+|tgmm|gmm", p).group(0)
        kernels[scope, name] = kernels.get((scope, name), 0) + 1
    assert kernels == {
        ("attention", "flash_attention_fwd"): 1,
        ("attention", "flash_attention_bwd"): 1,
        ("moe_experts", "gmm"): 4 * 6, ("moe_experts", "tgmm"): 4 * 2}
    solves = re.findall(
        r'custom_call_target="InvertDiagBlocksLowerTriangular".*?'
        r'op_name="([^"]*)"', hlo)
    assert solves and all("linear_attention" in p for p in solves)
    ma = compiled.memory_analysis()
    recorded = cell.config["bytes"]["compiled_step_for_described_v5e"]
    assert ma.argument_size_in_bytes == recorded["arguments"]
    assert 0.97 * recorded["temp"] < ma.temp_size_in_bytes <= recorded["temp"]
    assert _device_bytes(compiled) == recorded["total"] < 0.85 * 16.91e9
    assert cell.config["bytes"]["parameters"] == model.num_params() \
        == cell.family().total_params(cell.sizes)
