"""The block-diffusion mixture-of-experts configuration at a small size on the
CPU: the program (``TransformerLM`` under ``--objective masked_diffusion
--moe_top_k`` through ``make_device_train_step``) held to the plain reference
of its family (``benchmark/reference/sdar_moe.py``), and the mechanisms it
brought, each held to something written independently:

- loss, every leaf's gradient and the change after three Adam steps, in f32
  to 1e-5 and in bf16 within a band that the float8 control fails;
- the share ties to the whole: the routed layer's output under the four
  shares of 16 experts adds up to the reference's uncut layer;
- the block-diffusion mask in scan and (interpreted) kernel form against the
  dense mask of the family's equation 4, values and gradients; the causal
  case bit-equal to the form it had before the mask became a description;
- dropless routing: no row lost however the router piles them up, and the
  overflow counter rises only past the stated capacity;
- the experts' products stop at the last pair: what the kernels leave
  unwritten past it (NaN standing in for it here) reaches no output and no
  gradient, and ``tiles_run_frac`` is the kernel's own count of its work;
- the dispatch stops there too: gather, add-back and their gradients are
  loops over the buffer's live row tiles (a ``while`` with a traced bound),
  held to a plain per-expert layer at every fill, with NaN in every row
  nobody wrote, and ``dispatch_tiles_frac`` is the tiles they ran;
- the streamed head's row weights, the noise's key, the flags' validators.

d 64, 4 query / 2 key-value heads of width 16, 16 experts of width 32 with
4 a token and 4 held, S 64, L_b 4, 2 layers.
"""

import contextlib
import functools
import json
import os
import re
import statistics
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.harness import manifest
from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.data.device_data import DeviceData
from distributed_tensorflow_tpu.data.lm import LMDataSet
from distributed_tensorflow_tpu.models import get_model
from distributed_tensorflow_tpu.ops import attention, flash_attention, moe, nn
from distributed_tensorflow_tpu.ops.attention import (
    CAUSAL,
    Mask,
    blockwise_attention,
    multi_head_attention,
)
from distributed_tensorflow_tpu.training import (
    create_train_state,
    get_optimizer,
)
from distributed_tensorflow_tpu.training.device_step import (
    make_device_train_step,
)
from distributed_tensorflow_tpu.training.train_state import (
    loss_and_metrics,
    make_eval_step,
)
from distributed_tensorflow_tpu.utils import telemetry
from tests.mask_tables import assert_tables_follow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = manifest.load_family(
    os.path.join(REPO, "benchmark", "reference", "sdar_moe.py"))
SIZES = {"d_model": 64, "num_heads": 4, "kv_heads": 2, "head_dim": 16,
         "num_blocks": 2, "router_width": 16, "held_experts": 4,
         "first_expert": 4, "top_k": 4, "expert_dim": 32, "vocab_size": 300,
         "norm_eps": 1e-6, "rope_theta": 1e6, "block_length": 4,
         "t_min": 1e-3, "seq_len": 64}
SEED, ROWS, LR = 7, 4, 1e-3


def small_model(compute_dtype=None, **over):
    kw = dict(vocab_size=300, seq_len=64, d_model=64, num_heads=4,
              num_blocks=2, norm="rmsnorm", norm_eps=1e-6, rope_theta=1e6,
              num_kv_heads=2, head_dim=16, qk_norm=True, mlp_gated=True,
              biases=False, moe_experts=16, moe_top_k=4, moe_ffn_dim=32,
              moe_first_expert=4, moe_held_experts=4, moe_capacity=4.0,
              objective="masked_diffusion", diffusion_block=4, attn_block=16,
              ce_block=16, remat=True, compute_dtype=compute_dtype,
              noise_seed=SEED)
    kw.update(over)
    return get_model("lm", **kw)


def small_data():
    ds = LMDataSet(4096, 64, 300, seed=SEED, reserved_ids=1)
    return ds, DeviceData(jnp.asarray(ds.images), jnp.asarray(ds.labels))


@functools.lru_cache(maxsize=None)
def program(dtype_name):
    """Three steps of the trainer's own compiled step from the seed: the
    losses, the first gradient's leaves, the leaves' changes."""
    cd = {"f32": None, "bf16": jnp.bfloat16}[dtype_name]
    model = small_model(cd)
    opt = get_optimizer("adam", LR)
    state = create_train_state(model, opt, seed=SEED)
    step = make_device_train_step(model, opt, ROWS, chunk=1, donate=False)
    start, losses, first = state.params, [], None
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            state, metrics = step(state, small_data()[1])
            losses.append(float(metrics["loss"]))
            if i == 0:  # Adam's m after one step is (1 - b1) x the gradient
                first = [np.asarray(m) / 0.1
                         for m in jax.tree.leaves(state.opt_state["m"])]
    names = FAMILY.leaf_names(state.params)
    change = {n: float(jnp.linalg.norm(a - b)) for n, a, b in zip(
        names, jax.tree.leaves(state.params), jax.tree.leaves(start))}
    norms = {n: float(np.linalg.norm(g)) for n, g in zip(names, first)}
    return {"losses": losses, "grad_norms": norms, "change_norms": change,
            "first_gradient": first, "names": names}


@functools.lru_cache(maxsize=None)
def reference(precision="f32"):
    batches = FAMILY.first_batches(SEED, 3, SIZES, ROWS, 1)
    return FAMILY.first_steps(SEED, SIZES, batches, LR, precision=precision,
                              keep_first_gradient=True)


def gradient_shares(other, ref):
    """|other's first gradient - the reference's| / |the reference's|, by
    leaf, against the median leaf's norm where a leaf's is smaller."""
    floor = statistics.median(ref["grad_norms"].values())
    names = list(ref["grad_norms"])
    return {n: float(np.linalg.norm(np.asarray(a, np.float32) - b))
            / max(ref["grad_norms"][n], floor)
            for n, a, b in zip(names, other, ref["first_gradient"])}


# ---- the program against the reference --------------------------------------

def test_f32_program_matches_the_reference_loss_gradients_and_change():
    prog, ref = program("f32"), reference()
    assert prog["names"] == list(ref["grad_norms"])  # the same leaves
    for p, r in zip(prog["losses"], ref["losses"]):
        assert abs(p - r) / r < 1e-5
    for n, a, b in zip(prog["names"], prog["first_gradient"],
                       ref["first_gradient"]):
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b) + 1e-12, n
    for n in prog["names"]:
        assert abs(prog["change_norms"][n] - ref["change_norms"][n]) \
            <= 1e-4 * ref["change_norms"][n], n


def test_bf16_program_keeps_a_band_that_the_float8_control_fails():
    """bf16 rounds operands and results to 8 bits: the median leaf's
    gradient differs by about 1 % from the f32 reference's, the worst (a
    routed leaf: a row whose fourth and fifth expert swap under rounding
    moves whole rows of its gradient) by 4 %; the float8 control, the
    reference's own arithmetic three bits shorter, reads 10 % and 33 %."""
    prog, ref, control = program("bf16"), reference(), reference("fp8")
    for p, r in zip(prog["losses"], ref["losses"]):
        assert abs(p - r) / r < 2e-4
    shares = gradient_shares(prog["first_gradient"], ref)
    assert statistics.median(shares.values()) < 0.03
    assert max(shares.values()) < 0.12
    assert max(abs(prog["change_norms"][n] - ref["change_norms"][n])
               / ref["change_norms"][n] for n in shares) < 0.05
    fp8 = gradient_shares(control["first_gradient"], ref)
    assert statistics.median(fp8.values()) > 0.06
    assert max(fp8.values()) > 0.12


# ---- the share ties to the whole ---------------------------------------------

def _uncut_layer(seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    b = jax.random.normal(k[0], (2, 64, 64))
    full = {"router": jax.random.normal(k[1], (64, 16)) * 0.5,
            "w1": jax.random.normal(k[2], (16, 64, 64)) * 0.1,
            "w2": jax.random.normal(k[3], (16, 32, 64)) * 0.1}
    return b, full


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    b, full = _uncut_layer()
    with jax.default_matmul_precision("highest"):
        whole = FAMILY.routed_layer(b.reshape(-1, 64), full, SIZES, first=0)
        total, unrouted = 0.0, []
        for first in (0, 4, 8, 12):
            share = {"router": full["router"],
                     "w1": full["w1"][first:first + 4],
                     "w2": full["w2"][first:first + 4]}
            y, aux = moe.routed_experts(b, share, top_k=4, first_expert=first,
                                        capacity_factor=4.0)
            assert float(aux["overflow_rows"]) == 0
            # the reference's share is the program's share
            mine = FAMILY.routed_layer(b.reshape(-1, 64), share, SIZES,
                                       first=first)
            np.testing.assert_allclose(y.reshape(-1, 64), mine, atol=2e-6)
            total = total + y
            unrouted.append(float(aux["unrouted_frac"]))
    np.testing.assert_allclose(total.reshape(-1, 64), whole, atol=5e-6)
    # every row chose 4 of 16: none is unrouted under all four shares
    assert all(0.0 < u < 1.0 for u in unrouted)


# ---- the mask ----------------------------------------------------------------

@contextlib.contextmanager
def kernels_interpreted():
    """The dispatch as a TPU lowering would make it, the kernels run by
    Pallas's generic interpreter (as ``tests/test_flash_kernel.py``)."""
    def clear():
        flash_attention.flash_forward.clear_cache()
        flash_attention.flash_backward.clear_cache()

    by_platform = attention._by_platform
    pallas_call = flash_attention.pl.pallas_call
    clear()
    attention._by_platform = lambda fused, scan, *args: fused(*args)
    flash_attention.pl.pallas_call = functools.partial(pallas_call,
                                                       interpret=True)
    try:
        yield
    finally:
        attention._by_platform = by_platform
        flash_attention.pl.pallas_call = pallas_call
        clear()


def _qkvg(s2, heads, kv_heads, dh, dtype, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    shapes = [(2, s2, heads, dh), (2, s2, kv_heads, dh), (2, s2, kv_heads, dh),
              (2, s2, heads, dh)]
    return [jax.random.normal(key, shape).astype(dtype)
            for key, shape in zip(k, shapes)]


def _value_and_grads(fn, q, k, v, g):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(g.astype(out.dtype))


def _dense_by_equation_4(q, k, v, g, seq, lb):
    """Dense softmax attention under the reference's own mask, f32."""
    mask = FAMILY.dense_mask(seq, lb)

    def fn(q, k, v):
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    return _value_and_grads(fn, *(x.astype(jnp.float32) for x in (q, k, v)),
                            g)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("seq,lb", [(64, 4), (128, 128), (96, 12)])
def test_mask_description_is_equation_4(seq, lb):
    mask = Mask("block_diffusion", seq, lb)
    rows = jnp.arange(2 * seq)
    got = mask.allowed(rows[:, None], rows[None, :])
    assert np.array_equal(np.asarray(got), np.asarray(
        FAMILY.dense_mask(seq, lb)))
    assert int(got.sum()) == seq * seq + seq * lb  # a quarter, and L_b S


@pytest.mark.parametrize("seq,lb,tq,tk", [
    (256, 4, 128, 128), (256, 128, 128, 128), (512, 128, 256, 256),
    (512, 4, 256, 128), (512, 64, 128, 256)])
def test_tiles_and_tables_follow_the_dense_mask(seq, lb, tq, tk):
    """The three-way test of a tile and the kernels' two tables
    (``live_tiles``), against the dense mask cut into the same tiles."""
    mask = Mask("block_diffusion", seq, lb)
    dense = np.asarray(FAMILY.dense_mask(seq, lb))
    nq, nk = 2 * seq // tq, 2 * seq // tk
    for i in range(nq):
        for j in range(nk):
            tile = dense[i * tq:(i + 1) * tq, j * tk:(j + 1) * tk]
            visible, some = mask.tile(i * tq, (i + 1) * tq - 1,
                                      j * tk, (j + 1) * tk - 1)
            assert (bool(visible), bool(some)) == (tile.all(), tile.any())
    cut = dense.reshape(nq, tq, nk, tk)
    some, every = cut.any(axis=(1, 3)), cut.all(axis=(1, 3))
    assert assert_tables_follow(mask, 2 * seq, tq, tk) == (
        some.sum(), (some & ~every).sum())


def test_eighty_of_the_cells_256_tiles_run():
    mask = Mask("block_diffusion", 4096, 4)
    i, j = np.arange(16)[:, None] * 512, np.arange(16)[None, :] * 512
    visible, runs = mask.tile(i, i + 511, j, j + 511)
    # 28 + 8 noised -> clean, 28 + 8 clean -> clean, 8 on the noised diagonal
    assert (int(np.sum(runs)), int(np.sum(visible))) == (80, 56)


@pytest.mark.parametrize("seq,lb,tile", [(256, 4, 128), (256, 128, 128),
                                         (512, 128, 256), (192, 12, 64)])
def test_scan_form_matches_the_dense_mask(seq, lb, tile):
    mask = Mask("block_diffusion", seq, lb)
    q, k, v, g = _qkvg(2 * seq, 4, 2, 16, jnp.float32)
    want = _dense_by_equation_4(q, k, v, g, seq, lb)
    got = _value_and_grads(
        lambda q, k, v: blockwise_attention(q, k, v, tile, mask=mask),
        q, k, v, g)
    dense = _value_and_grads(
        lambda q, k, v: multi_head_attention(q, k, v, mask=mask), q, k, v, g)
    for a, b, c in zip(got, dense, want):
        assert _rel(a, c) < 1e-5 and _rel(b, c) < 1e-5


# L_b 4: every tile holds many blocks; L_b 128 at a 128 tile: the blocks are
# the tiles; L_b 128 at a 256 tile: the block boundary crosses the tile
@pytest.mark.parametrize("seq,lb,tile", [(256, 4, 128), (256, 128, 128),
                                         (512, 128, 256)])
def test_kernel_form_matches_the_dense_mask(seq, lb, tile):
    mask = Mask("block_diffusion", seq, lb)
    q, k, v, g = _qkvg(2 * seq, 4, 2, 64, jnp.bfloat16)
    assert attention.fusable(q, k, v, tile, mask)
    want = _dense_by_equation_4(q, k, v, g, seq, lb)
    with kernels_interpreted():
        got = _value_and_grads(
            lambda q, k, v: blockwise_attention(q, k, v, tile, mask=mask),
            q, k, v, g)
    assert got[2].shape == k.shape and got[3].shape == v.shape
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-2  # bf16 keeps 8 bits


def test_kernel_form_with_several_live_ranges_a_row(monkeypatch):
    """Tiles a quarter of the half on both sides: a noised query tile's
    live key tiles are two ranges with a gap between them (its own noised
    tile, then the clean tiles up to its own), a clean key tile's query
    tiles likewise; values and gradients against the dense mask and the
    scan, and the grid is the 24 of 64 tiles that run."""
    monkeypatch.setattr(flash_attention, "MAX_QUERY_TILE", 128)
    seq, lb, tile = 512, 4, 128
    mask = Mask("block_diffusion", seq, lb)
    qi, kj, _ = mask.live_tiles(2 * seq, tile, tile)
    assert list(kj[qi == 2]) == [2, 4, 5, 6] and qi.size == 24
    q, k, v, g = _qkvg(2 * seq, 4, 2, 64, jnp.bfloat16, seed=3)
    assert attention.fusable(q, k, v, tile, mask)
    want = _dense_by_equation_4(q, k, v, g, seq, lb)

    def attend(q, k, v):
        return blockwise_attention(q, k, v, tile, mask=mask)

    scan = _value_and_grads(attend, q, k, v, g)
    telemetry.get_tracer().clear()
    with kernels_interpreted():
        got = _value_and_grads(attend, q, k, v, g)
    notes = [n for n in telemetry.last_spans(100)
             if n["name"] == "attention_path"]
    assert [(n["path"], n["q_tile"], n["tiles_run"], n["grid_steps"],
             n["masked_tiles"]) for n in notes] == [
        ("fused", 128, 24, 24, 12)] * 2
    for name, a, b, c in zip(("out", "dq", "dk", "dv"), got, want, scan):
        assert _rel(a, b) < 1e-2 and _rel(a, c) < 1e-2, name


@pytest.mark.parametrize("why,seq,lb,tile,dtype", [
    ("a block that is no power of two", 384, 96, 128, jnp.bfloat16),
    ("a tile that straddles the halves", 384, 4, 256, jnp.bfloat16),
    ("f32 operands", 256, 4, 128, jnp.float32)])
def test_what_the_kernels_cannot_cut_takes_the_scan(why, seq, lb, tile, dtype):
    q, k, v, _ = _qkvg(2 * seq, 4, 2, 64, dtype)
    assert not attention.fusable(q, k, v, tile,
                                 Mask("block_diffusion", seq, lb)), why


def _scan_as_it_was(q, k, v, block):
    """The causal forward scan as the repository had it before the mask
    became a description (PR 26), written out: the case the new form must
    reproduce to the bit."""
    b, sq, h, dh = q.shape
    n = k.shape[1] // block
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    qf, rows = q.astype(jnp.float32), jnp.arange(sq)
    kb = jnp.moveaxis(k.reshape(b, n, block, h, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, n, block, h, dh), 1, 0)

    def step(carry, inp):
        o, m, l = carry
        t, k_blk, v_blk = inp
        cols = t * block + jnp.arange(block)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
        s = jnp.where((cols[None, :] <= rows[:, None])[None, None],
                      s * scale, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr, p = jnp.exp(m - m_new), jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        return (o, m_new, l), None

    (o, m, l), _ = lax.scan(
        step, (jnp.zeros((b, h, sq, dh), jnp.float32),
               jnp.full((b, h, sq), -jnp.inf, jnp.float32),
               jnp.zeros((b, h, sq), jnp.float32)), (jnp.arange(n), kb, vb))
    return jnp.einsum("bhqd->bqhd", o / l[..., None]).astype(q.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_causal_case_is_bit_equal_to_what_it_was(dtype):
    q, k, v, g = _qkvg(128, 2, 2, 16, dtype)
    as_flag = _value_and_grads(
        lambda q, k, v: blockwise_attention(q, k, v, 32, causal=True),
        q, k, v, g)
    as_mask = _value_and_grads(
        lambda q, k, v: blockwise_attention(q, k, v, 32, mask=CAUSAL),
        q, k, v, g)
    for a, b in zip(as_flag, as_mask):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(as_flag[0]),
                          np.asarray(jax.jit(_scan_as_it_was, static_argnums=3)(
                              q, k, v, 32)))
    # the kernels are handed the causal description: query tile 3's keys
    # stop at its diagonal, key tile 5's queries start there
    qi, kj, _ = Mask("causal").live_tiles(8192, 512, 512)
    assert list(kj[qi == 3]) == [0, 1, 2, 3]
    qi, kj, _ = Mask("causal").live_tiles(8192, 512, 512, key_major=True)
    assert list(qi[kj == 5]) == list(range(5, 16))


# ---- dropless ----------------------------------------------------------------

def test_a_router_forced_onto_one_expert_loses_no_row():
    b, full = _uncut_layer(1)
    # every row's largest probability is expert 5's, by a wide margin
    router = full["router"].at[:, 5].set(0.0)
    b = b.at[..., 0].set(50.0)
    router = router.at[0, 5].set(10.0)
    share = {"router": router, "w1": full["w1"][4:8], "w2": full["w2"][4:8]}
    rows = b.shape[0] * b.shape[1]
    with jax.default_matmul_precision("highest"):
        want = FAMILY.routed_layer(b.reshape(-1, 64), share, SIZES, first=4)
        y, aux = moe.routed_experts(b, share, top_k=4, first_expert=4,
                                    capacity_factor=4.0)
    assert float(aux["rows_per_expert_max"]) == rows  # all of them on one
    assert float(aux["overflow_rows"]) == 0
    np.testing.assert_allclose(y.reshape(-1, 64), want, rtol=2e-5, atol=2e-5)


def test_the_overflow_counter_rises_only_past_the_stated_capacity(
        monkeypatch):
    b, full = _uncut_layer(2)
    share = {"router": full["router"], "w1": full["w1"][:4],
             "w2": full["w2"][:4]}
    rows = b.shape[0] * b.shape[1]
    _, roomy = moe.routed_experts(b, share, top_k=4, capacity_factor=4.0)
    pairs = 4 * float(roomy["rows_per_expert_mean"])
    assert float(roomy["overflow_rows"]) == 0
    assert float(roomy["buffer_fill"]) == pytest.approx(
        pairs / moe.routed_capacity(rows, 4, 4, 16, 4.0))
    # the buffer is never smaller than one row tile: make the tile small
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    cap = moe.routed_capacity(rows, 4, 4, 16, 0.5)
    _, tight = moe.routed_experts(b, share, top_k=4, capacity_factor=0.5)
    assert cap < pairs and float(tight["overflow_rows"]) == pairs - cap
    assert float(tight["buffer_fill"]) == pytest.approx(pairs / cap)


def test_an_overflow_is_a_failed_step_not_a_silent_drop(monkeypatch):
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    model = small_model(moe_capacity=0.25)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (4, 64), 0, 299)
    batch = model.noise_batch((x, x), jax.random.key(2))
    loss, metrics = jax.jit(functools.partial(
        model.loss_with_metrics, train=True))(params, *batch)
    assert float(metrics["moe_overflow_rows"]) > 0 and np.isnan(float(loss))
    assert float(metrics["moe_buffer_fill_max"]) > 1
    roomy = small_model()
    loss, metrics = jax.jit(functools.partial(
        roomy.loss_with_metrics, train=True))(params, *batch)
    assert float(metrics["moe_overflow_rows"]) == 0 and np.isfinite(float(loss))
    assert float(metrics["moe_buffer_fill_max"]) <= 1


def test_remat_keeps_the_counters_the_loss_and_the_gradients():
    """The routed block under ``remat=True`` (its attention's out and
    logsumexp kept, the rest made again in the backward pass) still
    returns its routing counters, and the loss and every leaf's gradient
    are those of ``remat=False``."""
    x = jax.random.randint(jax.random.key(1), (4, 64), 0, 299)
    got = {}
    for remat in (False, True):
        model = small_model(remat=remat)
        params = model.init(jax.random.key(0))
        batch = model.noise_batch((x, x), jax.random.key(2))
        (loss, metrics), grads = jax.jit(jax.value_and_grad(
            functools.partial(model.loss_with_metrics, train=True),
            has_aux=True))(params, *batch)
        got[remat] = (float(loss), jax.device_get(metrics),
                      jax.tree.leaves(grads))
    (loss, metrics, grads), (r_loss, r_metrics, r_grads) = got[0], got[1]
    counters = {k for k in metrics if k.startswith("moe_")}
    assert {"moe_rows_per_expert_max", "moe_rows_per_expert_mean",
            "moe_overflow_rows", "moe_buffer_fill_max",
            "moe_tiles_run_frac", "moe_dispatch_tiles_frac",
            "moe_unrouted_frac"} <= counters
    assert metrics.keys() == r_metrics.keys()
    for k in metrics:
        assert float(metrics[k]) == float(r_metrics[k]), k
    assert loss == r_loss and np.isfinite(loss)
    for a, b in zip(grads, r_grads):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


def test_capacity_is_a_multiple_of_the_row_tile_and_never_past_every_pair():
    assert moe.routed_capacity(32768, 8, 16, 128, 1.25) == 40960
    assert moe.routed_capacity(32768, 8, 16, 128, 100.0) == 32768 * 8
    assert moe.routed_capacity(16, 2, 4, 8, 1.0) == moe.ROW_TILE


# ---- the products stop at the last pair ---------------------------------------

def test_grouped_matmul_with_rows_past_the_last_group():
    """``sum(sizes) < m``: the groups' rows are the full product's, the
    matrices' gradient sums over the groups' rows alone whatever ``lhs`` and
    the incoming gradient hold past them; what comes back for those rows is
    the caller's to select away."""
    k = jax.random.split(jax.random.key(3), 3)
    sizes = jnp.array([5, 0, 7], jnp.int32)
    live, group = 12, np.repeat(np.arange(3), [5, 0, 7])
    lhs = jax.random.normal(k[0], (24, 8)).at[live:].set(jnp.nan)
    rhs = jax.random.normal(k[1], (3, 8, 16))
    g = jax.random.normal(k[2], (24, 16)).at[live:].set(jnp.nan)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda a, b: moe.grouped_matmul(a, b, sizes),
                           lhs, rhs)
        dl, dr = vjp(g)
    want = np.einsum("mk,mkn->mn", lhs[:live], np.asarray(rhs)[group])
    np.testing.assert_allclose(out[:live], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        dl[:live], np.einsum("mn,mkn->mk", g[:live], np.asarray(rhs)[group]),
        rtol=1e-5, atol=1e-5)
    want_dr = np.zeros((3, 8, 16), np.float32)
    np.add.at(want_dr, group,
              np.einsum("mk,mn->mkn", lhs[:live], g[:live]))
    np.testing.assert_allclose(dr, want_dr, rtol=1e-5, atol=1e-5)
    assert not np.any(want_dr[1]) and np.all(np.isfinite(dr))


def _poisoned(fn, which):
    """``fn`` (a grouped product or its gradients) leaving NaN where the
    Pallas kernels leave memory unwritten: the rows past ``sum(sizes)`` of
    the result, or of the gradient with respect to the rows."""
    def run(lhs, rhs, sizes, *g):
        out = fn(lhs, rhs, sizes, *g)
        dead = (jnp.arange(lhs.shape[0]) >= jnp.sum(sizes))[:, None]
        if which == "result":
            return jnp.where(dead, jnp.nan, out)
        return jnp.where(dead, jnp.nan, out[0]), out[1]
    return run


def test_what_the_products_leave_unwritten_is_never_read_as_a_number(
        monkeypatch):
    """On the chip the rows past the last pair are uninitialised memory in
    the products' results and in their gradients with respect to the rows.
    With NaN standing there, the layer's output and the gradients of its
    input, its router and both expert matrices are finite and those of the
    plain run: each of those rows is selected away or dropped by index,
    never multiplied by a weight of nought."""
    b, full = _uncut_layer(4)
    share = {"router": full["router"], "w1": full["w1"][4:8],
             "w2": full["w2"][4:8]}
    probe = jax.random.normal(jax.random.key(5), b.shape)

    def run(b, share):
        def loss(b, share):
            y, aux = moe.routed_experts(b, share, top_k=4, first_expert=4,
                                        capacity_factor=4.0)
            return jnp.sum(y * probe), (y, aux)
        (_, (y, aux)), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(b, share)
        return y, aux, jax.tree.leaves(grads)

    with jax.default_matmul_precision("highest"):
        y, aux, grads = run(b, share)
        assert 0 < float(aux["buffer_fill"]) < 1  # there are such rows
        monkeypatch.setattr(moe, "_ragged", _poisoned(moe._ragged, "result"))
        monkeypatch.setattr(moe, "_ragged_grads",
                            _poisoned(moe._ragged_grads, "gradients"))
        # the stand-ins do poison what grouped_matmul hands back
        sizes = jnp.array([3, 2], jnp.int32)
        out, vjp = jax.vjp(
            lambda a: moe.grouped_matmul(a, jnp.ones((2, 4, 4)), sizes),
            jnp.ones((8, 4)))
        assert np.all(np.isnan(out[5:])) and np.all(np.isfinite(out[:5]))
        assert np.all(np.isnan(vjp(jnp.ones((8, 4)))[0][5:]))
        p_y, p_aux, p_grads = run(b, share)
    assert np.all(np.isfinite(p_y))
    np.testing.assert_array_equal(p_y, y)
    assert len(grads) == len(p_grads) == 4
    for want, got in zip(grads, p_grads):
        assert np.all(np.isfinite(got)) and np.any(got)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("why,counts", [
    ("an empty group", [5, 0, 9, 3]),
    ("a group ending on a tile's edge", [16, 5, 0, 2]),
    ("every group on an edge", [8, 8, 16, 8]),
    ("all pairs in one group", [0, 0, 37, 0]),
    ("groups cut inside one tile", [3, 2, 1, 1]),
    ("a full buffer", [16, 16, 16, 16]),
    ("an overflow", [30, 30, 30, 30]),
    ("no pair at all", [0, 0, 0, 0]),
])
def test_tiles_run_is_the_kernels_own_count(why, counts):
    """``row_tiles_run`` against ``num_active_tiles`` of the Pallas grouped
    product's ``make_group_metadata``, for group sizes made as
    ``routed_experts`` makes them (clipped at the buffer's 64 rows)."""
    cap, tile = 64, 8
    ends = np.minimum(np.cumsum(counts), cap)
    sizes = jnp.asarray(np.diff(ends, prepend=0), jnp.int32)
    _, theirs = moe._megablox().make_group_metadata(
        group_sizes=sizes, m=cap, tm=tile, start_group=jnp.int32(0),
        num_nonzero_groups=len(counts), visit_empty_groups=False)
    assert int(moe.row_tiles_run(sizes, tile)) == int(theirs), why
    if why in ("a full buffer", "an overflow"):  # and the tiles cut in two
        assert int(theirs) == {"a full buffer": 8, "an overflow": 10}[why]
    if why == "no pair at all":
        assert int(theirs) == 0


def test_the_layer_reports_the_tiles_its_products_run(monkeypatch):
    """``tiles_run_frac`` is the count above for the layer's own routing
    (made again here from the logits), over the buffer's row tiles."""
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    b, full = _uncut_layer(2)
    share = {"router": full["router"], "w1": full["w1"][:4],
             "w2": full["w2"][:4]}
    rows = b.shape[0] * b.shape[1]
    with jax.default_matmul_precision("highest"):
        _, aux = moe.routed_experts(b, share, top_k=4, capacity_factor=4.0)
        logits = np.asarray(b.reshape(rows, 64) @ full["router"])
    chosen = np.argsort(-logits, axis=-1)[:, :4]
    counts = np.bincount(chosen[chosen < 4], minlength=4)
    cap = moe.routed_capacity(rows, 4, 4, 16, 4.0)
    assert counts.sum() < cap and cap % 8 == 0
    ends = np.cumsum(counts)
    tiles = sum(-(-e // 8) - (e - c) // 8
                for e, c in zip(ends, counts) if c)
    assert float(aux["tiles_run_frac"]) == pytest.approx(tiles / (cap // 8))
    assert float(aux["buffer_fill"]) == pytest.approx(counts.sum() / cap)
    assert float(aux["buffer_fill"]) <= float(aux["tiles_run_frac"]) < 1


def test_an_overflow_still_counts_fails_the_step_and_runs_every_tile(
        monkeypatch):
    """The groups are clipped at the buffer's end: an overflow is counted,
    makes the loss NaN and has the products run every row tile; with room,
    ``moe_tiles_run_frac`` (the layers' mean) is under 1."""
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    params = small_model().init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (4, 64), 0, 299)
    got = {}
    for capacity in (0.25, 4.0):
        model = small_model(moe_capacity=capacity)
        batch = model.noise_batch((x, x), jax.random.key(2))
        got[capacity] = jax.jit(functools.partial(
            model.loss_with_metrics, train=True))(params, *batch)
    loss, metrics = got[0.25]
    assert float(metrics["moe_overflow_rows"]) > 0 and np.isnan(float(loss))
    assert float(metrics["moe_tiles_run_frac"]) >= 1
    loss, metrics = got[4.0]
    assert float(metrics["moe_overflow_rows"]) == 0
    assert np.isfinite(float(loss))
    assert (float(metrics["moe_buffer_fill_max"]) / 2
            < float(metrics["moe_tiles_run_frac"]) < 1)


# ---- the dispatch stops at the last pair --------------------------------------

DISPATCH = {"rows": 24, "d": 16, "experts": 8, "held": 2, "first": 2,
            "top_k": 2, "f": 8, "tile": 8}
# held pairs for a buffer of 2 x the 12 expected: 24 rows, three tiles of 8
FILLS = {"no held pair": 0, "one pair": 1, "a part-full last tile": 11,
         "the pairs end on a tile's edge": 16, "an overflow": 30}


def _dispatch_case(pairs, seed=0):
    """A layer whose logits are written out: ``h``'s first columns are the
    logits (the router is the identity there), so each row's two experts
    are chosen here: two of the six not held, but for ``pairs`` (row, slot)
    places, which go to the two held ones (a row's two slots to both)."""
    z = DISPATCH
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(z["rows"], z["experts"])).astype(np.float32)
    others = [e for e in range(z["experts"])
              if not z["first"] <= e < z["first"] + z["held"]]
    places = [(r, j) for j in range(z["top_k"]) for r in range(z["rows"])]
    chosen = set(places[:pairs])
    for r in range(z["rows"]):
        free = list(rng.permutation(others)[:z["top_k"]])
        for j in range(z["top_k"]):
            e = z["first"] + j if (r, j) in chosen else free[j]
            logits[r, e] += 6.0 + j
    h = rng.normal(size=(z["rows"], z["d"])).astype(np.float32)
    h[:, :z["experts"]] = logits
    router = np.zeros((z["d"], z["experts"]), np.float32)
    router[:z["experts"]] = np.eye(z["experts"])
    k = jax.random.split(jax.random.key(seed), 3)
    w1 = jax.random.normal(k[0], (z["held"], z["d"], 2 * z["f"])) * .3
    w2 = jax.random.normal(k[1], (z["held"], z["f"], z["d"])) * .3
    params = {"router": jnp.asarray(router), "w1": w1, "w2": w2}
    probe = jax.random.normal(k[2], (1, z["rows"], z["d"]))
    return jnp.asarray(h)[None], params, probe


def _plain_layer(h, params, scoring, scale, cap):
    """Every held expert on every row, each row's result weighted where the
    row chose it; of more pairs than the buffer's ``cap`` rows, those past
    it in (expert, row) order add nothing (the step then fails)."""
    z = DISPATCH
    hf = h.reshape(-1, z["d"])
    logits = jnp.dot(hf, params["router"], precision=lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    top_p, top_e = lax.top_k(scores, z["top_k"])
    gate = scale * top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    y, taken = 0.0, 0
    for e in range(z["held"]):
        chose = np.asarray(top_e == z["first"] + e)             # (rows, k)
        rank = taken + np.cumsum(chose.any(-1)) - 1
        taken += int(chose.any(-1).sum())
        weight = jnp.sum(jnp.where(chose, gate, 0.0), axis=-1)
        weight = jnp.where(rank < cap, weight, 0.0)
        up = hf @ params["w1"][e]
        out = (jax.nn.silu(up[:, :z["f"]]) * up[:, z["f"]:]) @ params["w2"][e]
        y = y + weight[:, None] * out
    return y.reshape(h.shape), None


@pytest.mark.parametrize("scoring,scale", [("softmax", 1.0), ("sigmoid", 2.5)])
@pytest.mark.parametrize("fill", sorted(FILLS))
def test_the_dispatch_runs_the_live_tiles_and_equals_the_plain_layer(
        monkeypatch, fill, scoring, scale):
    """``routed_experts`` against ``_plain_layer``: the result and the
    gradients with respect to the rows, the router and both expert matrices,
    at every fill of the buffer; again with NaN in every row no pass wrote
    (what the products leave past the last pair, and the loops' carries past
    the last live tile), bit for bit; and the counter is the tiles the
    pairs reach into."""
    z, pairs = DISPATCH, FILLS[fill]
    monkeypatch.setattr(moe, "ROW_TILE", z["tile"])
    cap = moe.routed_capacity(z["rows"], z["top_k"], z["held"], z["experts"],
                              2.0)
    assert cap == 24
    h, params, probe = _dispatch_case(pairs)

    def run(layer):
        def loss(h, params):
            y, aux = layer(h, params)
            return jnp.sum(y * probe), (y, aux)
        (_, (y, aux)), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(h, params)
        return y, aux, grads

    ours = functools.partial(
        moe.routed_experts, top_k=z["top_k"], first_expert=z["first"],
        capacity_factor=2.0, scoring=scoring, scale=scale)
    with jax.default_matmul_precision("highest"):
        want_y, _, want = run(functools.partial(
            _plain_layer, scoring=scoring, scale=scale, cap=cap))
        y, aux, grads = run(ours)
        monkeypatch.setattr(moe, "_ragged", _poisoned(moe._ragged, "result"))
        monkeypatch.setattr(moe, "_ragged_grads",
                            _poisoned(moe._ragged_grads, "gradients"))
        monkeypatch.setattr(moe, "_unwritten", lambda shape, dtype:
                            jnp.full(shape, jnp.nan, dtype))
        p_y, p_aux, p_grads = run(ours)
    assert float(aux["overflow_rows"]) == max(0, pairs - cap)
    assert float(aux["buffer_fill"]) == pytest.approx(pairs / cap)
    live_tiles = -(-min(pairs, cap) // z["tile"])
    assert float(aux["dispatch_tiles_frac"]) == pytest.approx(
        live_tiles * z["tile"] / cap)
    assert (float(aux["dispatch_tiles_frac"]) == 1) == (fill == "an overflow")
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    for name in ("router", "w1", "w2"):
        np.testing.assert_allclose(grads[1][name], want[1][name],
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(grads[0], want[0], rtol=1e-5, atol=1e-5)
    if pairs:
        assert np.any(np.asarray(grads[1]["w1"])) and np.any(np.asarray(y))
    else:
        assert not np.any(np.asarray(y)) and not np.any(
            np.asarray(grads[1]["w2"]))
    for got, plain in zip(jax.tree.leaves((p_y, p_grads)),
                          jax.tree.leaves((y, grads))):
        assert np.all(np.isfinite(got))
        np.testing.assert_array_equal(got, plain)
    assert {k: float(v) for k, v in p_aux.items()} == \
        {k: float(v) for k, v in aux.items()}


def test_the_dispatch_lowers_to_loops_whose_bound_is_the_routings():
    """The four passes over the buffer are ``while`` loops whose trip count
    is carried into them (the live tiles of this call's routing), in the
    lowered text, and the compiler finds no constant for it either."""
    h, params, probe = _dispatch_case(FILLS["a part-full last tile"])

    def loss(h, params):
        y, aux = moe.routed_experts(h, params, top_k=2, first_expert=2,
                                    capacity_factor=4.0)
        return jnp.sum(y * probe) + aux["dispatch_tiles_frac"]

    lowered = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(h, params)
    text = lowered.as_text()
    # counter and bound are both carried: compare LT, %iterArg, %iterArg_n
    carried = re.findall(
        r"stablehlo\.compare\s+LT, %iterArg\w*, %iterArg\w*", text)
    assert text.count("stablehlo.while") >= 4 and len(carried) >= 4
    loops = [line for line in lowered.compile().as_text().splitlines()
             if " while(" in line and "moe_router" in line]
    assert len(loops) == 4 and not any(
        "known_trip_count" in line for line in loops), loops


# ---- the streamed head, the noise, the flags ---------------------------------

def test_the_streamed_heads_row_weights_and_denominator():
    k = jax.random.split(jax.random.key(0), 4)
    h = jax.random.normal(k[0], (3, 20, 16))
    w = jax.random.normal(k[1], (16, 50)) * 0.3
    labels = jax.random.randint(k[2], (3, 20), 0, 50)
    weights = jnp.where(jax.random.uniform(k[3], (3, 20)) < 0.5,
                        jax.random.uniform(k[0], (3, 20)) * 5, 0.0)

    def direct(h, w):
        logp = jax.nn.log_softmax(h @ w, axis=-1)
        own = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return -jnp.sum(own * weights) / 60.0

    def streamed(h, w):
        return nn.streamed_softmax_ce_head(h, w, None, labels, block=16,
                                           weights=weights,
                                           denominator=60.0)[0]

    np.testing.assert_allclose(streamed(h, w), direct(h, w), rtol=1e-6)
    for a, b in zip(jax.grad(streamed, (0, 1))(h, w),
                    jax.grad(direct, (0, 1))(h, w)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    # weights of one and the row count: today's function, to the bit
    bias = jnp.zeros((50,))
    plain = nn.streamed_softmax_ce_head(h, w, bias, labels, block=16)
    ones = nn.streamed_softmax_ce_head(h, w, bias, labels, block=16,
                                       weights=jnp.ones((3, 20)),
                                       denominator=60.0)
    assert [float(x) for x in plain] == [float(x) for x in ones]


def test_the_noise_comes_from_the_key_and_the_eval_agrees_with_itself():
    model = small_model()
    ds, _ = small_data()
    assert ds.images.max() == 298  # the mask id, 299, is no data id
    x = jnp.asarray(ds.images[:8])
    a = model.noise_batch((x, x), jax.random.key(3))
    b = model.noise_batch((x, x), jax.random.key(3))
    c = model.noise_batch((x, x), jax.random.key(4))
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    noised, clean = np.asarray(a[0][:, :64]), np.asarray(a[0][:, 64:])
    masked = np.asarray(a[1]) > 0
    assert np.array_equal(clean, np.asarray(x))
    assert np.array_equal(noised == model.mask_token, masked)
    assert 0.3 < masked.mean() < 0.7  # t ~ U(0.001, 1): a half on average
    t = 1.0 / np.asarray(a[1])[masked]
    assert t.min() >= 1e-3 and t.max() <= 1.0
    # a block shares its t: the weights of a block's masked positions agree
    blocks = np.asarray(a[1]).reshape(8, 16, 4)
    assert all(len(set(np.round(blk[blk > 0], 5))) <= 1
               for row in blocks for blk in row)
    eval_fn = make_eval_step(model)
    params = model.init(jax.random.key(0))
    y = jnp.asarray(ds.labels[:8])
    one, two = eval_fn(params, (x, y)), eval_fn(params, (x, y))
    assert {k: float(v) for k, v in one.items()} == \
        {k: float(v) for k, v in two.items()}
    assert set(one) >= {"loss", "accuracy", "diffusion_masked_frac",
                        "moe_rows_per_expert_max", "moe_rows_per_expert_mean",
                        "moe_overflow_rows", "moe_unrouted_frac",
                        "moe_buffer_fill_max", "moe_tiles_run_frac",
                        "moe_dispatch_tiles_frac"}
    # about (1 - 4/16)^4 = 0.32 of the rows choose none of the four held
    assert 0.2 < float(one["moe_unrouted_frac"]) < 0.45


def lowered_eval(eval_step, *args):
    """The text of ``make_eval_step``'s program: a model that noises its
    batch gets the jitted eval with its key bound as a keyword."""
    program = getattr(eval_step, "func", eval_step)
    keywords = getattr(eval_step, "keywords", {})
    return program.lower(*args, **keywords).as_text()


def test_the_eval_program_is_the_same_at_every_seed():
    ds, _ = small_data()
    x = jnp.asarray(ds.images[:8])
    batch = (x, jnp.asarray(ds.labels[:8]))
    params = small_model().init(jax.random.key(0))
    texts = [lowered_eval(make_eval_step(small_model(noise_seed=s)),
                          params, batch) for s in (3, 4)]
    assert texts[0] == texts[1]

    # the control: the key folded inside the program bakes the seed in
    def key_inside(seed):
        model = small_model(noise_seed=seed)

        @jax.jit
        def eval_fn(params, batch, model_state=()):
            noised = model.noise_batch(batch, jax.random.fold_in(
                jax.random.PRNGKey(seed), 0xE7A1))
            return loss_and_metrics(model, params, noised, train=False,
                                    model_state=model_state)[1]["metrics"]

        return eval_fn.lower(params, batch).as_text()

    assert key_inside(3) != key_inside(4)

    # a model that noises nothing: the program it had, with no key
    lm = get_model("lm", vocab_size=50, seq_len=16, d_model=32,
                   num_heads=2, num_blocks=1)
    lm_params = lm.init(jax.random.key(0))
    tokens = jnp.zeros((2, 16), jnp.int32)

    @jax.jit
    def eval_fn(params, batch, model_state=()):
        _, aux = loss_and_metrics(lm, params, batch, train=False,
                                  model_state=model_state)
        return aux["metrics"]

    step = make_eval_step(lm)
    assert not hasattr(step, "keywords")
    assert lowered_eval(step, lm_params, (tokens, tokens)) == \
        eval_fn.lower(lm_params, (tokens, tokens)).as_text()


@contextlib.contextmanager
def prng_impl(name):
    prev = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", name)
    try:
        yield
    finally:
        jax.config.update("jax_default_prng_impl", prev)


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
def test_the_eval_noises_under_the_key_it_always_had(impl):
    ds, _ = small_data()
    x = jnp.asarray(ds.images[:8])
    batch = (x, jnp.asarray(ds.labels[:8]))
    with prng_impl(impl):
        model = small_model()
        params = model.init(jax.random.key(0))
        got = make_eval_step(model)(params, batch)
        key = jax.random.fold_in(jax.random.PRNGKey(SEED), 0xE7A1)
        want = jax.jit(lambda p, b: loss_and_metrics(
            model, p, model.noise_batch(b, key),
            train=False)[1]["metrics"])(params, batch)
        other = make_eval_step(small_model(noise_seed=SEED + 1))(params,
                                                                 batch)
    assert key.shape == {"threefry2x32": (2,), "rbg": (4,)}[impl]
    assert {k: float(v) for k, v in got.items()} == \
        {k: float(v) for k, v in want.items()}
    assert float(got["loss"]) != float(other["loss"])
    assert float(got["diffusion_masked_frac"]) != \
        float(other["diffusion_masked_frac"])


CACHED_EVAL = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from distributed_tensorflow_tpu.models import get_model
from distributed_tensorflow_tpu.training.train_state import make_eval_step
from distributed_tensorflow_tpu.utils import resources, telemetry
kw = json.loads(sys.argv[2])
model = get_model("lm", noise_seed=int(sys.argv[3]), **kw)
params = model.init(jax.random.key(0))
x = jnp.arange(8 * 64, dtype=jnp.int32).reshape(8, 64) % 299
eval_step = make_eval_step(model)
sentry = resources.CompileSentry()
resources.activate(sentry=sentry)
resources._install_compile_listener()
metrics = eval_step(params, (x, x))
spans = [r for r in telemetry.last_spans(2048)
         if r["name"] == "compile_backend"]
print(json.dumps({"spans": spans, "loss": float(metrics["loss"])}))
"""


def test_a_new_seed_loads_the_eval_from_the_cache(tmp_path):
    kw = dict(vocab_size=300, seq_len=64, d_model=64, num_heads=4,
              num_blocks=2, norm="rmsnorm", norm_eps=1e-6, rope_theta=1e6,
              num_kv_heads=2, head_dim=16, qk_norm=True, mlp_gated=True,
              biases=False, moe_experts=16, moe_top_k=4, moe_ffn_dim=32,
              moe_first_expert=4, moe_held_experts=4, moe_capacity=4.0,
              objective="masked_diffusion", diffusion_block=4, attn_block=16,
              ce_block=16, remat=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = []
    for seed in (1, 2):
        p = subprocess.run(
            [sys.executable, "-c", CACHED_EVAL, str(tmp_path / "cache"),
             json.dumps(kw), str(seed)],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        out.append(json.loads(p.stdout.strip().splitlines()[-1]))
    first, second = out
    evals = [[r for r in run["spans"] if r["fun"] == "jit(eval_fn)"]
             for run in out]
    assert [r["cache"] for r in evals[0]] == ["miss"]
    assert [r["cache"] for r in evals[1]] == ["hit"]
    assert all(r["cache"] == "hit" for r in second["spans"])
    assert first["loss"] != second["loss"]  # each seed its own noise


def test_todays_flags_build_todays_tree():
    first = get_model("lm", vocab_size=50, seq_len=16, d_model=32,
                      num_heads=2, num_blocks=1)
    assert first.arch is None and not hasattr(first, "noise_batch")
    tree = jax.tree.map(lambda x: x.shape, first.init(jax.random.key(0)))
    assert tree == {
        "tok": (50, 32), "pos": (16, 32),
        "blocks": [{"ln1_g": (32,), "ln1_b": (32,), "qkv": (32, 3, 2, 16),
                    "proj": (32, 32), "ln2_g": (32,), "ln2_b": (32,),
                    "mlp_in": {"w": (32, 128), "b": (128,)},
                    "mlp_out": {"w": (128, 32), "b": (32,)}}],
        "ln_f": {"g": (32,), "b": (32,)},
        "head": {"w": (32, 50), "b": (50,)}}


@pytest.fixture
def fresh_flags():
    flags.define_reference_flags()
    flags.FLAGS._reset()
    yield
    flags.FLAGS._reset()


@pytest.mark.parametrize("argv,needle", [
    (["--norm=batchnorm"], "--norm"),
    (["--objective=denoise"], "--objective"),
    (["--num_heads=4", "--num_kv_heads=3"], "must divide --num_heads"),
    (["--head_dim=15"], "--head_dim"),
    (["--moe_top_k=2"], "needs --moe_experts"),
    (["--moe_experts=8", "--moe_top_k=2"], "--mlp_gated"),
    (["--moe_experts=8", "--moe_top_k=2", "--mlp_gated",
      "--moe_first_expert=6", "--moe_held_experts=4"], "reach past"),
    (["--moe_experts=8", "--moe_held_experts=4"], "silently change"),
    (["--moe_experts=8", "--moe_top_k=2", "--mlp_gated", "--expert_parallel",
      "--model_axis=2"], "--expert_parallel"),
    (["--objective=masked_diffusion", "--dataset=lm"], "--device_data"),
    (["--objective=masked_diffusion", "--dataset=lm", "--device_data",
      "--seq_len=64", "--diffusion_block=5"], "must divide --seq_len"),
    (["--objective=masked_diffusion", "--dataset=lm", "--device_data",
      "--zero=1"], "--zero"),
    (["--diffusion_t_min=0"], "--diffusion_t_min"),
    (["--rope_theta=-1"], "--rope_theta"),
])
def test_the_new_flags_validators_reject_at_parse_time(fresh_flags, argv,
                                                       needle):
    with pytest.raises(ValueError) as e:
        flags.FLAGS._parse(argv)
    assert needle in str(e.value)


def test_the_configurations_flags_parse(fresh_flags):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "train-s4096.json")) as f:
        mix = json.load(f)
    given = FAMILY.trainer_flags(config, mix)
    flags.FLAGS._parse(
        [f"--{k}={str(v).lower() if isinstance(v, bool) else v}"
         for k, v in given.items()]
        + ["--model=lm", "--dataset=lm", "--device_data", "--seq_len=4096"])
    assert flags.FLAGS.moe_held_experts == 16 and flags.FLAGS.qk_norm is True
    sizes = FAMILY.sizes(config, mix)
    assert FAMILY.total_params(sizes) == 550_984_960
    parts = FAMILY.scope_flops_per_token(sizes)
    assert sum(parts.values()) == FAMILY.train_flops_per_token(sizes)
    assert parts["attention"] == 12 * 5 * 32 * 128 * (4096 + 4)
    assert round(FAMILY.train_flops_per_token(sizes) / 1e9, 2) == 2.67


def test_the_trainer_runs_the_configuration_from_flags_alone(tmp_path):
    """``mnist_dist.py`` -> ``training.loop.train`` ->
    ``make_device_train_step``, every choice a flag named by its mechanism;
    the display row carries the routed layer's and the objective's
    counters."""
    argv = ["--model=lm", "--dataset=lm", "--device_data", "--mode=local",
            "--seq_len=64", "--vocab_size=300", "--d_model=64",
            "--num_heads=4", "--num_blocks=2", "--batch_size=4",
            "--norm=rmsnorm", "--norm_eps=1e-6", "--rope_theta=1000000",
            "--num_kv_heads=2", "--head_dim=16", "--qk_norm", "--mlp_gated",
            "--biases=false", "--moe_experts=16", "--moe_top_k=4",
            "--moe_ffn_dim=32", "--moe_first_expert=4",
            "--moe_held_experts=4", "--moe_capacity=4",
            "--objective=masked_diffusion", "--diffusion_block=4",
            "--attn_block=16", "--ce_block=16", "--remat",
            "--optimizer=adam", "--learning_rate=0.001", "--training_iter=6",
            "--display_step=3", "--device_chunk=1", "--test_eval=false",
            f"--logdir={tmp_path}/logs", f"--data_dir={tmp_path}/data"]
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "mnist_dist.py"), *argv],
        capture_output=True, text=True, cwd=REPO, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert p.returncode == 0, p.stderr[-3000:]
    with open(tmp_path / "logs" / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    display = [r for r in rows if "mini_batch_loss" in r]
    assert [r["step"] for r in display] == [0, 3]
    for r in display:
        assert np.isfinite(r["mini_batch_loss"])
        assert r["moe_overflow_rows"] == 0 and 0 < r["moe_buffer_fill_max"] <= 1
        # the buffer is one row tile here, which each held expert visits
        assert r["moe_tiles_run_frac"] in (1, 2, 3, 4)
        # the pairs fill a quarter of the buffer: one live tile of its two
        assert r["moe_dispatch_tiles_frac"] == 0.5
        assert r["moe_rows_per_expert_max"] >= r["moe_rows_per_expert_mean"] > 0
        assert 0.2 < r["moe_unrouted_frac"] < 0.45
        assert 0.3 < r["diffusion_masked_frac"] < 0.7
    # the eval's noise has one key: the display loss moves with the weights
    assert display[0]["mini_batch_loss"] != display[1]["mini_batch_loss"]
