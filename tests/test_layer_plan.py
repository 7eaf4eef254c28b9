"""Layers that differ in one model (``TransformerLM(layer_plan=...)``) at a
small size on the CPU: the program (through ``make_device_train_step``) held
to the plain reference of the family that brought the mechanism
(``benchmark/reference/laguna.py``), and each mechanism held to something
written independently:

- the causal window ``Mask``: ``tile`` and the kernels' tables of live tiles
  (``live_tiles``) against ``allowed`` on
  every tile, for windows that are and are not multiples of the tile; the
  dense and the scan form against a mask built from indices (the fused
  kernels: ``tests/test_flash_kernel.py``);
- loss, every leaf's gradient and the change after three Adam steps, in f32
  to 1e-5 and in bf16 within a band that the float8 control fails;
- the share ties to the whole: the routed parts of the two shares of held
  experts plus the shared expert once add up to the uncut reference layer;
- the partial and the YaRN rotary positions against the written formula;
- the flags' validators, and the steps and the server that refuse a plan.

d 64, head width 16, 6 query heads on a full layer and 8 on a window layer
over 2 key/value heads, window 8 at S 64, 8 experts of width 32 with 2 a
token and 4 held, a shared expert of width 32, 1 dense + 4 layers.
"""

import functools
import json
import math
import os
import statistics
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest
from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.data.device_data import DeviceData
from distributed_tensorflow_tpu.data.lm import LMDataSet
from distributed_tensorflow_tpu.models import get_model
from distributed_tensorflow_tpu.models import transformer
from distributed_tensorflow_tpu.ops import moe
from distributed_tensorflow_tpu.ops.attention import (
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
from distributed_tensorflow_tpu.utils import telemetry
from tests.mask_tables import assert_tables_follow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = manifest.load_family(
    os.path.join(REPO, "benchmark", "reference", "laguna.py"))
YARN = (64.0, 16.0, 64.0, 1.0, 1.4158883083359672)
SIZES = {"d_model": 64, "kv_heads": 2, "head_dim": 16, "num_blocks": 5,
         "layer_types": ("full_attention",) + ("sliding_attention",) * 3
         + ("full_attention",),
         "layer_heads": (6, 8, 8, 8, 6),
         "mlp_types": ("dense",) + ("sparse",) * 4,
         "window": 8, "rope_full": (500000.0, 0.5, YARN),
         "rope_sliding": (10000.0, 1.0, ()), "dense_dim": 256,
         "router_width": 8, "held_experts": 4, "first_expert": 2, "top_k": 2,
         "expert_dim": 32, "shared_dim": 32, "routed_scale": 2.5,
         "vocab_size": 300, "norm_eps": 1e-6, "seq_len": 64}
PLAN = "full:6:dense,window:8:routed,window:8:routed,window:8:routed," \
       "full:6:routed"
SEED, ROWS, LR = 7, 4, 1e-3


def small_model(compute_dtype=None, **over):
    kw = dict(vocab_size=300, seq_len=64, d_model=64, num_heads=6,
              num_blocks=5, norm="rmsnorm", norm_eps=1e-6, rope_theta=5e5,
              num_kv_heads=2, head_dim=16, qk_norm=True, mlp_gated=True,
              biases=False, moe_experts=8, moe_top_k=2, moe_ffn_dim=32,
              moe_first_expert=2, moe_held_experts=4, moe_capacity=4.0,
              layer_plan=PLAN, attn_window=8, window_rope_theta=1e4,
              rope_fraction=0.5, rope_yarn=YARN, attn_gate=True,
              moe_shared_dim=32, moe_scoring="sigmoid", moe_scale=2.5,
              attn_block=16, ce_block=16, remat=True,
              compute_dtype=compute_dtype)
    kw.update(over)
    return get_model("lm", **kw)


def small_data():
    ds = LMDataSet(4096, 64, 300, seed=SEED)
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
            assert float(metrics["moe_overflow_rows"]) == 0
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
    floor = statistics.median(ref["grad_norms"].values())
    names = list(ref["grad_norms"])
    return {n: float(np.linalg.norm(np.asarray(a, np.float32) - b))
            / max(ref["grad_norms"][n], floor)
            for n, a, b in zip(names, other, ref["first_gradient"])}


# ---- the program against the reference --------------------------------------

def test_the_plan_builds_the_tree_the_reference_draws():
    model = small_model()
    assert [(x.heads, x.window, x.ffn, x.shared_dim) for x in model.plan] == [
        (6, 0, "dense", 0), (8, 8, "routed", 32), (8, 8, "routed", 32),
        (8, 8, "routed", 32), (6, 0, "routed", 32)]
    assert model.plan[0].rope_yarn == YARN and model.plan[0].rope_fraction == 0.5
    assert model.plan[1].rope_theta == 1e4 and model.plan[1].rope_yarn == ()
    key = jax.random.key(SEED, impl="threefry2x32")
    mine = model.init(jax.random.split(key)[0])
    theirs = FAMILY.init_params(SEED, SIZES)
    assert FAMILY.leaf_names(mine) == FAMILY.leaf_names(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert np.array_equal(a, b)
    assert model.num_params() == FAMILY.total_params(SIZES)


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
    prog, ref, control = program("bf16"), reference(), reference("fp8")
    for p, r in zip(prog["losses"], ref["losses"]):
        assert abs(p - r) / r < 5e-4
    shares = gradient_shares(prog["first_gradient"], ref)
    fp8 = gradient_shares(control["first_gradient"], ref)
    assert statistics.median(shares.values()) < 0.03
    assert statistics.median(fp8.values()) > 2 * statistics.median(
        shares.values())
    assert statistics.median(fp8.values()) > 0.06


# ---- the share ties to the whole ---------------------------------------------

def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    k = jax.random.split(jax.random.key(0), 6)
    b = jax.random.normal(k[0], (2, 64, 64))
    full = {"router": jax.random.normal(k[1], (64, 8)) * 0.5,
            "w1": jax.random.normal(k[2], (8, 64, 64)) * 0.1,
            "w2": jax.random.normal(k[3], (8, 32, 64)) * 0.1}
    shared = {"w1": jax.random.normal(k[4], (64, 64)) * 0.1,
              "w2": jax.random.normal(k[5], (32, 64)) * 0.1}
    with jax.default_matmul_precision("highest"):
        whole = FAMILY.feed_forward(
            b.reshape(-1, 64), {"moe": full, "shared": shared},
            dict(SIZES, first_expert=0))
        total = transformer._shared_expert(b, shared, None)  # once
        for first in (0, 4):
            share = {"router": full["router"],
                     "w1": full["w1"][first:first + 4],
                     "w2": full["w2"][first:first + 4]}
            y, aux = moe.routed_experts(
                b, share, top_k=2, first_expert=first, capacity_factor=4.0,
                scoring="sigmoid", scale=2.5)
            assert float(aux["overflow_rows"]) == 0
            mine = FAMILY.routed_layer(b.reshape(-1, 64), share, SIZES,
                                       first=first)
            np.testing.assert_allclose(y.reshape(-1, 64), mine, atol=5e-6)
            total = total + y
    np.testing.assert_allclose(total.reshape(-1, 64), whole, atol=1e-5)


def test_softmax_scores_and_a_scale_of_one_are_the_routed_layer_as_it_was():
    b = jax.random.normal(jax.random.key(1), (1, 64, 64))
    k = jax.random.split(jax.random.key(2), 3)
    params = {"router": jax.random.normal(k[0], (64, 8)),
              "w1": jax.random.normal(k[1], (4, 64, 64)) * 0.1,
              "w2": jax.random.normal(k[2], (4, 32, 64)) * 0.1}
    fn = functools.partial(moe.routed_experts, top_k=2, capacity_factor=4.0)
    was = jax.make_jaxpr(lambda b, p: fn(b, p)[0])(b, params)
    now = jax.make_jaxpr(lambda b, p: fn(b, p, scoring="softmax",
                                         scale=1.0)[0])(b, params)
    assert str(was) == str(now)
    y1, _ = fn(b, params)
    y2, _ = fn(b, params, scale=2.0)
    np.testing.assert_allclose(2.0 * y1, y2, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="scores by one of"):
        fn(b, params, scoring="tanh")


# ---- the rotary positions ----------------------------------------------------

def _rotated_by_the_formula(x, theta, share, yarn):
    """Equation 2 of the family in numpy, a pair of dimensions at a time."""
    s, dh = x.shape[1], x.shape[-1]
    dr = int(round(share * dh))
    out = np.array(x, np.float64)
    for i in range(dr // 2):
        inv = theta ** (-2.0 * i / dr)
        factor = 1.0
        if yarn:
            scale, original, fast, slow, factor = yarn
            t = lambda b: dr * math.log(original / (2 * math.pi * b)) / (  # noqa: E731
                2 * math.log(theta))
            lo, hi = max(math.floor(t(fast)), 0), min(math.ceil(t(slow)), dr - 1)
            c = 1.0 - min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
            inv = (1 - c) * inv / scale + c * inv
        for p in range(s):
            cos, sin = factor * math.cos(p * inv), factor * math.sin(p * inv)
            a, b = x[:, p, :, i].astype(np.float64), \
                x[:, p, :, i + dr // 2].astype(np.float64)
            out[:, p, :, i] = a * cos - b * sin
            out[:, p, :, i + dr // 2] = b * cos + a * sin
    return out


@pytest.mark.parametrize("theta,share,yarn", [
    (1e4, 1.0, ()), (5e5, 0.5, ()), (5e5, 0.5, YARN), (5e5, 1.0, YARN),
    (5e5, 0.5, (64.0, 4096.0, 64.0, 1.0, 1.4158883083359672)),
    (1e6, 0.25, (8.0, 32.0, 32.0, 1.0, 1.2))])
def test_partial_and_yarn_rotary_positions_follow_the_written_formula(
        theta, share, yarn):
    dh = 64 if yarn and yarn[1] == 4096.0 else 16
    x = np.asarray(jax.random.normal(jax.random.key(3), (2, 12, 3, dh)))
    got = transformer.rope(jnp.asarray(x), jnp.arange(12), theta, share, yarn)
    np.testing.assert_allclose(got, _rotated_by_the_formula(
        x, theta, share, yarn), rtol=2e-5, atol=2e-5)
    dr = int(round(share * dh))
    assert np.array_equal(np.asarray(got)[..., dr:], x[..., dr:])
    # the family's reference is written from the same formula
    theirs = FAMILY._rotate(jnp.asarray(x[0]), jnp.arange(12),
                            (theta, share, yarn))
    np.testing.assert_allclose(got[0], theirs, rtol=1e-5, atol=1e-5)


def test_the_published_yarn_blends_dimensions_5_to_16_of_32():
    inv, factor = transformer.yarn_frequencies(
        5e5, 64, (64.0, 4096.0, 64.0, 1.0, 1.4158883083359672))
    plain = 5e5 ** (-np.arange(0, 64, 2) / 64.0)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)       # kept
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)  # / factor
    assert np.all(inv[6:16] < plain[6:16]) and np.all(
        inv[6:16] > plain[6:16] / 64)
    assert factor == pytest.approx(1.4158883083359672)


# ---- the window mask ---------------------------------------------------------

WINDOWS = [(512, 128, 128, 128), (512, 200, 128, 128), (1024, 512, 256, 128),
           (512, 64, 128, 256), (768, 300, 384, 128), (256, 256, 128, 128),
           (512, 1000, 128, 128), (8192, 512, 512, 512)]


@pytest.mark.parametrize("seq,window,tq,tk", WINDOWS)
def test_tiles_and_tables_follow_the_dense_window(seq, window, tq, tk):
    mask = Mask("window", window=window)
    nq, nk = seq // tq, seq // tk
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    want = (j <= i) & (j > i - window)  # the family's equation 2
    assert np.array_equal(np.asarray(mask.allowed(i, j)), want)
    some = want.reshape(nq, tq, nk, tk).any(axis=(1, 3))
    every = want.reshape(nq, tq, nk, tk).all(axis=(1, 3))
    q0, k0 = np.arange(nq)[:, None] * tq, np.arange(nk)[None, :] * tk
    visible, runs = mask.tile(q0, q0 + tq - 1, k0, k0 + tk - 1)
    assert np.array_equal(np.asarray(visible), every)
    assert np.array_equal(np.asarray(runs), some)
    # the kernels' grids: every tile that runs, once, and no other step
    assert assert_tables_follow(mask, seq, tq, tk) == (
        some.sum(), (some & ~every).sum())
    # a row's steps are the band the window reaches, whatever S
    assert np.bincount(mask.live_tiles(seq, tq, tk)[0]).max() \
        == some.sum(axis=1).max() <= (tq + window - 2) // tk + 2


def test_the_cells_window_layers_run_31_steps_a_head():
    mask = Mask("window", window=512)
    for key_major in (False, True):  # 32 steps of a band of 2 until PR 37
        qi, kj, _ = mask.live_tiles(8192, 512, 512, key_major)
        assert qi.size == 31 and np.all((qi == kj) | (qi == kj + 1))
    assert mask.tiles_run(8192, 512, 512) == 31
    assert Mask("causal").tiles_run(8192, 512, 512) == 136
    assert Mask("causal").live_tiles(8192, 512, 512).shape == (3, 136)
    with pytest.raises(ValueError, match="window"):
        Mask("window")
    with pytest.raises(ValueError, match="window"):
        Mask("causal", window=8)


@pytest.mark.parametrize("seq,window,tile,heads,kv_heads", [
    (64, 8, 16, 4, 2), (64, 20, 16, 2, 2), (96, 96, 32, 3, 1),
    (64, 200, 16, 2, 1)])
def test_dense_and_scan_forms_match_a_mask_built_from_indices(
        seq, window, tile, heads, kv_heads):
    k = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(k[0], (2, seq, heads, 16))
    kk = jax.random.normal(k[1], (2, seq, kv_heads, 16))
    v = jax.random.normal(k[2], (2, seq, kv_heads, 16))
    g = jax.random.normal(k[3], (2, seq, heads, 16))
    mask = Mask("window", window=window)

    def by_hand(q, kk, v):
        group = heads // kv_heads
        ke, ve = jnp.repeat(kk, group, 2), jnp.repeat(v, group, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, ke) / 4.0
        i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
        s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), ve)

    def all_of(fn):
        out, vjp = jax.vjp(fn, q, kk, v)
        return (out,) + vjp(g)

    with jax.default_matmul_precision("highest"):
        want = all_of(by_hand)
        dense = all_of(lambda q, k, v: multi_head_attention(q, k, v, mask=mask))
        scan = all_of(lambda q, k, v: blockwise_attention(q, k, v, tile,
                                                          mask=mask))
    for a, b, c in zip(want, dense, scan):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(c, a, rtol=2e-5, atol=2e-5)


def test_remat_says_what_each_kind_of_layer_keeps():
    model = small_model(jnp.bfloat16)
    params = model.init(jax.random.key(0))
    x = jnp.zeros((2, 64), jnp.int32)
    telemetry.get_tracer().clear()
    jax.make_jaxpr(jax.grad(
        lambda p: model.loss_with_metrics(p, x, x, train=True)[0]))(params)
    notes = [r for r in telemetry.last_spans(100)
             if r["name"] == "remat_saved"]
    kinds = {(r["attention"], r["heads"], r["ffn"]): r["bytes_per_block"]
             for r in notes}
    # out (B S H Dh bf16) and the logsumexp (B H S f32), by the heads
    assert kinds == {("full", 6, "dense"): 2 * 64 * 6 * (16 * 2 + 4),
                     ("window", 8, "routed"): 2 * 64 * 8 * (16 * 2 + 4),
                     ("full", 6, "routed"): 2 * 64 * 6 * (16 * 2 + 4)}


# ---- flags -------------------------------------------------------------------

@pytest.fixture
def fresh_flags():
    flags.define_reference_flags()
    flags.FLAGS._reset()
    yield
    flags.FLAGS._reset()


ROUTED = ["--moe_experts=8", "--moe_top_k=2", "--mlp_gated"]


@pytest.mark.parametrize("argv,needle", [
    (["--layer_plan=full:4"], "<attention>:<query heads>:<feed-forward>"),
    (["--layer_plan=conv:4:dense"], "attention one of"),
    (["--layer_plan=full:0:dense"], "heads >= 1"),
    (["--layer_plan=full:4:switch"], "feed-forward one of"),
    (["--layer_plan=full:4:dense", "--num_blocks=2"], "names 1 layers"),
    (["--layer_plan=window:4:dense", "--num_blocks=1"], "--attn_window"),
    (["--layer_plan=full:4:routed", "--num_blocks=1"], "--moe_top_k"),
    (["--layer_plan=full:4:dense", "--num_blocks=1", *ROUTED],
     "names no routed layer"),
    (["--layer_plan=full:4:dense", "--num_blocks=1", "--attn_window=8"],
     "names no window layer"),
    (["--layer_plan=full:5:dense", "--num_blocks=1", "--num_kv_heads=2"],
     "do not divide"),
    (["--attn_window=8"], "silently change"),
    (["--window_rope_theta=100", "--rope_theta=100"], "silently change"),
    (["--rope_fraction=0.5"], "--rope_theta"),
    (["--rope_fraction=1.5", "--rope_theta=100"], "--rope_fraction"),
    (["--rope_fraction=0.3", "--rope_theta=100", "--head_dim=16"],
     "even number"),
    (["--rope_yarn=64,4096", "--rope_theta=100"], "rope_yarn"),
    (["--rope_yarn=64,4096,1,64,1.4", "--rope_theta=100"], "beta_fast"),
    (["--moe_shared_dim=32"], "--moe_top_k"),
    (["--moe_scoring=sigmoid"], "--moe_top_k"),
    (["--moe_scale=2.5"], "--moe_top_k"),
    ([*ROUTED, "--moe_scoring=tanh"], "--moe_scoring"),
    ([*ROUTED, "--moe_scale=0"], "--moe_scale"),
    (["--layer_plan=full:4:dense", "--num_blocks=1", "--seq_parallel",
      "--model_axis=2"], "--seq_parallel"),
    (["--layer_plan=full:4:dense", "--num_blocks=1", "--pipeline",
      "--model_axis=2"], "--pipeline"),
    (["--layer_plan=full:4:dense", "--num_blocks=1", "--model_axis=2"],
     "--model_axis"),
    (["--layer_plan=full:4:dense", "--num_blocks=1",
      "--objective=masked_diffusion", "--dataset=lm", "--device_data"],
     "next_token"),
])
def test_the_plans_flags_are_validated_at_parse_time(fresh_flags, argv,
                                                     needle):
    with pytest.raises(ValueError) as e:
        flags.FLAGS._parse(argv)
    assert needle in str(e.value)


def test_no_flag_and_no_module_of_the_program_names_a_model():
    out = subprocess.run(
        ["grep", "-rniE", "laguna|poolside",
         os.path.join(REPO, "distributed_tensorflow_tpu"),
         os.path.join(REPO, "mnist_dist.py")],
        capture_output=True, text=True)
    assert out.stdout == ""


def test_a_model_without_a_plan_is_the_model_it_was():
    first = get_model("lm", vocab_size=50, seq_len=16, d_model=32,
                      num_heads=2, num_blocks=2)
    assert first.arch is None and first.layer_plan == ""
    assert first.plan == (transformer.BlockArch(),) * 2
    switch = get_model("lm", vocab_size=50, seq_len=16, d_model=32,
                       num_heads=2, num_blocks=1, moe_experts=4)
    assert switch.arch is None and switch.plan[0].ffn == "switch"
    routed = get_model("lm", vocab_size=50, seq_len=16, d_model=32,
                       num_heads=2, num_blocks=1, norm="rmsnorm",
                       rope_theta=1e4, mlp_gated=True, biases=False,
                       moe_experts=4, moe_top_k=2)
    assert routed.plan == (routed.arch,) and routed.arch.ffn == "routed"
    # 4 + 8 L keys of the seed's split, as every checkpoint so far was drawn
    tree = first.init(jax.random.key(0))
    keys = iter(jax.random.split(jax.random.key(0), 4 + 8 * 2))
    from distributed_tensorflow_tpu.models.cnn import truncated_normal_init
    assert np.array_equal(tree["tok"], truncated_normal_init(
        next(keys), (50, 32), 0.02, jnp.float32))


@pytest.mark.parametrize("what", ["decode", "tensor_parallel", "pipeline"])
def test_what_runs_one_kind_of_layer_refuses_a_plan(what):
    model = small_model()
    if what == "decode":
        from distributed_tensorflow_tpu.serving.decode import check_decodable

        dense = small_model(moe_experts=0, moe_top_k=0, moe_ffn_dim=0,
                            moe_first_expert=0, moe_held_experts=0,
                            moe_shared_dim=0, moe_scoring="softmax",
                            moe_scale=1.0,
                            layer_plan="full:6:dense,window:8:dense",
                            num_blocks=2)
        with pytest.raises(ValueError, match="layer_plan"):
            check_decodable(dense)
    elif what == "tensor_parallel":
        from distributed_tensorflow_tpu.parallel.tensor_parallel import (
            shard_attention,
        )

        with pytest.raises(ValueError, match="layer_plan"):
            shard_attention(model, None)
    else:
        from distributed_tensorflow_tpu.parallel import pipeline_parallel

        with pytest.raises(ValueError, match="layer_plan"):
            pipeline_parallel.make_pp_train_step(model, None, None, 2)


def test_the_configurations_flags_parse_and_its_counts_add_up(fresh_flags):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna-xs2.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "train-s8192.json")) as f:
        mix = json.load(f)
    given = FAMILY.trainer_flags(config, mix)
    assert given["layer_plan"] == (
        "full:48:dense,window:64:routed,window:64:routed,window:64:routed,"
        "full:48:routed")
    flags.FLAGS._parse(
        [f"--{k}={str(v).lower() if isinstance(v, bool) else v}"
         for k, v in given.items()]
        + ["--model=lm", "--dataset=lm", "--device_data", "--seq_len=8192"])
    assert flags.FLAGS.moe_shared_dim == 512 and flags.FLAGS.attn_gate is True
    assert transformer.parse_rope_yarn(flags.FLAGS.rope_yarn) == (
        64.0, 4096.0, 64.0, 1.0, 1.4158883083359672)
    sizes = FAMILY.sizes(config, mix)
    assert FAMILY.total_params(sizes) == 490_298_624
    parts = FAMILY.scope_flops_per_token(sizes)
    assert sum(parts.values()) == FAMILY.train_flops_per_token(sizes)
    assert round(FAMILY.train_flops_per_token(sizes) / 1e9, 2) == 2.37


def test_the_trainer_runs_a_plan_from_flags_alone(tmp_path):
    """``mnist_dist.py`` -> ``training.loop.train`` ->
    ``make_device_train_step``, every choice a flag named by its mechanism;
    the display row carries the routed layers' counters."""
    argv = ["--model=lm", "--dataset=lm", "--device_data", "--mode=local",
            "--seq_len=64", "--vocab_size=300", "--d_model=64",
            "--num_heads=6", "--num_blocks=5", "--batch_size=4",
            "--norm=rmsnorm", "--norm_eps=1e-6", "--rope_theta=500000",
            "--num_kv_heads=2", "--head_dim=16", "--qk_norm", "--mlp_gated",
            "--biases=false", "--moe_experts=8", "--moe_top_k=2",
            "--moe_ffn_dim=32", "--moe_first_expert=2",
            "--moe_held_experts=4", "--moe_capacity=4",
            f"--layer_plan={PLAN}", "--attn_window=8",
            "--window_rope_theta=10000", "--rope_fraction=0.5",
            "--rope_yarn=64,16,64,1,1.4158883", "--attn_gate",
            "--moe_shared_dim=32", "--moe_scoring=sigmoid", "--moe_scale=2.5",
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
        assert r["moe_rows_per_expert_max"] >= r["moe_rows_per_expert_mean"] > 0
    assert display[0]["mini_batch_loss"] != display[1]["mini_batch_loss"]
