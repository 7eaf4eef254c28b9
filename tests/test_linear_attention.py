"""Linear-attention layers (Gated DeltaNet: ``layer_plan`` entries
``linear:<value heads>:<feed-forward>``) beside gated full attention in one
model, at a small size on the CPU: the program (through
``make_device_train_step``) held to the plain reference of the family that
brought the mechanism (``benchmark/reference/qwen3_next.py``), and each
mechanism held to something written independently:

- the chunked delta rule (``ops/linear_attention.py``) against the token
  recurrence, values and the gradients of all five inputs, in f32 to 1e-5,
  for chunks of 64 and of 16, under strong and weak decays;
- loss, every leaf's gradient and the change after three Adam steps, in f32
  and in bf16 within a band that the float8 control fails;
- the share ties to the whole: the parts of sixteen shares of a routed
  layer's experts plus the gated shared expert once add up to the uncut
  reference layer;
- the flags' validators, the model's own refusals, and the steps that
  refuse a linear layer;
- a model without the new choices is the model it was.

d 32, 4 query heads of 16 over 2 key/value heads, rotary positions on a
quarter of the head width; linear layers of 2 key heads and 4 value heads of
8, a conv of 4 taps; 8 experts of width 16 with 2 a token and 4 held, a
gated shared expert of 16; three linear layers and a full one, S 128 (two
chunks of 64).
"""

import functools
import os
import re
import statistics
import subprocess

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
from distributed_tensorflow_tpu.ops.linear_attention import gated_delta_rule
from distributed_tensorflow_tpu.training import (
    create_train_state,
    get_optimizer,
)
from distributed_tensorflow_tpu.training.device_step import (
    make_device_train_step,
)
from distributed_tensorflow_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = manifest.load_family(
    os.path.join(REPO, "benchmark", "reference", "qwen3_next.py"))
SIZES = {"d_model": 32, "heads": 4, "kv_heads": 2, "head_dim": 16,
         "num_blocks": 4,
         "layer_types": ("linear_attention",) * 3 + ("full_attention",),
         "rope_theta": 1e7, "rope_fraction": 0.25, "key_heads": 2,
         "value_heads": 4, "key_dim": 8, "value_dim": 8, "conv": 4,
         "router_width": 8, "held_experts": 4, "first_expert": 2,
         "top_k": 2, "expert_dim": 16, "shared_dim": 16, "vocab_size": 100,
         "norm_eps": 1e-6, "seq_len": 128}
PLAN = "linear:4:routed,linear:4:routed,linear:4:routed,full:4:routed"
SEED, ROWS, LR = 7, 4, 1e-3


def small_model(compute_dtype=None, **over):
    kw = dict(vocab_size=100, seq_len=128, d_model=32, num_heads=4,
              num_blocks=4, norm="rmsnorm_zero_centred", norm_eps=1e-6,
              rope_theta=1e7, rope_fraction=0.25, num_kv_heads=2,
              head_dim=16, qk_norm=True, mlp_gated=True, biases=False,
              moe_experts=8, moe_top_k=2, moe_ffn_dim=16, moe_first_expert=2,
              moe_held_experts=4, moe_capacity=4.0, moe_shared_dim=16,
              moe_shared_gate=True, attn_gate_elementwise=True,
              layer_plan=PLAN, linear_key_heads=2, linear_key_dim=8,
              linear_value_dim=8, linear_conv=4, attn_block=16, ce_block=16,
              remat=True, compute_dtype=compute_dtype)
    kw.update(over)
    return get_model("lm", **kw)


@functools.lru_cache(maxsize=None)
def program(dtype_name):
    """Three steps of the trainer's own compiled step from the seed: the
    losses, the first gradient's leaves, the leaves' changes."""
    cd = {"f32": None, "bf16": jnp.bfloat16}[dtype_name]
    model = small_model(cd)
    opt = get_optimizer("adam", LR)
    state = create_train_state(model, opt, seed=SEED)
    ds = LMDataSet(4096, 128, 100, seed=SEED)
    data = DeviceData(jnp.asarray(ds.images), jnp.asarray(ds.labels))
    step = make_device_train_step(model, opt, ROWS, chunk=1, donate=False)
    start, losses, first = state.params, [], None
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            state, metrics = step(state, data)
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
    """A leaf's gradient difference over its own norm or the median leaf's,
    whichever is larger (``harness/compare.py``'s denominator)."""
    floor = statistics.median(ref["grad_norms"].values())
    names = list(ref["grad_norms"])
    return {n: float(np.linalg.norm(np.asarray(a, np.float32) - b))
            / max(ref["grad_norms"][n], floor)
            for n, a, b in zip(names, other, ref["first_gradient"])}


# ---- the chunked rule against the recurrence ---------------------------------

def rule_inputs(decay, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    b, s, h, dk, dv = 2, 256, 3, 16, 8

    def l2(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = l2(jax.random.normal(k[0], (b, s, h, dk))) / 4.0
    kk = l2(jax.random.normal(k[1], (b, s, h, dk)))
    v = jax.random.normal(k[2], (b, s, h, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(k[3], (b, s, h)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (b, s, h)))
    return (q, kk, v, g, beta), jax.random.normal(k[5], (b, s, h, dv))


@pytest.mark.parametrize("decay", [3.0, 0.01])  # exp(g) ~ 0.1 and ~ 0.99
@pytest.mark.parametrize("chunk", [64, 16])
def test_the_chunked_rule_is_the_token_recurrence(chunk, decay):
    args, cotangent = rule_inputs(decay)
    recurrence = jax.vmap(FAMILY.delta_rule)

    def both(fn):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(cotangent)

    with jax.default_matmul_precision("highest"):
        want = both(recurrence)
        got = both(lambda *a: gated_delta_rule(*a, chunk=chunk))
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b), name


def test_the_sequence_must_divide_into_chunks():
    args, _ = rule_inputs(1.0)
    with pytest.raises(ValueError, match="does not divide"):
        gated_delta_rule(*(x[:, :100] for x in args))
    telemetry.get_tracer().clear()
    jax.make_jaxpr(gated_delta_rule)(*args)
    note = telemetry.last_spans(10)[-1]
    assert (note["name"], note["implementation"], note["chunk"],
            note["chunks"], note["state_bytes_per_head"]) == (
        "linear_attention_path", "chunked_scan", 64, 4, 16 * 8 * 4)


# ---- the program against the reference --------------------------------------

def test_the_plan_builds_the_tree_the_reference_draws():
    model = small_model()
    assert [(x.linear, x.heads) for x in model.plan] == [
        ((2, 8, 8, 4), 4)] * 3 + [((), 4)]
    key = jax.random.key(SEED, impl="threefry2x32")
    mine = model.init(jax.random.split(key)[0])
    theirs = FAMILY.init_params(SEED, SIZES)
    assert FAMILY.leaf_names(mine) == FAMILY.leaf_names(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert np.array_equal(a, b)
    assert model.num_params() == FAMILY.total_params(SIZES)
    # the zero-centred norms start at nought, the decays' A in (0, 16)
    assert not np.any(mine["ln_f"]["g"]) and not np.any(
        mine["blocks"][3]["q_norm_g"])
    assert np.all(np.exp(mine["blocks"][0]["a_log"]) < 16)


def test_f32_program_matches_the_reference_loss_gradients_and_change():
    """To 1e-5 of a leaf's own norm or the median leaf's: ``a_log`` and
    ``dt_bias`` have gradients of 1e-7 to 1e-5 where the median leaf's is
    1e-2, sums over every token of terms that cancel, whose f32 rounding
    reads 1e-3 of their own norm alone (harness/compare.py measures them
    the same way). The change after three Adam steps to 1e-3 of its own
    norm or the median leaf's, for the same reason: Adam moves an element
    by its gradient's sign and size against its second moment, and those
    elements carry that rounding (``a_log``'s change reads 0.8 % of its
    own norm off, 2e-4 of the median leaf's)."""
    prog, ref = program("f32"), reference()
    assert prog["names"] == list(ref["grad_norms"])  # the same leaves
    for p, r in zip(prog["losses"], ref["losses"]):
        assert abs(p - r) / r < 1e-5
    for name, share in gradient_shares(prog["first_gradient"], ref).items():
        assert share < 1e-5, name
    floor = statistics.median(ref["change_norms"].values())
    for n in prog["names"]:
        assert abs(prog["change_norms"][n] - ref["change_norms"][n]) \
            <= 1e-3 * max(ref["change_norms"][n], floor), n


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

def test_sixteen_shares_and_the_gated_shared_expert_once_add_up_to_the_uncut_layer():
    k = jax.random.split(jax.random.key(0), 7)
    b = jax.random.normal(k[0], (2, 64, 32))
    full = {"router": jax.random.normal(k[1], (32, 32)) * 0.5,
            "w1": jax.random.normal(k[2], (32, 32, 32)) * 0.1,
            "w2": jax.random.normal(k[3], (32, 16, 32)) * 0.1}
    shared = {"w1": jax.random.normal(k[4], (32, 32)) * 0.1,
              "w2": jax.random.normal(k[5], (16, 32)) * 0.1,
              "gate": jax.random.normal(k[6], (32, 1)) * 0.5}
    sizes = dict(SIZES, first_expert=0, top_k=4, router_width=32,
                 held_experts=32)
    with jax.default_matmul_precision("highest"):
        whole = FAMILY.feed_forward(b.reshape(-1, 32),
                                    {"moe": full, "shared": shared}, sizes)
        total = transformer._shared_expert(b, shared, None)  # once
        for first in range(0, 32, 2):
            share = {"router": full["router"],
                     "w1": full["w1"][first:first + 2],
                     "w2": full["w2"][first:first + 2]}
            y, aux = moe.routed_experts(b, share, top_k=4, first_expert=first,
                                        capacity_factor=4.0)
            assert float(aux["overflow_rows"]) == 0
            mine = FAMILY.routed_layer(b.reshape(-1, 32), share, sizes,
                                       first=first)
            np.testing.assert_allclose(y.reshape(-1, 32), mine, atol=5e-6)
            total = total + y
    np.testing.assert_allclose(total.reshape(-1, 32), whole, atol=1e-5)


# ---- remat and the layer's instants -----------------------------------------

def test_remat_keeps_nothing_of_a_linear_layer_and_says_so():
    model = small_model(jnp.bfloat16)
    params = model.init(jax.random.key(0))
    x = jnp.zeros((2, 128), jnp.int32)
    telemetry.get_tracer().clear()
    jax.make_jaxpr(jax.grad(
        lambda p: model.loss_with_metrics(p, x, x, train=True)[0]))(params)
    spans = telemetry.last_spans(200)
    kinds = {(r["attention"], r["heads"], r["ffn"]): (r["names"],
                                                      r["bytes_per_block"])
             for r in spans if r["name"] == "remat_saved"}
    # a full layer keeps its attention's out (B S H Dh bf16) and logsumexp
    # (B H S f32); a linear layer keeps nothing and runs its scan again
    assert kinds == {("linear", 4, "routed"): ([], 0),
                     ("full", 4, "routed"): (
                         ["attention_lse", "attention_out"],
                         2 * 128 * 4 * (16 * 2 + 4))}
    paths = [r for r in spans if r["name"] == "linear_attention_path"]
    assert paths and all((r["chunk"], r["chunks"]) == (64, 2) for r in paths)


# ---- flags and refusals --------------------------------------------------------

@pytest.fixture
def fresh_flags():
    flags.define_reference_flags()
    flags.FLAGS._reset()
    yield
    flags.FLAGS._reset()


ROUTED = ["--moe_experts=8", "--moe_top_k=2", "--mlp_gated"]
LINEAR = ["--linear_key_heads=2", "--linear_key_dim=8",
          "--linear_value_dim=8", "--linear_conv=4"]


@pytest.mark.parametrize("argv,needle", [
    (["--layer_plan=linear:4:dense", "--num_blocks=1"], "--linear_key_heads"),
    (["--layer_plan=linear:5:dense", "--num_blocks=1", *LINEAR],
     "do not divide over --linear_key_heads=2"),
    (["--layer_plan=full:4:dense", "--num_blocks=1", *LINEAR],
     "names no linear layer"),
    (LINEAR, "silently change"),
    (["--linear_conv=-1"], "--linear_conv"),
    (["--moe_shared_gate"], "silently change"),
    (["--attn_gate", "--attn_gate_elementwise"], "drop one"),
    (["--norm=zero_centred"], "--norm"),
    (["--layer_plan=linear:4:dense", "--num_blocks=1", *LINEAR,
      "--pipeline", "--model_axis=2"], "--pipeline"),
    (["--layer_plan=linear:4:dense", "--num_blocks=1", *LINEAR,
      "--model_axis=2"], "--model_axis"),
])
def test_the_linear_layers_flags_are_validated_at_parse_time(fresh_flags,
                                                             argv, needle):
    with pytest.raises(ValueError) as e:
        flags.FLAGS._parse(argv)
    assert needle in str(e.value)


def test_a_linear_entry_parses_and_its_flags_build_the_layer(fresh_flags):
    assert transformer.parse_layer_plan("linear:32:routed,full:16:routed") \
        == [("linear", 32, "routed"), ("full", 16, "routed")]
    flags.FLAGS._parse(["--layer_plan=linear:4:dense", "--num_blocks=1",
                        "--norm=rmsnorm_zero_centred", *LINEAR])
    assert flags.FLAGS.linear_key_heads == 2 and flags.FLAGS.linear_conv == 4


@pytest.mark.parametrize("kw,needle", [
    (dict(layer_plan="linear:4:dense", linear_conv=0), "linear_conv > 0"),
    (dict(layer_plan="linear:3:dense"), "divided over the key heads"),
    (dict(layer_plan="full:4:dense"), "names no linear layer"),
    (dict(layer_plan=""), "name those in layer_plan"),
    (dict(layer_plan="full:4:dense", linear_key_heads=0, linear_key_dim=0,
          linear_value_dim=0, linear_conv=0, attn_gate=True,
          attn_gate_elementwise=True), "pick one"),
    (dict(layer_plan="linear:4:dense", moe_shared_gate=True), "moe_shared_dim"),
    (dict(layer_plan="linear:4:dense", norm="zero_centred"), "norm"),
])
def test_the_model_refuses_what_its_flags_refuse(kw, needle):
    base = dict(vocab_size=50, seq_len=64, d_model=32, num_heads=4,
                num_blocks=1, norm="rmsnorm", head_dim=8, linear_key_heads=2,
                linear_key_dim=8, linear_value_dim=8, linear_conv=4)
    with pytest.raises(ValueError, match=needle):
        get_model("lm", **dict(base, **kw))


@pytest.mark.parametrize("what", ["tensor_parallel", "pipeline"])
def test_the_model_parallel_steps_refuse_a_linear_layer(what):
    model = small_model()
    if what == "tensor_parallel":
        from distributed_tensorflow_tpu.parallel.tensor_parallel import (
            shard_attention,
        )

        with pytest.raises(ValueError, match="no linear-attention layer"):
            shard_attention(model, None)
    else:
        from distributed_tensorflow_tpu.parallel import pipeline_parallel

        with pytest.raises(ValueError, match="no linear-attention layer"):
            pipeline_parallel.make_pp_train_step(model, None, None, 2)


def test_no_flag_and_no_module_of_the_program_names_a_model():
    out = subprocess.run(
        ["grep", "-rniE", "qwen",
         os.path.join(REPO, "distributed_tensorflow_tpu"),
         os.path.join(REPO, "mnist_dist.py")],
        capture_output=True, text=True)
    assert out.stdout == ""


# ---- the model as it was -------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(),
    dict(norm="rmsnorm", rope_theta=1e4, num_kv_heads=1, head_dim=16,
         qk_norm=True, mlp_gated=True, biases=False, moe_experts=8,
         moe_top_k=2, moe_ffn_dim=32, moe_held_experts=4, moe_capacity=4.0,
         layer_plan="full:2:dense,window:2:routed", attn_window=8,
         attn_gate=True, moe_shared_dim=32, attn_block=16, ce_block=16,
         remat=True),
])
def test_without_the_new_choices_the_model_is_the_model_it_was(kw):
    base = dict(vocab_size=50, seq_len=64, d_model=32, num_heads=2,
                num_blocks=2, **kw)
    was = get_model("lm", **base)
    now = get_model("lm", **base, attn_gate_elementwise=False,
                    moe_shared_gate=False, linear_key_heads=0,
                    linear_key_dim=0, linear_value_dim=0, linear_conv=0)
    assert now.arch == was.arch and now.plan == was.plan
    assert not any(x.linear or x.attn_gate_elementwise or x.shared_gate
                   for x in now.plan)
    tree = was.init(jax.random.key(0))
    assert jax.tree.structure(tree) == jax.tree.structure(
        now.init(jax.random.key(0)))
    x = jax.random.randint(jax.random.key(1), (2, 64), 0, 50)

    def program_of(model):  # less the addresses of its function objects
        return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
            lambda p: model.loss_with_metrics(p, x, x, train=True)[0]
            if model.wants_loss_hook else model.apply(p, x))(tree)))

    assert program_of(now) == program_of(was)


def test_the_configurations_flags_parse_and_build_its_model(fresh_flags):
    cell = manifest.load_cell("qwen3-next-80b-a3b.train-b1-s16384")
    flags.FLAGS._parse(manifest.trainer_argv(cell, 7, "/tmp/x"))
    assert flags.FLAGS.layer_plan == (
        "linear:32:routed,linear:32:routed,linear:32:routed,full:16:routed")
    from distributed_tensorflow_tpu.training.loop import build_model_for

    model = build_model_for(flags.FLAGS, {"kind": "lm", "vocab_size": 18992,
                                          "seq_len": 16384})
    assert [x.linear for x in model.plan] == [(16, 128, 128, 4)] * 3 + [()]
    assert model.plan[0].heads == 32
    assert model.plan[3].rope_fraction == 0.25 and model.plan[3].shared_gate
    assert model.num_params() == 424_340_544 \
        == cell.family().total_params(cell.sizes)
