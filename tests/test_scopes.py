"""The scope catalog (``telemetry.SCOPES``) on the compiled step: every
catalogued ``jax.named_scope`` reaches the ``op_name`` of some operation of
one ``TransformerLM`` train step, with ``--remat`` and without, for dense and
blockwise attention, with the dense MLP (``mlp``) or the dropless routed
layer in its place (``moe_router``, ``moe_experts``, under the
masked-diffusion objective), or with a plan of layers that differ (a dense
full-attention layer and a routed window layer with a shared expert:
``attention_window`` and ``moe_shared`` beside all the others; or a routed
linear layer and a routed full one: ``linear_attention`` beside
``attention``, both inside ``attn_proj``), or with the
stack run three times over shared weights (``loop_exit``: the exit gate and
the weighting of the passes' losses, never around a block: the scan over
passes itself is under no scope); a scope
changes metadata only, so the step's outputs
are bit-equal with ``jax.named_scope`` patched to a no-op; and the program
opens no scope that the catalog does not hold."""

import ast
import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.data.device_data import DeviceData
from distributed_tensorflow_tpu.models import get_model
from distributed_tensorflow_tpu.training import (
    create_train_state,
    get_optimizer,
)
from distributed_tensorflow_tpu.training.device_step import (
    make_device_train_step,
)
from distributed_tensorflow_tpu.utils import profiling
from distributed_tensorflow_tpu.utils.telemetry import SCOPES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUTED = {"moe_router", "moe_experts"}
PLANNED = {"attention_window", "moe_shared"}
LOOPED = {"loop_exit"}
LINEAR = {"linear_attention"}
FORMS = [(remat, block, False) for remat in (False, True)
         for block in (None, 16)] + [(False, 16, True), (True, 16, True),
                                     (False, None, "plan"), (True, 16, "plan"),
                                     (False, None, "loop"), (True, 16, "loop"),
                                     (False, None, "linear"),
                                     (True, 16, "linear")]


def scopes_of(routed) -> set:
    """The catalog's names a form opens: the feed-forward is the dense
    MLP or the routed layer, never both, unless a plan gives a layer of
    each, which also has the window layers' attention and the shared
    expert; a plan of a linear layer and a full one, routed both, has the
    linear layer's core and the shared expert, and no dense MLP."""
    if routed == "plan":
        return set(SCOPES) - LOOPED - LINEAR
    if routed == "loop":
        return set(SCOPES) - PLANNED - ROUTED - LINEAR
    if routed == "linear":
        return set(SCOPES) - LOOPED - {"mlp", "attention_window"}
    return set(SCOPES) - PLANNED - LOOPED - LINEAR - (
        {"mlp"} if routed else ROUTED)


def build(remat, attn_block, routed=False):
    kw = {}
    if routed == "loop":
        kw = dict(norm="rmsnorm", rope_theta=1e4, mlp_gated=True, mlp_dim=48,
                  biases=False, sandwich_norm=True, loop_passes=3,
                  loop_exit_beta=0.05)
    elif routed:
        kw = dict(norm="rmsnorm", rope_theta=1e4, num_kv_heads=1, head_dim=16,
                  qk_norm=True, mlp_gated=True, biases=False, moe_experts=8,
                  moe_top_k=2, moe_ffn_dim=32, moe_held_experts=4,
                  moe_capacity=2.0)
        if routed == "linear":
            kw.update(norm="rmsnorm_zero_centred",
                      layer_plan="linear:4:routed,full:2:routed",
                      linear_key_heads=2, linear_key_dim=8,
                      linear_value_dim=8, linear_conv=4,
                      attn_gate_elementwise=True, moe_shared_dim=32,
                      moe_shared_gate=True)
        elif routed == "plan":
            kw.update(layer_plan="full:2:dense,window:3:routed",
                      attn_window=8, window_rope_theta=1e2, rope_fraction=0.5,
                      rope_yarn="4,16,8,1,1.1", attn_gate=True,
                      moe_shared_dim=32, moe_scoring="sigmoid", moe_scale=2.5)
        else:
            kw.update(objective="masked_diffusion", diffusion_block=4)
    model = get_model("lm", vocab_size=300, seq_len=64, d_model=32,
                      num_heads=2, num_blocks=2, compute_dtype=jnp.bfloat16,
                      attn_block=attn_block, remat=remat, ce_block=16, **kw)
    opt = get_optimizer("adam", 1e-3)
    state = create_train_state(model, opt, seed=0)
    tokens = np.random.default_rng(0).integers(0, 300, (32, 65))
    data = DeviceData(jnp.asarray(tokens[:, :-1], jnp.int32),
                      jnp.asarray(tokens[:, 1:], jnp.int32))
    step = make_device_train_step(model, opt, 4, chunk=2, donate=False)
    return step, state, data


@functools.lru_cache(maxsize=None)
def compiled(remat, attn_block, routed):
    """A form's step as ``build`` makes it, compiled once for both tests
    below: its text for the catalog, its outputs for the numbers."""
    step, state, data = build(remat, attn_block, routed)
    return step.lower(state, data).compile(), state, data


def scopes_in(op_name: str) -> set:
    """Catalogued scopes among the elements of a path, seen through the
    transform wrappers (``transpose(jvp(mlp))`` holds ``mlp``)."""
    return {e for e in re.findall(r"[A-Za-z_]\w*", op_name) if e in SCOPES}


@pytest.mark.parametrize("remat,attn_block,routed", FORMS)
def test_every_scope_of_the_catalog_is_in_the_compiled_step(remat,
                                                            attn_block,
                                                            routed):
    text = compiled(remat, attn_block, routed)[0].as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    found = set().union(*(scopes_in(p) for p in paths))
    assert found == scopes_of(routed)
    if routed == "plan":  # beside attention, never inside it
        assert not any({"attention", "attention_window"} <= scopes_in(p)
                       for p in paths)
    if routed == "linear":  # beside attention, inside attn_proj, backward too
        assert not any({"attention", "linear_attention"} <= scopes_in(p)
                       for p in paths)
        # attn_proj as a backward names it: inside transpose(jvp(...))
        assert any(re.search(r"attn_proj\)*/linear_attention", p)
                   and "transpose(" in p for p in paths)
    if routed == "loop":
        # the gate and the weighting forward and backward; the scan over
        # passes and the blocks inside it are not under it
        assert any("transpose(" in p and "loop_exit" in scopes_in(p)
                   for p in paths)
        assert all(scopes_in(p) == {"loop_exit"} for p in paths
                   if "loop_exit" in scopes_in(p))
    elif routed:  # the grouped products' backward carries its name too
        assert any("transpose(" in p and "moe_experts" in scopes_in(p)
                   for p in paths)
    # the backward pass carries the names too, through jvp and transpose
    assert any("transpose(" in p and "attention" in scopes_in(p)
               for p in paths)
    assert any("transpose(" in p and "lm_head" in scopes_in(p)
               for p in paths)
    # attention is opened inside attn_proj and is the innermost there
    assert any(re.search(r"attn_proj\)?/attention", p) for p in paths)
    # the delta rule recomputes its chunks in its own backward, remat or not
    assert any("rematted_computation" in p for p in paths) == (
        remat or routed == "linear")


@pytest.mark.parametrize("remat,attn_block,routed", FORMS)
def test_a_scope_changes_no_number(remat, attn_block, routed, monkeypatch):
    def outputs(step, state, data):
        new_state, metrics = step(state, data)
        return jax.device_get((new_state.params, new_state.opt_state,
                               metrics))

    with_scopes = outputs(*compiled(remat, attn_block, routed))
    opened = []

    @contextlib.contextmanager
    def no_scope(name):
        opened.append(name)
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = outputs(*build(remat, attn_block, routed))
    assert set(opened) == scopes_of(routed)  # the patch was what the step opened
    for a, b in zip(jax.tree.leaves(with_scopes), jax.tree.leaves(without)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_program_opens_only_catalogued_scopes():
    """Every ``scope("x")`` / ``scoped("x")`` literal in the package is in
    the catalog (a name outside it raises as the module is imported), the
    package calls ``jax.named_scope`` nowhere else, and each catalogued
    name has a site."""
    with pytest.raises(ValueError, match="scope catalog"):
        profiling.scope("not_in_the_catalog")
    with pytest.raises(ValueError, match="scope catalog"):
        profiling.scoped("not_in_the_catalog")
    used = set()
    package = os.path.join(REPO, "distributed_tensorflow_tpu")
    for dirpath, _, files in os.walk(package):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            tree = ast.parse(open(path).read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                callee = getattr(node.func, "attr",
                                 getattr(node.func, "id", None))
                if callee == "named_scope":
                    assert path.endswith(os.path.join("utils", "profiling.py"))
                if callee in ("scope", "scoped") and node.args and \
                        isinstance(node.args[0], ast.Constant):
                    used.add(node.args[0].value)
    assert used == set(SCOPES)
