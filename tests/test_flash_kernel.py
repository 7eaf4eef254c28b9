"""The fused causal attention (``ops/flash_attention.py``) held to the dense
triangle and to the ``lax.scan`` form of ``blockwise_attention``, values and
all three gradients, under the Pallas interpreter on the CPU; and the
dispatch between the two forms: what the shapes, the dtype and the lowering
platform select, and that the ``attention_path`` instant says so.

The interpreter is Pallas's generic one (``pallas_call(interpret=True)``:
the kernel's jaxpr evaluated with plain JAX operations), not
``pltpu.force_tpu_interpret_mode()``: that one runs kernels through ordered
host callbacks on threads, which ``jax.checkpoint`` refuses to split and
which deadlocked here in four runs of six when a model made several calls.

The kernels compile for the chip in ``tests/test_chip_compile.py`` (that
file holds the TPU compiler's per-process lock)."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.ops import attention, flash_attention
from distributed_tensorflow_tpu.ops.attention import (
    blockwise_attention,
    multi_head_attention,
)
from distributed_tensorflow_tpu.utils import telemetry
from distributed_tensorflow_tpu.utils.profiling import lowering_instant
from tests.mask_tables import assert_tables_follow

DH = 64
# bf16 keeps 8 bits: operands, p, ds and the results are each rounded once
TOL = 1e-2


@contextlib.contextmanager
def _kernels_interpreted():
    """Steer the dispatch as a TPU lowering would, with the kernels run by
    the Pallas interpreter. The kernels' wrappers are jitted, so their
    traces are dropped on the way in and out: none made under the patch is
    met outside it."""
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


@pytest.fixture
def fused_on_cpu():
    with _kernels_interpreted():
        yield


def _operands(s, h, seed=0, b=1, dtype=jnp.bfloat16):
    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, (b, s, h, DH), jnp.float32).astype(dtype)
            for k in keys]


def _value_and_grads(fn, q, k, v, g):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(g.astype(out.dtype))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@functools.lru_cache(maxsize=None)
def _fused(s, tile, h):
    q, k, v, g = _operands(s, h)
    with _kernels_interpreted():
        return _value_and_grads(
            lambda q, k, v: blockwise_attention(q, k, v, tile, causal=True),
            q, k, v, g)


def _reference(name, s, tile, h):
    q, k, v, g = _operands(s, h)
    if name == "scan":  # what a CPU lowering takes at any shape
        return _value_and_grads(
            lambda q, k, v: blockwise_attention(q, k, v, tile, causal=True),
            q, k, v, g)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    return _value_and_grads(
        lambda q, k, v: multi_head_attention(q, k, v, causal=True), *f32, g)


@pytest.mark.parametrize("reference", ["dense", "scan"])
@pytest.mark.parametrize("h", [2, 3])
@pytest.mark.parametrize("tile", [128, 256])
@pytest.mark.parametrize("s", [256, 512])
def test_fused_matches_the_reference(s, tile, h, reference):
    """out, dq, dk, dv of the kernels against the dense f32 triangle and
    against the scan on the same bf16 operands."""
    got = _fused(s, tile, h)
    want = _reference(reference, s, tile, h)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == jnp.bfloat16
        assert _rel(a, b) < TOL, (name, _rel(a, b))


@pytest.mark.parametrize("s,window,tile,h,hkv", [
    (512, 128, 128, 2, 2), (512, 200, 128, 4, 2), (512, 256, 256, 2, 1),
    (256, 100, 128, 2, 2), (384, 1000, 128, 2, 1)])
def test_fused_window_matches_the_dense_window_and_the_scan(fused_on_cpu, s,
                                                            window, tile, h,
                                                            hkv):
    """The causal window over the kernels' grids of live tiles (a window
    that is and is not a multiple of the tile, one wider than the sequence,
    grouped-query heads): out, dq, dk, dv against the dense f32 window and
    the scan on the same bf16 operands; the instant says the grid is the
    tiles that run."""
    mask = attention.Mask("window", window=window)
    q, _, _, g = _operands(s, h)
    _, k, v, _ = _operands(s, hkv, seed=5)

    def attend(q, k, v):
        return blockwise_attention(q, k, v, tile, mask=mask)

    telemetry.get_tracer().clear()
    got = _value_and_grads(attend, q, k, v, g)
    notes = {n["pass"]: n for n in telemetry.last_spans(100)
             if n["name"] == "attention_path" and n["path"] == "fused"}
    tq = flash_attention.query_tile(s, mask)
    assert notes["forward"]["mask"] == "window" \
        and notes["forward"]["window"] == window
    live, masked = assert_tables_follow(mask, s, tq, tile)
    for n in notes.values():
        assert (n["tiles_run"], n["grid_steps"], n["masked_tiles"]) == (
            live, live, masked)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    dense = _value_and_grads(
        lambda q, k, v: multi_head_attention(q, k, v, mask=mask), *f32, g)
    flash_attention.flash_forward.clear_cache()
    flash_attention.flash_backward.clear_cache()
    attention._by_platform, steered = (
        lambda fused, scan, *args: scan(*args)), attention._by_platform
    try:
        scan = _value_and_grads(attend, q, k, v, g)
    finally:
        attention._by_platform = steered
    for name, a, b, c in zip(("out", "dq", "dk", "dv"), got, dense, scan):
        assert a.shape == b.shape and a.dtype == jnp.bfloat16
        assert _rel(a, b) < TOL and _rel(a, c) < TOL, name


def test_the_causal_kernels_grid_is_the_tiles_that_run(fused_on_cpu):
    """No mask's grid has a step that runs nothing: 12 of the 16 tiles are
    at or under the diagonal, and 12 steps walk them (16 until PR 37, four
    of them skipped); the diagonal crosses 8."""
    q, k, v, g = _operands(1024, 2)  # two query tiles of 512, eight of keys

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, 128, causal=True)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    _, notes = _paths(jax.grad(loss, (0, 1, 2)), q, k, v)
    assert [(n["pass"], n["tiles_run"], n["grid_steps"], n["masked_tiles"])
            for n in notes] == [("forward", 12, 12, 8),
                                ("backward", 12, 12, 8)]
    assert "window" not in notes[0] and "mask" not in notes[0]


# the cells' own (mask, S, query tile, key tile) and what runs of them
CELLS = {
    "opt-125m.train-s2048": (attention.CAUSAL, 2048, 512, 512, (10, 4)),
    "opt-1.3b.train-s2048": (attention.CAUSAL, 2048, 512, 512, (10, 4)),
    "ouro-2.6b.train-b2-s4096": (attention.CAUSAL, 4096, 512, 512, (36, 8)),
    "laguna-xs2.train-s8192 full": (attention.CAUSAL, 8192, 512, 512,
                                    (136, 16)),
    "laguna-xs2.train-s8192 window": (attention.Mask("window", window=512),
                                      8192, 512, 512, (31, 31)),
    "sdar-30b-a3b.train-s4096": (attention.Mask("block_diffusion", 4096, 4),
                                 8192, 512, 512, (80, 24)),
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_cells_tables_list_the_tiles_that_run(cell):
    """(tiles that run, of which masked) a head, at the cells' shapes."""
    mask, s, tq, tk, want = CELLS[cell]
    assert flash_attention.query_tile(s, mask) == tq
    assert assert_tables_follow(mask, s, tq, tk) == want


@pytest.mark.parametrize("s,tq,tk", [
    (512, 128, 128), (512, 256, 128), (512, 128, 256), (1024, 256, 256),
    (1024, 512, 128)])
@pytest.mark.parametrize("kind", ["causal", "window", "block_diffusion"])
def test_the_tables_follow_the_dense_mask_at_the_same_shapes(kind, s, tq, tk):
    """One enumeration for every mask: the three kinds at the same
    sequences and tiles (query tiles larger and smaller than key tiles; a
    window off the tile grid; diffusion blocks far smaller than a tile, so
    a row has two live ranges)."""
    mask = {"causal": attention.CAUSAL,
            "window": attention.Mask("window", window=200),
            "block_diffusion": attention.Mask("block_diffusion", s // 2, 4)
            }[kind]
    assert mask.tiles_fit(s, tq, tk)
    live, masked = assert_tables_follow(mask, s, tq, tk)
    assert 0 < masked <= live <= (s // tq) * (s // tk)


def test_a_row_with_no_live_tile_is_refused():
    """Every row and column of every mask has its diagonal tile; a table
    that left one out would leave its result unwritten."""
    class Hollow(attention.Mask):
        def tile(self, q0, q1, k0, k1):
            visible, runs = super().tile(q0, q1, k0, k1)
            return visible, np.logical_and(runs, q0 > 0)

    with pytest.raises(ValueError, match="nothing to run"):
        Hollow("causal").live_tiles(512, 128, 128)


def test_a_query_tile_smaller_than_the_key_tile(fused_on_cpu, monkeypatch):
    """The tiles need not be equal: 128 queries against 256 keys (the
    cases above have query tiles as large as the key tile or larger)."""
    monkeypatch.setattr(flash_attention, "MAX_QUERY_TILE", 128)
    q, k, v, g = _operands(512, 2, seed=1)
    got = _value_and_grads(
        lambda q, k, v: blockwise_attention(q, k, v, 256, causal=True),
        q, k, v, g)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = _value_and_grads(
        lambda q, k, v: multi_head_attention(q, k, v, causal=True), *f32, g)
    for a, b in zip(got, want):
        assert _rel(a, b) < TOL


def test_fused_through_checkpoint(fused_on_cpu):
    """``jax.checkpoint`` (the trainer's ``--remat``) reruns the forward
    kernel in the backward pass and changes no number."""
    q, k, v, g = _operands(384, 2, seed=2)  # a shape no other test traces

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, 128, causal=True)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    plain = jax.grad(loss, (0, 1, 2))(q, k, v)
    remat = jax.grad(jax.checkpoint(loss), (0, 1, 2))(q, k, v)
    for a, b in zip(plain, remat):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def _calls(jaxpr, name):
    """How many equations of ``jaxpr``, and of the jaxprs inside its
    equations, call the jitted function ``name``."""
    from tools.dttcheck.inventory import _sub_jaxprs

    return sum((eqn.params.get("name") == name)
               + sum(_calls(sub, name) for value in eqn.params.values()
                     for sub in _sub_jaxprs(value))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("form", ["fused", "scan"])
def test_a_rematerialized_layer_runs_the_forward_once(form):
    """Through the model's checkpoint (``transformer._remat``, the
    trainer's ``--remat``) the backward pass has the layer's ``out`` and
    logsumexp and does not make them again: the gradient's jaxpr holds the
    forward (the kernel, the scan) once a layer where a checkpoint that
    keeps nothing holds it twice, and every gradient is bit-equal to the
    one taken with no checkpoint at all."""
    from distributed_tensorflow_tpu.models.transformer import _remat

    q, k, v, g = _operands(384, 2, seed=3)
    layers = 2

    def attend(q, k, v):
        return blockwise_attention(q, k, v, 128, causal=True)

    def loss(layer, q, k, v):
        for _ in range(layers):
            q = layer(q, k, v)
        return jnp.sum(q.astype(jnp.float32) * g.astype(jnp.float32))

    forms = {"none": attend, "kept": _remat(attend, ()),
             "bare": jax.checkpoint(attend)}
    # a CPU lowering traces both forms (and lowers the scan): count its own
    names = {"fused": ("flash_forward", "flash_backward"),
             "scan": ("_scan_forward", "_scan_backward")}[form]
    with _kernels_interpreted() if form == "fused" \
            else contextlib.nullcontext():
        grads, forwards, backwards = {}, {}, {}
        for name, layer in forms.items():
            grad = jax.grad(functools.partial(loss, layer), (0, 1, 2))
            grads[name] = grad(q, k, v)
            jaxpr = jax.make_jaxpr(grad)(q, k, v).jaxpr
            forwards[name] = _calls(jaxpr, names[0])
            backwards[name] = _calls(jaxpr, names[1])
    assert forwards == {"none": layers, "kept": layers, "bare": 2 * layers}
    assert backwards == dict.fromkeys(forms, layers)
    for a, b in zip(grads["none"], grads["kept"]):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def _lm_loss_and_grads(dtype, attn_block):
    model = TransformerLM(vocab_size=64, seq_len=256, d_model=128,
                          num_heads=2, num_blocks=2, attn_block=attn_block,
                          compute_dtype=dtype)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 257), 0, 64)

    def loss(p):
        return model.loss_with_metrics(p, tokens[:, :-1], tokens[:, 1:])[0]

    return jax.value_and_grad(loss)(params)


def test_fused_through_the_lm(fused_on_cpu, monkeypatch):
    """``TransformerLM(attn_block=128)``'s loss and gradient in bf16:
    with the kernels every leaf is as near the f32 model's (dense
    attention) as it is through the scan. (Two bf16 programs differ from
    each other by as much as each does from f32, 3-6 % of a leaf here:
    a gradient is a sum of cancelling terms.)"""
    fused_loss, fused_grads = _lm_loss_and_grads(jnp.bfloat16, 128)
    assert any(r["name"] == "attention_path" and r["path"] == "fused"
               for r in telemetry.last_spans(200))
    monkeypatch.setattr(attention, "_by_platform",
                        lambda fused, scan, *args: scan(*args))
    scan_loss, scan_grads = _lm_loss_and_grads(jnp.bfloat16, 128)
    f32_loss, f32_grads = _lm_loss_and_grads(None, None)
    assert abs(float(fused_loss) - float(f32_loss)) < 2e-4 * float(f32_loss)
    assert abs(float(fused_loss) - float(scan_loss)) < 1e-4 * float(f32_loss)
    for fused, scan, f32 in zip(*map(jax.tree.leaves,
                                     (fused_grads, scan_grads, f32_grads))):
        assert _rel(fused, f32) < 1.25 * _rel(scan, f32) + 0.005


def _paths(fn, *args):
    """The ``attention_path`` instants lowering ``fn`` records."""
    tracer = telemetry.get_tracer()
    tracer.clear()
    lowered = jax.jit(fn).lower(*args)
    return lowered, [r for r in telemetry.last_spans(100)
                     if r["name"] == "attention_path"]


@pytest.mark.parametrize("why,s,block,dtype,causal", [
    ("tiny shapes", 32, 8, jnp.bfloat16, True),
    ("a key tile off the 128 grid", 256, 64, jnp.bfloat16, True),
    ("S off the 128 grid", 192, 192, jnp.bfloat16, True),
    ("f32 operands", 256, 128, jnp.float32, True),
    ("no mask", 256, 128, jnp.bfloat16, False),
    ("a non-TPU lowering", 256, 128, jnp.bfloat16, True),
])
def test_everything_else_takes_the_scan(why, s, block, dtype, causal):
    q, k, v, g = _operands(s, 2, dtype=dtype)

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, block, causal=causal)
        return jnp.sum(out.astype(jnp.float32))

    lowered, notes = _paths(jax.grad(loss, (0, 1, 2)), q, k, v)
    assert [(n["path"], n["pass"]) for n in notes] == [
        ("scan", "forward"), ("scan", "backward")], why
    assert all(n["seq_len"] == s and n["q_tile"] == s and n["k_tile"] == block
               and n["dtype"] == jnp.dtype(dtype).name for n in notes)
    assert "custom_call" not in lowered.as_text()


def test_the_fused_path_says_so(fused_on_cpu):
    q, k, v, _ = _operands(256, 2)
    _, notes = _paths(
        lambda q, k, v: blockwise_attention(q, k, v, 128, causal=True),
        q, k, v)
    assert [(n["path"], n["pass"], n["seq_len"], n["q_tile"], n["k_tile"],
             n["dtype"]) for n in notes] == [
        ("fused", "forward", 256, 256, 128, "bfloat16")]


@pytest.mark.parametrize("shape,kshape,dtype,block,want", [
    ((8, 2048, 32, 64), None, jnp.bfloat16, 512, True),
    ((8, 2048, 12, 64), None, jnp.bfloat16, 512, True),
    ((1, 128, 1, 128), None, jnp.bfloat16, 128, True),
    ((8, 2048, 32, 64), None, jnp.float32, 512, False),
    ((8, 2048, 32, 64), None, jnp.float16, 512, False),
    ((8, 2048, 32, 64), (8, 1024, 32, 64), jnp.bfloat16, 512, False),
    ((8, 2048, 32, 64), None, jnp.bfloat16, 64, False),
    ((8, 2000, 32, 64), None, jnp.bfloat16, 400, False),
    ((8, 2048, 32, 32), None, jnp.bfloat16, 512, False),
    ((2, 64, 2, 16), None, jnp.bfloat16, 16, False),
])
def test_fusable(shape, kshape, dtype, block, want):
    q = jax.ShapeDtypeStruct(shape, dtype)
    kv = jax.ShapeDtypeStruct(kshape or shape, dtype)
    assert attention.fusable(q, kv, kv, block) is want


@pytest.mark.parametrize("s,want", [(128, 128), (256, 256), (2048, 512),
                                    (640, 128), (768, 384), (1536, 512)])
def test_query_tile_divides_the_sequence(s, want):
    assert flash_attention.query_tile(s) == want


def test_lowering_instant_is_recorded_at_lowering_and_emits_nothing():
    def f(x):
        return lowering_instant("probe", x, a=1, b="two") + 1

    x = jnp.arange(4.0)
    tracer = telemetry.get_tracer()
    tracer.clear()
    jaxpr = jax.make_jaxpr(f)(x)  # traced: nothing yet
    assert "lowering_instant" in str(jaxpr)
    assert not [r for r in telemetry.last_spans(10) if r["name"] == "probe"]
    lowered = jax.jit(f).lower(x)
    notes = [r for r in telemetry.last_spans(10) if r["name"] == "probe"]
    assert len(notes) == 1 and notes[0]["a"] == 1 and notes[0]["b"] == "two"
    assert notes[0]["instant"] and "lowering_instant" not in lowered.as_text()
    assert np.array_equal(jax.vmap(f)(x[None]), x[None] + 1)
    assert np.array_equal(f(x), x + 1)  # eager: recorded as it is called
