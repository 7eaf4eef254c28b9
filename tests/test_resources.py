"""Resource plane (utils/resources.py): the analytic per-chip budget
across the mode matrix, the comm ledger and its per-mode rows, the
MemoryMeter, the recompilation sentry (signature deltas + the storm
report), the OOM postmortem, the loop scalar contract, the serving
hbm block + headroom floor, and the mem_report / --comm CLIs."""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.utils import resources, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


@pytest.fixture(autouse=True)
def clean_plane():
    """Every test starts with the global plane quiet: no active meter/
    sentry, tracer ring cleared, no sink."""
    telemetry.configure(logdir=None, enabled=True)
    telemetry.get_tracer().clear()
    resources.activate()
    yield
    telemetry.configure(logdir=None, enabled=True)
    telemetry.get_tracer().clear()
    resources.activate()


def _cnn():
    from distributed_tensorflow_tpu.models import DeepCNN

    return DeepCNN()


def _lm(**kw):
    from distributed_tensorflow_tpu.models import get_model

    cfg = dict(vocab_size=64, seq_len=32, d_model=32, num_heads=2,
               num_blocks=4)
    cfg.update(kw)
    return get_model("lm", **cfg)


def _adam():
    from distributed_tensorflow_tpu.training import adam

    return adam(1e-3)


# ------------------------------------------------------ analytic budget


def test_budget_matches_zero_memory_budget():
    """The generalized budget must agree with the r10 ZeRO accounting
    leaf-for-leaf — same eval_shape, same padding convention."""
    from distributed_tensorflow_tpu.parallel.zero import zero_memory_budget

    model, opt = _cnn(), _adam()
    zb = zero_memory_budget(model, opt, 8)
    dp = resources.resource_budget(model, opt, 128, mode="dp",
                                   data_ways=8)
    z1 = resources.resource_budget(model, opt, 128, mode="zero1",
                                   data_ways=8, zero_level=1)
    z3 = resources.resource_budget(model, opt, 128, mode="zero3",
                                   data_ways=8, zero_level=3)
    assert dp["per_chip"]["params"] == zb["per_chip"]["replicated"]["params"]
    assert dp["per_chip"]["opt"] == zb["per_chip"]["replicated"]["opt"]
    assert z1["per_chip"]["opt"] == zb["per_chip"]["zero1"]["opt"]
    assert z1["per_chip"]["params"] == zb["per_chip"]["zero1"]["params"]
    assert z3["per_chip"]["params"] == zb["per_chip"]["zero3"]["params"]
    assert z3["per_chip"]["opt"] == zb["per_chip"]["zero3"]["opt"]
    # grads are the transient full leaves in every mode
    assert dp["per_chip"]["grads"] == zb["param_bytes"]


def test_budget_pp_tp_ep_shard_something():
    """Each model-axis mode's divisor must actually shrink the per-chip
    params — and never below full/K (the sharding can't create bytes)."""
    opt = _adam()
    lm = _lm()
    full = resources.resource_budget(lm, opt, 16)["per_chip"]["params"]
    pp = resources.resource_budget(lm, opt, 16, mode="pp", data_ways=2,
                                   model_axis=2)["per_chip"]["params"]
    tp = resources.resource_budget(lm, opt, 16, mode="tp", data_ways=4,
                                   model_axis=2)["per_chip"]["params"]
    assert full / 2 <= pp < full  # blocks halve, embed/head replicate
    assert full / 2 <= tp < full  # qkv/mlp split, norms replicate
    moe = _lm(num_blocks=2, moe_experts=4)
    ep_full = resources.resource_budget(moe, opt, 16)["per_chip"]["params"]
    ep = resources.resource_budget(moe, opt, 16, mode="ep", data_ways=4,
                                   model_axis=2)["per_chip"]["params"]
    assert ep < ep_full  # expert leaves halve


def test_budget_activation_rows_positive_every_family():
    for model in (_cnn(), _lm()):
        b = resources.resource_budget(model, _adam(), 32)
        assert b["per_chip"]["activations"] > 0
        assert all(r["bytes"] >= 0 for r in b["activation_rows"])
    # batch splits over the data axis
    b1 = resources.resource_budget(_cnn(), None, 128, data_ways=1)
    b8 = resources.resource_budget(_cnn(), None, 128, mode="dp",
                                   data_ways=8)
    assert b8["per_chip"]["activations"] < b1["per_chip"]["activations"]


def test_budget_without_optimizer_prices_params_only():
    b = resources.resource_budget(_cnn(), None, 8)
    assert b["per_chip"]["opt"] == 0
    assert b["per_chip"]["params"] > 0


# --------------------------------------------------------- comm ledger


def _padded_param_bytes(model, d: int) -> int:
    """ZeRO's wire payload: every leaf zero-pads to a multiple of the
    data-axis size before the flat chunking (the r18 jaxpr-proven
    convention — padding lanes ride the wire)."""
    import jax

    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    total = 0
    for leaf in jax.tree.leaves(params):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += (-(-n // d)) * d * np.dtype(leaf.dtype).itemsize
    return total


def test_comm_ledger_dp_and_zero_pins():
    """DP moves ~2|G|; ZeRO moves |G|+|P| at BOTH levels over the
    PADDED flat layout — the r10 doc table as ledger rows, hand-pinned
    and r18 jaxpr-proven (dttcheck found the pre-r18 rows priced
    unpadded bytes and a phantom level-3 backward re-gather: the
    checkpointed gather's output is itself the saved residual)."""
    model, opt = _cnn(), _adam()
    g = resources.resource_budget(model, opt, 128)["param_bytes_full"]
    gp = _padded_param_bytes(model, 8)
    assert gp > g  # the flagship CNN has non-multiple-of-8 leaves
    dp = resources.comm_ledger(model, opt, 128, mode="dp", data_ways=8)
    assert dp["comm_bytes_per_step"] == 2 * g  # unpadded: plain pmean
    z1 = resources.comm_ledger(model, opt, 128, mode="zero1",
                               data_ways=8, zero_level=1)
    assert z1["comm_bytes_per_step"] == 2 * gp  # |G|+|P| padded
    assert {r["collective"] for r in z1["rows"]} == {
        "psum_scatter(grads)", "all_gather(params)"}
    z3 = resources.comm_ledger(model, opt, 128, mode="zero3",
                               data_ways=8, zero_level=3)
    # |G| + ONE |P|: the serial path gathers once per step — no
    # backward re-gather reaches the wire (dttcheck-proven)
    assert z3["comm_bytes_per_step"] == 2 * gp
    assert {r["collective"] for r in z3["rows"]} == {
        "reduce_scatter(grad transpose)", "all_gather(params, forward)"}
    # one chip moves nothing
    local = resources.comm_ledger(model, opt, 128, mode="dp", data_ways=1)
    assert local["comm_bytes_per_step"] == 0


def test_comm_ledger_pp_hand_pinned():
    """PP ring bytes are TICK-exact (r18): one activation slot permutes
    on EVERY tick of the static schedule — bubble ticks included —
    each direction, plus the replicated-leaf grad psum over the stage
    axis the pre-r18 ledger missed."""
    import jax

    lm = _lm(seq_len=32, d_model=32)
    led = resources.comm_ledger(lm, _adam(), 16, mode="pp", data_ways=2,
                                model_axis=2, microbatches=2,
                                virtual_stages=2)
    act = (16 // 2 // 2) * 32 * 32 * 4   # per-microbatch (B/d/M, S, d) f32
    ticks = 2 * 2 + 2 - 1                # M*V + K - 1
    # replicated leaves: everything outside the blocks list
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    rep = sum(int(np.prod(l.shape)) * 4
              for key in ("tok", "pos", "ln_f", "head")
              for l in jax.tree.leaves(params[key]))
    pp_rows = [r for r in led["rows"] if r["axis"] == "model"]
    assert sum(r["bytes"] for r in pp_rows) == 2 * ticks * act + 2 * rep
    ring = [r for r in pp_rows if "ppermute" in r["collective"]]
    assert [r["bytes"] for r in ring] == [ticks * act, ticks * act]
    # the data-axis grad all-reduce rides along, at the PER-RANK
    # payload: block leaves contribute their 1/K stage shard
    data_rows = [r for r in led["rows"] if r["axis"] == "data"]
    blocks = sum(int(np.prod(l.shape)) * 4
                 for l in jax.tree.leaves(params["blocks"]))
    assert sum(r["bytes"] for r in data_rows) == 2 * (rep + blocks // 2)


def test_comm_ledger_tp_ep_sp_rows():
    lm = _lm()
    for mode in ("tp", "ep", "sp"):
        model = _lm(num_blocks=2, moe_experts=4) if mode == "ep" else lm
        led = resources.comm_ledger(model, _adam(), 16, mode=mode,
                                    data_ways=4, model_axis=2)
        model_rows = [r for r in led["rows"] if r["axis"] == "model"]
        assert model_rows, mode
        assert all(r["bytes"] > 0 for r in model_rows), (mode, model_rows)


def test_parallel_config_from_flags_mode_table():
    class F:
        model_axis = 1
        zero = 0
        pipeline = False
        expert_parallel = False
        seq_parallel = False
        virtual_stages = 1
        pp_microbatches = 0

    assert resources.parallel_config_from_flags(F(), 8)["mode"] == "dp"
    f = F(); f.zero = 1
    cfg = resources.parallel_config_from_flags(f, 8)
    assert cfg["mode"] == "zero1" and cfg["data_ways"] == 8
    f = F(); f.pipeline = True; f.model_axis = 2
    cfg = resources.parallel_config_from_flags(f, 8)
    assert cfg["mode"] == "pp" and cfg["data_ways"] == 4
    f = F(); f.model_axis = 2
    assert resources.parallel_config_from_flags(f, 8)["mode"] == "tp"


# --------------------------------------------------------- MemoryMeter


def test_memory_meter_samples_and_peak():
    import jax.numpy as jnp

    x = jnp.ones((256, 256), jnp.float32)  # noqa: F841 — held live
    m = resources.MemoryMeter(analytic_bytes=123)
    s = m.sample()
    assert s is not None and s["in_use"] > 0
    assert s["source"] in ("memory_stats", "live_arrays")
    out = m.scalars()
    assert out["hbm_in_use_bytes"] > 0
    assert out["hbm_peak_bytes"] >= out["hbm_in_use_bytes"] or True
    assert out["hbm_analytic_bytes"] == 123.0
    # peak is monotone even when usage drops
    peak = out["hbm_peak_bytes"]
    del x
    m.sample()
    assert m.scalars()["hbm_peak_bytes"] >= peak


def test_memory_meter_sample_cadence_and_instant_span():
    calls = {"n": 0}

    def fake():
        calls["n"] += 1
        return {"in_use": 100 * calls["n"], "peak": 100 * calls["n"],
                "limit": 1000, "source": "fake", "per_device": []}

    m = resources.MemoryMeter(sample_every=3, sample_fn=fake)
    for _ in range(6):
        m.scalars()
    assert calls["n"] == 2  # calls 0 and 3 sampled; the rest reused
    spans = [r for r in telemetry.last_spans(16)
             if r["name"] == "hbm_sample"]
    assert len(spans) == 2
    assert spans[-1]["in_use"] == 200


def test_memory_meter_headroom_pct():
    def fake():
        return {"in_use": 750, "peak": 800, "limit": 1000,
                "source": "fake", "per_device": []}

    m = resources.MemoryMeter(sample_fn=fake)
    out = m.scalars()
    assert out["hbm_headroom_pct"] == 25.0
    assert resources.headroom_pct(10, 0) == -1.0  # no limit = unknown


def test_sample_note_rides_the_flight_ring(tmp_path):
    telemetry.configure(logdir=str(tmp_path), host="worker-0")
    m = resources.MemoryMeter()
    resources.activate(meter=m)
    resources.sample_note("ckpt_write")
    path = telemetry.flight_recorder().dump("test")
    recs = [json.loads(l) for l in open(path)]
    tagged = [r for r in recs if r.get("name") == "hbm_sample"
              and r.get("tag") == "ckpt_write"]
    assert tagged, recs
    resources.sample_note("nobody_home")  # no meter after deactivate
    resources.activate()
    resources.sample_note("nobody_home")  # must be a quiet no-op


# ------------------------------------------------------ compile sentry


def test_sentry_signature_ledger_and_delta():
    cs = resources.CompileSentry()
    sig_a = (((32, 784), "float32"), ((32, 10), "float32"))
    sig_b = (((64, 784), "float32"), ((64, 10), "float32"))
    assert cs.observe("train_step", sig_a) is None  # first compile
    assert cs.observe("train_step", sig_a) is None  # cache hit
    delta = cs.observe("train_step", sig_b)
    assert "dim 0: 32 -> 64" in delta
    assert cs.recompiles_total == 1
    assert cs.site_signatures("train_step") == 2
    # a revisit of a known signature is NOT another recompile
    assert cs.observe("train_step", sig_a) is None
    assert cs.recompiles_total == 1
    # dtype churn is named as such
    sig_c = (((64, 784), "bfloat16"), ((64, 10), "float32"))
    assert "dtype float32 -> bfloat16" in cs.observe("train_step", sig_c)


def test_sentry_counts_real_backend_compiles():
    import jax
    import jax.numpy as jnp

    cs = resources.CompileSentry()
    resources.activate(sentry=cs)
    resources._install_compile_listener()
    fn = jax.jit(lambda a: (a + 1.0).sum())
    jax.block_until_ready(fn(jnp.ones((4, 4))))
    first = cs.compiles_total
    assert first >= 1
    assert cs.compile_time_s > 0
    jax.block_until_ready(fn(jnp.ones((4, 4))))  # cache hit
    assert cs.compiles_total == first
    jax.block_until_ready(fn(jnp.ones((8, 4))))  # new shape
    assert cs.compiles_total > first


def test_sentry_storm_trips_and_names_the_dim(tmp_path):
    """A deliberate shape-churn loop must trip the storm report with
    the changed dimension named, drop the recompile_storm span, and
    dump the flight recorder."""
    telemetry.configure(logdir=str(tmp_path), host="worker-0")
    cs = resources.CompileSentry(budget=3, window_s=60.0)
    for i, b in enumerate((8, 9, 10, 11, 12, 13)):
        cs.observe("train_step", (((b, 784), "float32"),))
    assert cs.storms == 1
    storm = [r for r in telemetry.last_spans(32)
             if r["name"] == "recompile_storm"]
    assert storm, "no recompile_storm instant span"
    assert "dim 0" in storm[-1]["delta"]
    assert storm[-1]["site"] == "train_step"
    fr = tmp_path / "flightrec-worker-0.jsonl"
    assert fr.exists()
    meta = json.loads(fr.read_text().splitlines()[0])
    assert meta["reason"].startswith("recompile_storm:")
    # the window cleared on report: the next churn starts a new count
    cs.observe("train_step", (((99, 784), "float32"),))
    assert cs.storms == 1


def test_sentry_signature_ledger_is_bounded():
    """A client-controlled signature axis (serve_decode's per-request
    max_new_tokens) must not grow the monitoring plane without bound —
    the per-site ledger evicts oldest-first past the cap."""
    cs = resources.CompileSentry()
    n = resources.MAX_SIGS_PER_SITE + 100
    for i in range(n):
        cs.observe("serve_decode", (4, 16, i))
    with cs._lock:
        held = len(cs._sites["serve_decode"])
    assert held <= resources.MAX_SIGS_PER_SITE + 1
    assert cs.recompiles_total == n - 1  # counting is unaffected


def test_sentry_budget_zero_never_trips():
    cs = resources.CompileSentry(budget=0)
    for b in range(8, 40):
        cs.observe("s", (((b, 4), "float32"),))
    assert cs.storms == 0
    assert cs.recompiles_total == 31


def test_scalars_shape():
    cs = resources.CompileSentry()
    out = cs.scalars()
    assert set(out) == {"compiles_total", "compile_time_s",
                        "compile_cache_hits", "recompiles_total"}


# ------------------------------------------------------- OOM postmortem


def test_oom_postmortem_subprocess(tmp_path):
    """A forced RESOURCE_EXHAUSTED crash leaves a flight-recorder
    postmortem naming the largest live buffers and the analytic budget
    — diagnosable from flightrec-*.jsonl alone (the acceptance
    drill)."""
    script = f"""
import jax, jax.numpy as jnp
from distributed_tensorflow_tpu.utils import telemetry, resources
from distributed_tensorflow_tpu.models import DeepCNN
from distributed_tensorflow_tpu.training import adam, create_train_state

telemetry.configure(logdir={str(tmp_path)!r}, host="worker-0")
model = DeepCNN()
budget = resources.resource_budget(model, adam(1e-3), 128)
meter = resources.MemoryMeter(analytic_bytes=budget["per_chip_state_bytes"])
resources.activate(meter=meter, sentry=resources.CompileSentry(),
                   budget=budget)
resources.install_oom_hook()
state = create_train_state(model, adam(1e-3), seed=0)
jax.block_until_ready(state.params)
meter.sample(tag="pre_oom")
big = jnp.ones((1024, 1024), jnp.float32)  # the buffer the report names
jax.block_until_ready(big)
raise RuntimeError(
    "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
    "9999999999 bytes")
"""
    p = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                       env=CPU_ENV, capture_output=True, text=True,
                       timeout=240)
    assert p.returncode != 0
    fr = tmp_path / "flightrec-worker-0.jsonl"
    assert fr.exists(), (p.stdout, p.stderr)
    recs = [json.loads(l) for l in fr.read_text().splitlines()]
    kinds = {r.get("kind") for r in recs}
    # the three postmortem sections: the note, the budget table, the
    # largest live buffers — plus the hbm samples riding the ring
    notes = [r for r in recs if r.get("kind") == "note"
             and "OOM postmortem" in r.get("note", "")]
    assert notes, kinds
    budgets = [r for r in recs if r.get("kind") == "hbm_budget"]
    assert budgets and budgets[0]["per_chip"]["params"] > 0
    assert budgets[0]["largest_leaves"]
    buffers = [r for r in recs if r.get("kind") == "live_buffer"]
    assert buffers, kinds
    # the 4 MB canary buffer must be among the largest
    assert any(r["nbytes"] == 1024 * 1024 * 4 for r in buffers), buffers
    samples = [r for r in recs if r.get("kind") == "span"
               and r.get("name") == "hbm_sample"]
    assert any(r.get("tag") == "pre_oom" for r in samples)


def test_is_oom_recognizer():
    class XlaRuntimeError(RuntimeError):
        pass

    assert resources._is_oom(XlaRuntimeError, XlaRuntimeError("boom"))
    assert resources._is_oom(RuntimeError,
                             RuntimeError("RESOURCE_EXHAUSTED: ..."))
    assert not resources._is_oom(ValueError, ValueError("bad shape"))


# ---------------------------------------- scalar contract (every loop)


@pytest.fixture
def fresh_flags():
    flags.define_reference_flags()
    flags.FLAGS._reset()
    yield
    flags.FLAGS._reset()


LOOP_VARIANTS = {
    "host_fed": [],
    "device_resident": ["--device_data", "--device_chunk=5"],
    "pp": ["--model=lm", "--dataset=lm", "--seq_len=32",
           "--vocab_size=16", "--d_model=32", "--num_heads=2",
           "--num_blocks=2", "--model_axis=2", "--pipeline"],
    # r14: the zero-bubble schedule is its own loop-variant surface
    # (explicit F/B/W scan, pp_step_zb spans) — it must emit the same
    # scalar family (zb needs >= 2 blocks per group, hence 4 blocks)
    "pp_zb": ["--model=lm", "--dataset=lm", "--seq_len=32",
              "--vocab_size=16", "--d_model=32", "--num_heads=2",
              "--num_blocks=4", "--model_axis=2", "--pipeline",
              "--pp_schedule=zb"],
    "zero": ["--zero=1"],
    # the device-resident driver's other two layouts ("device_resident"
    # is its plain one): the same contract from the one loop
    "pp_device": ["--model=lm", "--dataset=lm", "--seq_len=32",
                  "--vocab_size=16", "--d_model=32", "--num_heads=2",
                  "--num_blocks=2", "--model_axis=2", "--pipeline",
                  "--device_data", "--device_chunk=5"],
    "zero_device": ["--zero=1", "--device_data", "--device_chunk=5"],
    # r14: the overlapped-ZeRO collective pattern rides its own spans
    # (zero_step_overlap) and ledger pricing — same contract
    "zero_overlap": ["--zero=3", "--zero_overlap",
                     "--zero_bucket_mb=1"],
}

# THE scalar contract: every loop variant must emit this full set at
# the display cadence — a new loop variant that forgets the wiring
# fails this test loudly instead of shipping blind
STANDARD_SCALARS = (
    "images_per_sec",
    "step_host_wait_s", "step_dispatch_s", "step_device_s",
    "mfu", "model_flops_per_sec", "goodput", "resize_s",
    "hbm_in_use_bytes", "hbm_peak_bytes", "hbm_headroom_pct",
    "compiles_total", "compile_time_s", "compile_cache_hits",
    "recompiles_total",
    "comm_bytes_per_step", "comm_exposed_bytes_per_step",
)


@pytest.mark.parametrize("variant", sorted(LOOP_VARIANTS))
def test_scalar_contract_every_loop_variant(tmp_path, fresh_flags,
                                            variant):
    """Table-driven: every host-fed loop and every layout of the
    device-resident driver emits the STANDARD scalar
    set (throughput, breakdown, efficiency, hbm, compiles, comm) in
    metrics.jsonl, and the resource-plane markers land in the span
    sink."""
    from distributed_tensorflow_tpu.training.loop import train

    flags.FLAGS._parse([
        f"--logdir={tmp_path}/logs",
        f"--data_dir={tmp_path}/no-data",
        "--training_iter=10", "--batch_size=16", "--display_step=5",
        "--save_model_secs=100000", "--test_eval=false",
        *LOOP_VARIANTS[variant],
    ])
    res = train(flags.FLAGS, mode="sync")
    assert res.final_step == 10
    lines = [json.loads(l)
             for l in open(f"{tmp_path}/logs/metrics.jsonl")]
    full = [l for l in lines if "hbm_in_use_bytes" in l]
    assert full, f"{variant}: no resource scalars in {lines}"
    rec = full[-1]
    for key in STANDARD_SCALARS:
        assert key in rec, f"{variant}: scalar contract broken — no " \
                           f"{key!r} in {sorted(rec)}"
    assert rec["hbm_in_use_bytes"] > 0
    assert rec["compiles_total"] >= 1  # the step executable compiled
    assert rec["recompiles_total"] == 0  # stable shapes: no churn
    # every variant has a multi-chip axis on the 8-device mesh, so the
    # ledger always prices something
    assert rec["comm_bytes_per_step"] > 0
    span_files = glob.glob(f"{tmp_path}/logs/spans-*.jsonl")
    assert span_files
    names = {json.loads(l)["name"]
             for l in open(span_files[0]).read().splitlines()}
    assert "hbm_sample" in names, f"{variant}: {names}"
    assert "comm_ledger" in names, f"{variant}: {names}"


def test_telemetry_off_drops_resource_scalars(tmp_path, fresh_flags):
    from distributed_tensorflow_tpu.training.loop import train

    flags.FLAGS._parse([
        f"--logdir={tmp_path}/logs",
        f"--data_dir={tmp_path}/no-data",
        "--training_iter=6", "--batch_size=16", "--display_step=3",
        "--save_model_secs=100000", "--test_eval=false",
        "--telemetry=false",
    ])
    train(flags.FLAGS, mode="sync")
    lines = [json.loads(l)
             for l in open(f"{tmp_path}/logs/metrics.jsonl")]
    assert not any("hbm_in_use_bytes" in l for l in lines)
    assert not any("compiles_total" in l for l in lines)


# ------------------------------------------------------ flag validation


@pytest.mark.parametrize("argv,msg", [
    (["--hbm_sample_every=-1"], "--hbm_sample_every"),
    (["--recompile_budget=-2"], "--recompile_budget"),
    (["--serve_hbm_headroom_pct=100"], "--serve_hbm_headroom_pct"),
    (["--serve_hbm_headroom_pct=-5"], "--serve_hbm_headroom_pct"),
    (["--telemetry=false", "--recompile_budget=4"], "silently inert"),
    (["--telemetry=false", "--serve_hbm_headroom_pct=10"],
     "silently inert"),
    (["--telemetry=false", "--hbm_sample_every=5"], "silently inert"),
    (["--serve_hbm_headroom_pct=10", "--hbm_sample_every=0"],
     "silently inert"),
])
def test_resource_flag_validation(fresh_flags, argv, msg):
    with pytest.raises(ValueError, match="--"):
        try:
            flags.FLAGS._parse(argv)
        except ValueError as e:
            assert msg in str(e)
            raise


def test_resource_flag_defaults_pass(fresh_flags):
    flags.FLAGS._parse([])
    assert flags.FLAGS.hbm_sample_every == 1
    assert flags.FLAGS.recompile_budget == 0
    flags.FLAGS._reset()
    # telemetry=false with DEFAULT resource flags stays legal
    flags.FLAGS._parse(["--telemetry=false"])


# -------------------------------------------------- serving resources


SEQ = 16


class _HostModel:
    @staticmethod
    def apply(params, x):
        return np.asarray(x) @ params["w"]


def _serving_server(tmp_path, sample_fn, floor=0.0):
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        save_checkpoint,
    )
    from distributed_tensorflow_tpu.serving.batcher import DynamicBatcher
    from distributed_tensorflow_tpu.serving.engine import InferenceEngine
    from distributed_tensorflow_tpu.serving.server import (
        InferenceServer,
        InProcessClient,
        make_predict_runner,
        predict_group_key,
    )

    params = {"w": np.eye(SEQ, dtype=np.float32)}
    save_checkpoint(str(tmp_path), {"params": params}, 10)
    eng = InferenceEngine(_HostModel(), str(tmp_path), jit=False,
                          params_template=params, max_batch=4)
    sentry = resources.CompileSentry()
    eng.resources = resources.ResourceMonitor(
        resources.MemoryMeter(sample_fn=sample_fn), sentry, None)
    batcher = DynamicBatcher(make_predict_runner(eng),
                             group_key=predict_group_key,
                             max_batch=4, max_delay_ms=1.0,
                             queue_depth=16, name="predict")
    client = InProcessClient(predict_batcher=batcher)
    srv = InferenceServer(eng, client, port=0,
                          hbm_headroom_floor_pct=floor)
    # shutdown() deadlocks unless serve_forever is running — start the
    # background thread so close() in the finally blocks can return
    srv.start_background()
    return srv, batcher


def test_serving_metrics_hbm_block_and_compiles(tmp_path):
    def fake():
        return {"in_use": 600, "peak": 800, "limit": 1000,
                "source": "fake",
                "per_device": [{"device": 0, "in_use": 600, "peak": 800,
                                "limit": 1000}]}

    srv, batcher = _serving_server(tmp_path, fake)
    try:
        m = srv.metrics()
        assert m["hbm"]["in_use_bytes"] == 600
        assert m["hbm"]["headroom_pct"] == 40.0
        assert m["hbm"]["per_device"][0]["headroom_pct"] == 40.0
        assert m["compiles_total"] == 0.0
        assert m["recompiles_total"] == 0.0
        h = srv.healthz()
        assert h["ok"] and not h["hbm_low_headroom"]
        assert h["hbm_headroom_pct"] == 40.0
    finally:
        batcher.close(drain=False)
        srv.close()


def test_serving_healthz_503_below_headroom_floor(tmp_path):
    state = {"in_use": 100}

    def fake():
        return {"in_use": state["in_use"], "peak": state["in_use"],
                "limit": 1000, "source": "fake", "per_device": []}

    srv, batcher = _serving_server(tmp_path, fake, floor=15.0)
    try:
        assert srv.healthz()["ok"]  # 90% headroom, floor 15%
        state["in_use"] = 990       # 1% headroom: drain me
        import time as _time

        _time.sleep(1.1)  # past the sample_if_stale window
        h = srv.healthz()
        assert not h["ok"] and h["hbm_low_headroom"]
        import urllib.request

        try:
            urllib.request.urlopen(f"{srv.address}/healthz", timeout=10)
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            body = json.loads(e.read())
            assert body["hbm_low_headroom"]
    finally:
        batcher.close(drain=False)
        srv.close()


def test_serving_floor_judges_the_worst_device(tmp_path):
    """One device near its limit must trip the drain floor even when
    idle peers keep the AGGREGATE headroom comfortable."""
    def fake():
        return {"in_use": 1190, "peak": 1190, "limit": 2000,
                "source": "fake",
                "per_device": [
                    {"device": 0, "in_use": 990, "peak": 990,
                     "limit": 1000},   # 1% headroom: the leaker
                    {"device": 1, "in_use": 200, "peak": 200,
                     "limit": 1000}]}  # 80% headroom: idle peer

    srv, batcher = _serving_server(tmp_path, fake, floor=15.0)
    try:
        h = srv.healthz()
        # aggregate headroom is ~40% — above the floor — but device 0
        # is at 1%: the replica must drain
        assert h["hbm_headroom_pct"] > 15.0
        assert not h["ok"] and h["hbm_low_headroom"]
        m = srv.metrics()
        assert m["hbm"]["min_device_headroom_pct"] == 1.0
    finally:
        batcher.close(drain=False)
        srv.close()


def test_monitor_serve_tp_override_prices_sharded_params():
    """The serving entry point's --serve_tp override: a TP replica's
    analytic budget prices the 1/K params each chip holds."""
    class F:
        telemetry = True
        hbm_sample_every = 1
        recompile_budget = 0
        model_axis = 1
        zero = 0
        pipeline = False
        expert_parallel = False
        seq_parallel = False
        virtual_stages = 1
        pp_microbatches = 0

    lm = _lm()
    plain = resources.monitor_from_flags(F(), lm, None, 8, 8)
    tp = resources.monitor_from_flags(F(), lm, None, 8, 8, model_axis=2)
    assert tp.meter.analytic_bytes < plain.meter.analytic_bytes


def test_serving_unknown_headroom_never_trips_floor(tmp_path):
    def fake():  # no limit reported (the CPU-mesh replica)
        return {"in_use": 10 ** 12, "peak": 10 ** 12, "limit": 0,
                "source": "live_arrays", "per_device": []}

    srv, batcher = _serving_server(tmp_path, fake, floor=50.0)
    try:
        h = srv.healthz()
        assert h["ok"] and h["hbm_headroom_pct"] == -1.0
    finally:
        batcher.close(drain=False)
        srv.close()


def test_engine_signatures_feed_the_active_sentry(tmp_path):
    def fake():
        return {"in_use": 1, "peak": 1, "limit": 0, "source": "fake",
                "per_device": []}

    srv, batcher = _serving_server(tmp_path, fake)
    try:
        resources.activate(sentry=srv.resources.sentry)
        eng = srv.engine
        eng.predict(np.ones((3, SEQ), np.float32))  # bucket 4
        eng.predict(np.ones((4, SEQ), np.float32))  # same bucket: no new sig
        assert srv.resources.sentry.site_signatures("serve_predict") == 1
        eng.predict(np.ones((2, SEQ), np.float32))  # bucket 2: a new sig
        assert srv.resources.sentry.site_signatures("serve_predict") == 2
        assert srv.resources.sentry.recompiles_total == 1
    finally:
        batcher.close(drain=False)
        srv.close()


# --------------------------------------------------------------- tools


def test_mem_report_cli(tmp_path):
    logdir = tmp_path / "logs"
    logdir.mkdir()
    with open(logdir / "metrics.jsonl", "w") as f:
        for step, b in ((5, 1000), (10, 3000), (15, 2000)):
            f.write(json.dumps({"step": step, "hbm_in_use_bytes": b,
                                "hbm_peak_bytes": max(b, 3000),
                                "hbm_headroom_pct": 50.0,
                                "compiles_total": 2.0,
                                "comm_bytes_per_step": 123456.0}) + "\n")
    p = subprocess.run(
        [sys.executable, "tools/mem_report.py", str(logdir),
         "--model", "deep_cnn", "--optimizer", "adam", "--batch", "128",
         "--d", "8", "--zero", "1"],
        cwd=REPO, env=CPU_ENV, capture_output=True, text=True,
        timeout=240)
    assert p.returncode == 0, p.stderr
    assert "hbm_in_use_bytes" in p.stdout
    assert "analytic per-chip budget" in p.stdout
    assert "live peak vs analytic" in p.stdout
    assert "mode=zero1" in p.stdout


def test_mem_report_scalars_only_no_run(tmp_path):
    logdir = tmp_path / "empty"
    logdir.mkdir()
    p = subprocess.run(
        [sys.executable, "tools/mem_report.py", str(logdir),
         "--no-analytic"],
        cwd=REPO, env=CPU_ENV, capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 0, p.stderr
    assert "no resource-plane scalars" in p.stdout


def test_trace_ops_comm_cli():
    p = subprocess.run(
        [sys.executable, "tools/trace_ops.py", "--comm", "lm", "8",
         "--batch", "32"],
        cwd=REPO, env=CPU_ENV, capture_output=True, text=True,
        timeout=240)
    assert p.returncode == 0, p.stderr
    for mode in ("dp", "zero1", "zero3", "pp", "tp", "sp"):
        assert f"\n{mode} (" in p.stdout, p.stdout
    assert "all_reduce(grads)" in p.stdout
    assert "ppermute(activations, forward)" in p.stdout


def test_fleet_report_hbm_and_comm_columns(tmp_path):
    sys.path.insert(0, REPO)
    from tools.fleet_report import analyze

    for host, peak in (("worker-0", 111 * 2 ** 20),
                       ("worker-1", 222 * 2 ** 20)):
        with open(tmp_path / f"spans-{host}.jsonl", "w") as f:
            f.write(json.dumps({
                "name": "comm_ledger", "ts": 1.0, "dur_s": 0.0,
                "host": host, "instant": True, "mode": "dp",
                "comm_bytes_per_step": 777}) + "\n")
            for i, b in enumerate((peak // 2, peak)):
                f.write(json.dumps({
                    "name": "hbm_sample", "ts": 2.0 + i, "dur_s": 0.0,
                    "host": host, "instant": True,
                    "in_use": b, "peak": b, "limit": 0}) + "\n")
            f.write(json.dumps({
                "name": "train_step", "ts": 5.0, "dur_s": 0.01,
                "host": host, "step": 1}) + "\n")
    report = analyze(sorted(str(p) for p in
                            tmp_path.glob("spans-*.jsonl")))
    assert report["hosts"]["worker-0"]["hbm_peak_bytes"] == 111 * 2 ** 20
    assert report["hosts"]["worker-1"]["hbm_peak_bytes"] == 222 * 2 ** 20
    assert report["hosts"]["worker-0"]["comm_bytes_per_step"] == 777
    # hosts without the markers read None, not crash
    from tools.fleet_report import print_report
    import io

    buf = io.StringIO()
    print_report(report, out=buf)
    assert "hbm_peak" in buf.getvalue()


# --------------------------------------------------------------- bench


def test_bench_resources_phase_fields():
    import bench

    bench._RESOURCES_CACHE.clear()
    out = bench.resources_phase()
    assert out.get("resources_error") is None, out
    assert out["resources_hbm_live_bytes"] > 0
    assert out["resources_hbm_source"] in ("memory_stats", "live_arrays")
    assert out["resources_compiles_distinct_shapes"] == 2
    assert out["resources_recompiles"] == 1
    assert out["resources_comm_bytes_dp"] > 0
    # the live/analytic cross-check is a sane ratio, not a unit error
    assert 0.1 < out["resources_live_vs_analytic"] < 100


def test_bench_degraded_record_resources_non_null():
    import bench

    rec = bench.degraded_record("UNAVAILABLE: socket closed")
    assert rec["resources_hbm_live_bytes"] is not None
    assert rec["resources_comm_bytes_dp"] is not None
    assert rec["resources_compiles_distinct_shapes"] == 2
