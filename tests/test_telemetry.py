"""Telemetry spine (utils/telemetry.py): span tracing, step-time
breakdown, hang watchdog, crash flight recorder — and the satellites
(StreamingHistogram snapshot consistency, MetricsLogger flush/thread
safety, serving /healthz + /metrics routes, trace_view CLI, bench
phase)."""

import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.utils import faults, telemetry
from distributed_tensorflow_tpu.utils.telemetry import (
    StepTimer,
    Watchdog,
    chrome_trace,
    trace_span,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


@pytest.fixture(autouse=True)
def clean_telemetry():
    """Every test starts with the global spine quiet: ring cleared, no
    sink, no watchdog; faults disarmed."""
    telemetry.configure(logdir=None, enabled=True)
    telemetry.get_tracer().clear()
    faults.reset()
    yield
    telemetry.configure(logdir=None, enabled=True)
    telemetry.get_tracer().clear()
    faults.reset()


# ------------------------------------------------- what --remat keeps

@pytest.mark.parametrize("remat,attn_block,names", [
    (True, 8, ["attention_lse", "attention_out"]),
    (True, None, None),   # dense attention names nothing: nothing is kept
    (False, 8, None),     # no checkpoint, no policy
], ids=["remat-attn_block", "remat-dense", "attn_block-alone"])
def test_remat_saved_instant_names_what_a_block_keeps(remat, attn_block,
                                                      names):
    """Tracing the gradient of a model with ``remat=True`` records one
    ``remat_saved`` instant: the names its blocks' checkpoint keeps and
    the bytes a block they cost (out (B, S, H, Dh) in the compute dtype,
    the logsumexp (B, H, S) f32)."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.transformer import TransformerLM

    b, s, h, dh = 2, 16, 2, 8
    model = TransformerLM(vocab_size=16, seq_len=s, d_model=h * dh,
                          num_heads=h, num_blocks=3, remat=remat,
                          attn_block=attn_block, compute_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    x = jax.ShapeDtypeStruct((b, s), jnp.int32)
    jax.make_jaxpr(jax.grad(
        lambda p, x: model.loss_with_metrics(p, x, x)[0]))(params, x)
    saved = [r for r in telemetry.last_spans(100)
             if r["name"] == "remat_saved"]
    if names is None:
        assert saved == []
        return
    (rec,) = saved  # once a traced model, not once a block
    assert rec["instant"] and rec["names"] == names
    assert rec["bytes_per_block"] == b * s * h * dh * 2 + b * h * s * 4


# ------------------------------------------------------------- spans


def test_span_nesting_depth_and_attrs():
    with trace_span("outer", step=7):
        with trace_span("inner", what="x"):
            pass
    inner, outer = telemetry.last_spans(2)
    assert outer["name"] == "outer" and outer["step"] == 7
    assert outer["depth"] == 0
    assert inner["name"] == "inner" and inner["what"] == "x"
    assert inner["depth"] == 1  # nested under outer on this thread
    assert inner["dur_s"] <= outer["dur_s"]


def test_span_error_tagged():
    with pytest.raises(RuntimeError):
        with trace_span("boom"):
            raise RuntimeError("x")
    rec = telemetry.last_spans(1)[0]
    assert rec["name"] == "boom" and rec["error"] == "RuntimeError"


def test_span_disabled_is_noop():
    tracer = telemetry.get_tracer()
    tracer.enabled = False
    try:
        before = len(telemetry.last_spans(10 ** 6))
        with trace_span("invisible"):
            pass
        assert len(telemetry.last_spans(10 ** 6)) == before
    finally:
        tracer.enabled = True


def test_span_thread_safety():
    """Concurrent spans from many threads: every record intact, per-
    thread nesting depths correct."""
    n_threads, per_thread = 8, 100  # 1600 spans: under the 2048 ring

    def work():
        for i in range(per_thread):
            with trace_span("t_outer", i=i):
                with trace_span("t_inner"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = telemetry.last_spans(10 ** 6)
    mine = [r for r in recs if r["name"] in ("t_outer", "t_inner")]
    assert len(mine) == n_threads * per_thread * 2
    for r in mine:
        assert r["depth"] == (0 if r["name"] == "t_outer" else 1)
        assert r["dur_s"] >= 0 and r["ts"] > 0


def test_chrome_trace_export_valid():
    with trace_span("a", step=1):
        pass
    telemetry.get_tracer().record_instant("fault:test", mode="error")
    ct = chrome_trace()
    assert set(ct) == {"traceEvents", "displayTimeUnit"}
    evs = ct["traceEvents"]
    assert evs, "no events exported"
    for ev in evs:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], float) and "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert "dur" in ev
    names = {ev["name"] for ev in evs}
    assert {"a", "fault:test"} <= names
    json.dumps(ct)  # must be JSON-serializable as-is


def test_tracer_jsonl_sink_batched_flush(tmp_path):
    telemetry.configure(logdir=str(tmp_path), host="worker-0")
    with trace_span("sunk", step=3):
        pass
    path = tmp_path / "spans-worker-0.jsonl"
    assert not path.exists() or "sunk" not in path.read_text()
    telemetry.get_tracer().flush()
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert any(r["name"] == "sunk" and r["step"] == 3 for r in recs)


def test_span_ids_and_parents_nest_across_threads():
    """Every record has an id of its own; ``parent`` is the id of the span
    open on the SAME thread (None at the top), whatever other threads have
    open — so self time needs neither ``depth`` nor the thread id."""
    inside = threading.Event()
    release = threading.Event()

    def other():
        with trace_span("other_outer"):
            inside.set()
            release.wait(5)
            with trace_span("other_inner"):
                telemetry.get_tracer().record_instant("fault:other")

    t = threading.Thread(target=other)
    with trace_span("main_outer"):
        t.start()
        assert inside.wait(5)
        with trace_span("main_inner"):  # opened while other_outer is open
            pass
        release.set()
        t.join(5)
        assert not t.is_alive()
    by = {r["name"]: r for r in telemetry.last_spans(10)}
    assert len({r["id"] for r in by.values()}) == 5
    assert by["main_outer"]["parent"] is None
    assert by["other_outer"]["parent"] is None
    assert by["main_inner"]["parent"] == by["main_outer"]["id"]
    assert by["other_inner"]["parent"] == by["other_outer"]["id"]
    assert by["fault:other"]["parent"] == by["other_inner"]["id"]
    # self time of a parent: its duration less its children's
    kids = [r for r in by.values() if r["parent"] == by["main_outer"]["id"]]
    assert [k["name"] for k in kids] == ["main_inner"]
    assert by["main_outer"]["dur_s"] - kids[0]["dur_s"] > 0
    # one monotonic clock beside the epoch: a child starts after its parent
    assert by["main_inner"]["pc"] >= by["main_outer"]["pc"]


def test_spans_file_starts_with_a_header_line(tmp_path):
    """Each configuration owes the file one header: the run's id, the pid
    and one reading of the epoch and of perf_counter taken together; it is
    no span (neither ``ts`` nor ``dur_s``), and a second configuration of
    the same file (a restart into one logdir) writes a second one."""
    path = tmp_path / "spans-worker-0.jsonl"
    runs = []
    for _ in range(2):
        before = (time.time(), time.perf_counter())
        telemetry.configure(logdir=str(tmp_path), host="worker-0")
        with trace_span("a"):
            pass
        telemetry.get_tracer().flush()
        telemetry.get_tracer().flush()  # nothing pending: no second header
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        head = [r for r in recs if r.get("kind") == "header"]
        runs.append(head[-1])
        assert head[-1]["pid"] == os.getpid()
        assert before[0] <= head[-1]["epoch"] <= time.time()
        assert before[1] <= head[-1]["perf_counter"] <= time.perf_counter()
        assert "ts" not in head[-1] and "dur_s" not in head[-1]
    assert recs[0]["kind"] == "header" and recs[1]["name"] == "a"
    assert [r.get("kind") for r in recs] == ["header", None, "header", None]
    assert runs[0]["run"] != runs[1]["run"]
    # the pair places a record's monotonic ``pc`` on the epoch of its ``ts``
    span = recs[-1]
    on_epoch = runs[1]["epoch"] + span["pc"] - runs[1]["perf_counter"]
    assert on_epoch == pytest.approx(span["ts"], abs=0.05)


def test_annotator_hook_once_a_span_and_never_without_one():
    calls = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            calls.append(("enter", self.name))

        def __exit__(self, *exc):
            calls.append(("exit", self.name))

    with trace_span("unhooked"):
        pass
    assert calls == []
    telemetry.set_annotator(Annotation)
    try:
        with trace_span("outer"):
            with trace_span("inner"):
                pass
        telemetry.get_tracer().record_instant("fault:x")  # no interval
        assert calls == [("enter", "outer"), ("enter", "inner"),
                         ("exit", "inner"), ("exit", "outer")]
        telemetry.configure(logdir=None, enabled=False)
        with trace_span("disabled"):
            pass
        assert len(calls) == 4
    finally:
        telemetry.set_annotator(None)
    telemetry.configure(logdir=None, enabled=True)
    with trace_span("unhooked_again"):
        pass
    assert len(calls) == 4


def test_chrome_trace_carries_ids_and_still_loads():
    with trace_span("outer"):
        with trace_span("inner"):
            pass
    ct = json.loads(json.dumps(chrome_trace()))
    by = {e["name"]: e for e in ct["traceEvents"]}
    assert by["inner"]["args"]["parent"] == by["outer"]["args"]["id"]
    assert by["outer"]["args"]["parent"] is None
    assert by["outer"]["ph"] == "X" and by["outer"]["dur"] >= by["inner"]["dur"]


# ----------------------------------------------------- step breakdown


def test_step_timer_window_means_and_reset():
    st = StepTimer()
    for _ in range(4):
        st.add("host_wait", 0.01)
        st.add("dispatch", 0.02)
        st.steps()
    st.add("device", 0.04)  # one cadenced block in the window
    out = st.scalars()
    assert out["step_host_wait_s"] == pytest.approx(0.01, rel=1e-6)
    assert out["step_dispatch_s"] == pytest.approx(0.02, rel=1e-6)
    assert out["step_device_s"] == pytest.approx(0.01, rel=1e-6)
    # window reset: a second read is all zeros over an empty window
    out2 = st.scalars()
    assert all(v == 0.0 for v in out2.values())


def test_step_timer_cumulative_work_survives_windows():
    """cumulative_work (the straggler-attribution numerator) counts
    host_wait+dispatch across scalars() window turns, and clears only
    on a full reset (the compile boundary)."""
    st = StepTimer()
    st.add("host_wait", 0.1)
    st.add("dispatch", 0.2)
    st.add("device", 5.0)  # collective wait: NOT work
    st.steps(2)
    st.scalars()  # window turn must not clear the cumulative ledger
    st.add("dispatch", 0.3)
    st.steps()
    work, steps = st.cumulative_work()
    assert work == pytest.approx(0.6)
    assert steps == 3
    st.reset()
    assert st.cumulative_work() == (0.0, 0)


# ------------------------------------------------------------ watchdog


def test_watchdog_fires_and_dumps_on_stall(tmp_path):
    """A deliberately stalled fake dispatch becomes a report: the
    stalled op's name, recent spans, and thread stacks."""
    with trace_span("before_the_hang", step=41):
        pass
    out_path = tmp_path / "wd.txt"
    with open(out_path, "w") as out:
        wd = Watchdog(0.2, out=out)
        try:
            with wd.arm("fake_dispatch", step=42):
                time.sleep(0.7)  # the stall
            time.sleep(0.1)
        finally:
            wd.close()
    assert wd.fired == 1
    txt = out_path.read_text()
    assert "WATCHDOG" in txt and "fake_dispatch" in txt
    assert "'step': 42" in txt
    assert "before_the_hang" in txt  # the last-K-spans section
    assert "Thread" in txt  # faulthandler all-thread stacks


def test_watchdog_quiet_on_healthy_loop(tmp_path):
    with open(tmp_path / "wd.txt", "w") as out:
        wd = Watchdog(0.5, out=out)
        try:
            for _ in range(20):
                with wd.arm("healthy_dispatch"):
                    time.sleep(0.01)
            time.sleep(0.8)  # disarmed: expiry never fires
        finally:
            wd.close()
    assert wd.fired == 0


def test_watchdog_via_configure_and_armed(tmp_path):
    telemetry.configure(logdir=str(tmp_path), watchdog_s=0.2)
    wd = telemetry.get_watchdog()
    assert wd is not None
    wd._out = open(tmp_path / "wd.txt", "w")
    try:
        with telemetry.armed("cfg_dispatch"):
            time.sleep(0.6)
        time.sleep(0.1)
        assert wd.fired == 1
        # the fire also dumped the flight recorder
        fr = tmp_path / "flightrec-worker-0.jsonl"
        assert fr.exists()
        meta = json.loads(fr.read_text().splitlines()[0])
        assert meta["reason"].startswith("watchdog:")
    finally:
        wd._out.close()
        telemetry.configure(logdir=None)
    # watchdog removed: armed() is a no-op again
    assert telemetry.get_watchdog() is None


# ----------------------------------------------------- flight recorder


def test_flightrec_dump_on_injected_ckpt_write_error(tmp_path):
    """mode=error at ckpt_write: the dump happens at the fire (not the
    excepthook), contains the pre-crash spans, and its last span is the
    injected fault marker."""
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        save_checkpoint,
    )

    telemetry.configure(logdir=str(tmp_path), host="worker-0")
    faults.configure("ckpt_write:mode=error")
    with trace_span("pre_crash_work", step=5):
        pass
    with pytest.raises(faults.InjectedFault):
        save_checkpoint(str(tmp_path),
                        {"params": {"w": np.arange(8, dtype=np.float32)}}, 10)
    fr = tmp_path / "flightrec-worker-0.jsonl"
    assert fr.exists()
    recs = [json.loads(l) for l in fr.read_text().splitlines()]
    assert recs[0]["kind"] == "meta"
    assert recs[0]["reason"] == "fault:ckpt_write:error"
    spans = [r for r in recs if r.get("kind") == "span"]
    assert any(r["name"] == "pre_crash_work" for r in spans)
    assert spans[-1]["name"] == "fault:ckpt_write"
    assert spans[-1]["mode"] == "error"


def test_flightrec_survives_injected_hard_crash(tmp_path):
    """mode=crash is os._exit — no atexit, no excepthook. The fault-fire
    dump is the postmortem's only chance; assert it lands and ends with
    the injected ckpt_write fault (the PR-3 chaos scenario's shape)."""
    script = f"""
import numpy as np
from distributed_tensorflow_tpu.utils import telemetry, faults
from distributed_tensorflow_tpu.checkpoint.checkpoint import save_checkpoint
telemetry.configure(logdir={str(tmp_path)!r}, host="worker-0")
faults.configure("ckpt_write:mode=crash")
with telemetry.trace_span("pre_crash_work", step=40):
    pass
save_checkpoint({str(tmp_path)!r}, {{"params": {{"w": np.arange(8, dtype=np.float32)}}}}, 40)
print("NOT REACHED")
"""
    p = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                       env=CPU_ENV, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == faults.FAULT_EXIT_CODE, (p.stdout, p.stderr)
    assert "NOT REACHED" not in p.stdout
    fr = tmp_path / "flightrec-worker-0.jsonl"
    assert fr.exists(), (p.stdout, p.stderr)
    recs = [json.loads(l) for l in fr.read_text().splitlines()]
    assert recs[0]["kind"] == "meta"
    assert recs[0]["reason"] == "fault:ckpt_write:crash"
    spans = [r for r in recs if r.get("kind") == "span"]
    assert any(r["name"] == "pre_crash_work" for r in spans)
    assert spans[-1]["name"] == "fault:ckpt_write"
    assert spans[-1]["mode"] == "crash"


def test_flightrec_ring_is_bounded(tmp_path):
    telemetry.configure(logdir=str(tmp_path), host="worker-0",
                        flight_events=16)
    for i in range(100):
        with trace_span("flood", i=i):
            pass
    path = telemetry.flight_recorder().dump("test")
    recs = [json.loads(l) for l in open(path).read().splitlines()]
    spans = [r for r in recs if r.get("kind") == "span"]
    assert len(spans) == 16  # the ring kept only the newest
    assert spans[-1]["i"] == 99


# ------------------------------------- step breakdown in the real loops


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
    "zero": ["--zero=1"],
}


@pytest.mark.parametrize("variant", sorted(LOOP_VARIANTS))
def test_step_breakdown_scalars_in_every_loop_variant(
        tmp_path, fresh_flags, variant):
    """All four loop variants emit the step-time breakdown next to the
    throughput scalar, and their spans land in the sink."""
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
    breakdown = [l for l in lines if "step_dispatch_s" in l]
    assert breakdown, f"{variant}: no breakdown scalars in {lines}"
    rec = breakdown[-1]
    for key in ("step_host_wait_s", "step_dispatch_s", "step_device_s"):
        assert key in rec and rec[key] >= 0
    assert "images_per_sec" in rec  # next to the throughput number
    # r12 efficiency accounting rides the same emission in every variant
    for key in ("mfu", "model_flops_per_sec", "goodput"):
        assert key in rec, f"{variant}: no {key} scalar in {rec}"
    assert 0.0 <= rec["mfu"] <= 1.0
    assert 0.0 <= rec["goodput"] <= 1.0
    assert rec["model_flops_per_sec"] >= 0
    span_files = glob.glob(f"{tmp_path}/logs/spans-*.jsonl")
    assert span_files, f"{variant}: no span sink"
    names = {json.loads(l)["name"]
             for l in open(span_files[0]).read().splitlines()}
    assert "ckpt_write" in names, names  # the final save traced
    dispatch_spans = {"host_fed": "train_step",
                      "device_resident": "device_chunk",
                      "pp": "pp_step", "zero": "zero_step"}
    assert dispatch_spans[variant] in names, (variant, names)


DISPLAY_VARIANTS = {
    "host_fed": [],
    "device_resident": ["--device_data", "--device_chunk=5"],
    "zero": ["--zero=1"],
    "zero_device": ["--zero=1", "--device_data", "--device_chunk=5"],
}


@pytest.mark.parametrize("variant", sorted(DISPLAY_VARIANTS))
def test_display_spans_and_the_synced_row_between_them(
        tmp_path, fresh_flags, variant):
    """One display is ``display_stage`` (only where the loop stages a fresh
    batch for it), ``display_wait``, ``display_eval``, ``display_log``, in
    that order at the top of the main thread, and the synced row is written
    right after the readback: its time lies between ``display_eval``'s end
    and ``display_log``'s end."""
    from distributed_tensorflow_tpu.training.loop import train

    flags.FLAGS._parse([
        f"--logdir={tmp_path}/logs", f"--data_dir={tmp_path}/no-data",
        "--training_iter=15", "--batch_size=16", "--display_step=5",
        "--save_model_secs=100000", "--test_eval=false",
        *DISPLAY_VARIANTS[variant]])
    train(flags.FLAGS, mode="sync")
    rows = [json.loads(l) for l in open(f"{tmp_path}/logs/metrics.jsonl")]
    synced = {r["step"]: r["time"] for r in rows if "mini_batch_loss" in r}
    spans = [json.loads(l) for l in
             open(glob.glob(f"{tmp_path}/logs/spans-*.jsonl")[0])]
    staged = "device" in variant
    parts = (["display_stage"] if staged else []) + [
        "display_wait", "display_eval", "display_log"]
    for step in (0, 5, 10):
        mine = [s for s in spans if s["name"].startswith("display_")
                and s.get("step") == step]
        assert [s["name"] for s in mine] == parts, (variant, step, mine)
        assert all(s["parent"] is None for s in mine)
        assert [s["id"] for s in mine] == sorted(s["id"] for s in mine)
        by = {s["name"]: s for s in mine}
        end = lambda s: s["ts"] + s["dur_s"]
        slack = 1e-3  # ``ts`` is the epoch clock, ``dur_s`` the monotonic
        assert end(by["display_eval"]) - slack <= synced[step] \
            <= end(by["display_log"]) + slack
        assert by["display_log"]["ts"] <= synced[step] + slack
    if staged:
        # the staging's seconds are the display's share of host_wait (a
        # mean over the five steps since the last display), read off the
        # span and not timed a second time
        scalars = [r for r in rows if "step_host_wait_s" in r
                   and r["step"] == 10][0]
        stage = [s for s in spans if s["name"] == "display_stage"
                 and s.get("step") == 10][0]
        assert scalars["step_host_wait_s"] == pytest.approx(
            stage["dur_s"] / 5, rel=1e-3, abs=1e-9)


# ------------------------------------------- serving /healthz /metrics


SEQ = 16


class _HostModel:
    @staticmethod
    def apply(params, x):
        return np.asarray(x) @ params["w"]


def _serving_stack(tmp_path):
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        save_checkpoint,
    )
    from distributed_tensorflow_tpu.serving.batcher import DynamicBatcher
    from distributed_tensorflow_tpu.serving.engine import InferenceEngine
    from distributed_tensorflow_tpu.serving.server import (
        InferenceServer,
        InProcessClient,
        make_predict_runner,
    )
    from distributed_tensorflow_tpu.utils.metrics import StreamingHistogram

    params = {"w": np.eye(SEQ, dtype=np.float32)}
    save_checkpoint(str(tmp_path), {"params": params}, 10)
    eng = InferenceEngine(_HostModel(), str(tmp_path), jit=False,
                          params_template=params, max_batch=4)
    pb = DynamicBatcher(make_predict_runner(eng), max_batch=4,
                        max_delay_ms=1, queue_depth=8,
                        latency=StreamingHistogram())
    srv = InferenceServer(eng, InProcessClient(pb), port=0)
    srv.start_background()
    return srv, pb


def test_healthz_and_metrics_routes(tmp_path):
    srv, pb = _serving_stack(tmp_path)
    try:
        pb.submit(np.ones(SEQ, np.float32)).result(10)  # one served req

        health = json.loads(urllib.request.urlopen(
            srv.address + "/healthz", timeout=10).read())
        assert health["ok"] is True
        assert health["step"] == 10 and health["params_step"] == 10
        assert health["closed_batchers"] == []
        assert health["queue_depth"] == 0
        assert health["uptime_s"] >= 0

        m = json.loads(urllib.request.urlopen(
            srv.address + "/metrics", timeout=10).read())
        assert m["params_step"] == 10
        assert m["reloads"] == 0 and m["reload_failures"] == 0
        p = m["predict"]
        assert p["completed"] >= 1 and p["batches"] >= 1
        assert p["latency_ms"]["p99"] >= p["latency_ms"]["p50"] >= 0
        assert p["latency_ms"]["count"] >= 1.0
        bp = p["backpressure"]
        assert bp["queue_limit"] == 8 and bp["queue_depth"] == 0
        assert bp["saturated"] is False and bp["closed"] is False
    finally:
        srv.close()
        pb.close(drain=False)


def test_metrics_goodput_uptime_and_health_block(tmp_path):
    """r12: /metrics carries the per-replica fields the router will
    consume — goodput_uptime_pct plus a per-batcher health block (p99
    trend between polls, saturation streak)."""
    srv, pb = _serving_stack(tmp_path)
    try:
        pb.submit(np.ones(SEQ, np.float32)).result(10)
        m1 = json.loads(urllib.request.urlopen(
            srv.address + "/metrics", timeout=10).read())
        assert m1["goodput_uptime_pct"] == pytest.approx(100.0)
        h1 = m1["predict"]["health"]
        assert h1["p99_ms"] >= 0
        assert h1["p99_trend"] == "flat"  # no previous poll to compare
        assert h1["saturation_streak"] == 0 and h1["closed"] is False

        m2 = json.loads(urllib.request.urlopen(
            srv.address + "/metrics", timeout=10).read())
        h2 = m2["predict"]["health"]
        assert h2["p99_prev_ms"] == pytest.approx(h1["p99_ms"])
        assert h2["p99_trend"] in ("rising", "flat", "falling")

        # close the batcher: uptime goodput starts decaying poll-over-
        # poll (the downtime integrates lazily between polls)
        pb.close(drain=False)
        json.loads(urllib.request.urlopen(
            srv.address + "/metrics", timeout=10).read())
        time.sleep(0.2)
        m3 = json.loads(urllib.request.urlopen(
            srv.address + "/metrics", timeout=10).read())
        assert m3["goodput_uptime_pct"] < 100.0
        assert m3["predict"]["health"]["closed"] is True
    finally:
        srv.close()
        pb.close(drain=False)


def test_healthz_503_when_batcher_closed(tmp_path):
    srv, pb = _serving_stack(tmp_path)
    try:
        pb.close(drain=False)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.address + "/healthz", timeout=10)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["ok"] is False
        assert body["closed_batchers"] == ["predict"]
    finally:
        srv.close()


# ------------------------------------------------ histogram + logger


def test_streaming_histogram_summary_is_consistent_snapshot():
    """summary() under concurrent record(): the count always equals a
    value the quantiles were computed against (one locked snapshot) —
    p50<=p90<=p99 and count grows monotonically between reads."""
    from distributed_tensorflow_tpu.utils.metrics import StreamingHistogram

    h = StreamingHistogram()
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            h.record((i % 100) + 1.0)
            i += 1

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        last_count = 0
        for _ in range(50):
            s = h.summary("x_")
            assert s["x_p50"] <= s["x_p90"] <= s["x_p99"]
            assert s["x_count"] >= last_count
            last_count = s["x_count"]
    finally:
        stop.set()
        for t in threads:
            t.join()
    total = h.count
    s = h.summary()
    assert s["count"] == float(total)  # quiescent: exact agreement
    assert h.quantile(0.5) == s["p50"]


def test_metrics_logger_thread_safe_scalars_and_flush(tmp_path):
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger

    logger = MetricsLogger(str(tmp_path), job_name="serve")

    def emit(tid):
        for i in range(50):
            logger.scalars(i, {f"v{tid}": float(i)})

    threads = [threading.Thread(target=emit, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    logger.flush()
    lines = open(tmp_path / "metrics.jsonl").read().splitlines()
    assert len(lines) == 300
    for l in lines:  # no interleaved/torn lines
        json.loads(l)
    logger.close()


def test_flightrec_dump_flushes_registered_logger(tmp_path):
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger

    telemetry.configure(logdir=str(tmp_path), host="worker-0")
    logger = MetricsLogger(str(tmp_path))
    logger.scalars(1, {"x": 1.0})
    path = telemetry.flight_recorder().dump("test")
    recs = [json.loads(l) for l in open(path).read().splitlines()]
    scalar_recs = [r for r in recs if r.get("kind") == "scalars"]
    assert scalar_recs and scalar_recs[-1]["values"]["x"] == 1.0
    logger.close()


# ------------------------------------------------------ flags + bench


def test_telemetry_flag_validation(fresh_flags):
    flags.FLAGS._parse(["--watchdog_s=5", "--watchdog_abort"])
    assert flags.FLAGS.watchdog_s == 5.0
    flags.FLAGS._reset()
    with pytest.raises(ValueError, match="watchdog_s"):
        flags.FLAGS._parse(["--watchdog_s=-1"])
    flags.FLAGS._reset()
    with pytest.raises(ValueError, match="watchdog_abort"):
        flags.FLAGS._parse(["--watchdog_abort"])
    flags.FLAGS._reset()
    with pytest.raises(ValueError, match="flightrec_events"):
        flags.FLAGS._parse(["--flightrec_events=0"])
    flags.FLAGS._reset()
    with pytest.raises(ValueError, match="telemetry"):
        # a watchdog with telemetry off would be silently inert
        flags.FLAGS._parse(["--watchdog_s=5", "--telemetry=false"])


def test_degraded_record_keeps_telemetry_facts_non_null():
    """The bench contract: host-only telemetry evidence (span overhead,
    breakdown machinery) survives a chip outage; only the chip A/B's
    overhead_pct stays null."""
    import bench

    rec = bench.degraded_record("UNAVAILABLE: no device")
    assert rec["telemetry_span_overhead_ns"] is not None
    assert rec["telemetry_step_dispatch_s"] is not None
    assert rec["telemetry_breakdown_source"] == "synthetic"
    assert rec["telemetry_overhead_pct"] is None
    # r12: the efficiency facts are host-only too — mfu/flops/goodput
    # stay non-null in the outage record, MFU a real ratio in (0, 1]
    assert rec.get("efficiency_error") is None, rec
    assert rec["flops_per_step"] is not None
    assert 0.0 < rec["mfu"] <= 1.0
    assert 0.0 < rec["goodput"] <= 1.0


def test_bench_telemetry_phase_fields():
    import bench

    out = bench.telemetry_phase()
    assert out.get("telemetry_error") is None, out
    assert out["telemetry_span_overhead_ns"] is not None
    assert out["telemetry_span_overhead_ns"] < bench.TELEMETRY_SPAN_BUDGET_NS
    for k in ("telemetry_step_host_wait_s", "telemetry_step_dispatch_s",
              "telemetry_step_device_s"):
        assert out[k] is not None and out[k] > 0
    assert out["telemetry_breakdown_source"] == "synthetic"
    assert "telemetry_overhead_pct" in out  # null here; the A/B fills it


# --------------------------------------------------------- trace_view


def test_trace_view_timeline_and_chrome_export(tmp_path, capsys):
    from tools import trace_view

    telemetry.configure(logdir=str(tmp_path), host="worker-0")
    with trace_span("viewed_span", step=12):
        pass
    telemetry.get_tracer().flush()
    spans = f"{tmp_path}/spans-worker-0.jsonl"

    assert trace_view.main([spans]) == 0
    out = capsys.readouterr().out
    assert "viewed_span" in out and "step 12" in out

    chrome = f"{tmp_path}/trace.json"
    assert trace_view.main([spans, "--chrome", chrome]) == 0
    ct = json.load(open(chrome))
    assert any(ev["name"] == "viewed_span" for ev in ct["traceEvents"])

    # flight-recorder files render through the same loader
    telemetry.flight_recorder().dump("test")
    fr = f"{tmp_path}/flightrec-worker-0.jsonl"
    recs = trace_view.load_records(fr)
    assert any(r["name"] == "viewed_span" for r in recs)
