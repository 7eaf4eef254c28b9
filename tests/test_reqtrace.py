"""The request plane (r19, serving/reqtrace.py): request-id round-trip
over HTTP and in-process clients, waterfall completeness for every
disposition, phase sums vs wall time, tail attribution, the SLO ledger
and its /healthz burn-rate 503, the req_report CLI, loadgen columns,
and flag validation."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.checkpoint import save_checkpoint
from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.serving import (
    CheckpointWatcher,
    DynamicBatcher,
    InferenceEngine,
    InferenceServer,
    InProcessClient,
    RejectedError,
    generate_group_key,
    make_generate_runner,
    make_predict_runner,
    predict_group_key,
)
from distributed_tensorflow_tpu.serving import reqtrace
from distributed_tensorflow_tpu.training import create_train_state, sgd
from distributed_tensorflow_tpu.utils import faults, telemetry

VOCAB, SEQ, DM, HEADS, BLOCKS = 32, 96, 32, 2, 2


class _HostModel:
    """Minimal host model (no jax): logits = x @ w + b."""

    @staticmethod
    def apply(params, x):
        return np.asarray(x) @ params["w"] + params["b"]


@pytest.fixture(autouse=True)
def _clean_plane_and_faults():
    """Every test starts with no plane, no faults, a quiet tracer ring,
    and leaves none behind (the plane is process-global like the
    telemetry spine)."""
    faults.reset()
    prev = reqtrace.get_plane()
    tracer = telemetry.get_tracer()
    prev_enabled = tracer.enabled
    yield
    faults.reset()
    reqtrace._PLANE = prev
    tracer.enabled = prev_enabled
    telemetry.configure(logdir=None, enabled=prev_enabled)


@pytest.fixture
def plane():
    """An armed request plane with a generous SLO."""
    return reqtrace.configure(enabled=True, slo_p99_ms=60_000.0)


def _host_engine(tmpdir) -> tuple:
    rng = np.random.default_rng(0)
    params = {"w": rng.standard_normal((64, 16)).astype(np.float32),
              "b": np.zeros(16, np.float32)}
    save_checkpoint(str(tmpdir), {"params": params}, 10)
    eng = InferenceEngine(_HostModel(), str(tmpdir), jit=False,
                          params_template=params, max_batch=8)
    return eng, params


def _predict_batcher(eng, **kw):
    cfg = dict(max_batch=8, max_delay_ms=1.0, queue_depth=64,
               group_key=predict_group_key, name="predict")
    cfg.update(kw)
    return DynamicBatcher(make_predict_runner(eng), **cfg)


@pytest.fixture(scope="module")
def lm_ckpt(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("reqtrace-lm"))
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
                          num_heads=HEADS, num_blocks=BLOCKS)
    state = create_train_state(model, sgd(0.1), seed=0)
    save_checkpoint(d, state, 10)
    return d, model, state


# ------------------------------------------------------ id round-trip


def test_inprocess_id_minted_and_echoed(tmp_path, plane):
    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng)
    client = InProcessClient(predict_batcher=b)
    x = np.zeros(64, np.float32)
    _out, meta = client.predict_ex(x)
    assert meta["request_id"].startswith("req-")
    assert meta["disposition"] == "ok"
    # a client-supplied id round-trips verbatim
    _out, meta2 = client.predict_ex(x, request_id="req-client-0042")
    assert meta2["request_id"] == "req-client-0042"
    assert plane.audit[-1]["request_id"] == "req-client-0042"
    b.close()


def test_plain_predict_api_unchanged(tmp_path, plane):
    """The non-_ex surface keeps returning the bare result."""
    eng, params = _host_engine(tmp_path)
    b = _predict_batcher(eng)
    client = InProcessClient(predict_batcher=b)
    x = np.ones(64, np.float32)
    out = client.predict(x)
    np.testing.assert_allclose(out, x @ params["w"] + params["b"],
                               rtol=1e-6)
    b.close()


def test_http_id_echo_and_phase_block(lm_ckpt, plane):
    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    pb = _predict_batcher(eng, max_batch=4)
    gb = DynamicBatcher(make_generate_runner(eng), max_batch=4,
                        max_delay_ms=1, queue_depth=8,
                        group_key=generate_group_key, name="generate")
    client = InProcessClient(pb, gb)
    srv = InferenceServer(eng, client, port=0).start_background()
    try:
        def post(path, obj):
            req = urllib.request.Request(
                srv.address + path, data=json.dumps(obj).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        # client-supplied id echoes; server-minted id is returned
        out = post("/v1/predict", {"inputs": np.zeros(SEQ).tolist(),
                                   "request_id": "req-http-7"})
        assert out["request_id"] == "req-http-7"
        assert out["disposition"] == "ok"
        assert set(out["phases_ms"]) >= {"admit", "queue_wait",
                                         "batch_assembly", "prefill",
                                         "respond"}
        out = post("/v1/generate", {"prompt": list(range(8)),
                                    "max_new_tokens": 4})
        assert out["request_id"].startswith("req-")
        assert out["phases_ms"]["decode"] >= 0
        # backpressure carries the id too
        gb.close(drain=False)
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/v1/generate", {"prompt": [1, 2, 3],
                                  "request_id": "req-rej-1"})
        assert ei.value.code == 429
        body = json.loads(ei.value.read())
        assert body["request_id"] == "req-rej-1"
    finally:
        srv.close()
        pb.close(drain=False)


# ------------------------------------------- waterfalls + dispositions


def test_ok_waterfall_complete_and_sums_to_wall(tmp_path, plane):
    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng)
    client = InProcessClient(predict_batcher=b)
    for _ in range(8):
        client.predict_ex(np.zeros(64, np.float32))
    b.close()
    assert len(plane.audit) == 8
    for s in plane.audit:
        assert s["disposition"] == "ok"
        assert set(s["phases_ms"]) >= {"admit", "queue_wait",
                                       "batch_assembly", "prefill",
                                       "respond"}
        # exhaustive phases: the sum IS the wall time (rounding only)
        assert sum(s["phases_ms"].values()) == pytest.approx(
            s["total_ms"], abs=0.05)


def test_rejected_full_and_closed_get_dispositions(tmp_path, plane):
    gate = threading.Event()

    def slow(payloads, opts_list):
        gate.wait(10)
        return payloads

    b = DynamicBatcher(slow, max_batch=1, max_delay_ms=0, queue_depth=2,
                       default_timeout_ms=60_000, name="predict")
    futs = [b.submit(np.zeros(1))]
    time.sleep(0.05)
    futs += [b.submit(np.zeros(1)), b.submit(np.zeros(1))]
    with pytest.raises(RejectedError) as ei:
        b.submit(np.zeros(1))
    assert ei.value.request_id.startswith("req-")
    rec = plane.audit[-1]
    assert rec["disposition"] == "rejected_full"
    assert "queue full" in rec["reason"]
    assert rec["request_id"] == ei.value.request_id
    gate.set()
    for f in futs:
        f.result(5)
    b.close()
    with pytest.raises(RejectedError):
        b.submit(np.zeros(1))
    assert plane.audit[-1]["disposition"] == "rejected_closed"


def test_failed_disposition_on_batch_error(tmp_path, plane):
    faults.configure("serve_batch:mode=error:times=1")
    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng)
    bad = b.submit(np.zeros(64, np.float32))
    with pytest.raises(faults.InjectedFault):
        bad.result(5)
    assert bad.meta["disposition"] == "failed"
    assert "InjectedFault" in bad.meta["reason"]
    rec = plane.audit[-1]
    assert rec["disposition"] == "failed"
    assert rec["request_id"] == bad.request_id
    b.close()


def test_expired_reconstructable_from_span_file_alone(tmp_path, plane):
    """The bugfix acceptance: a deadline-expired request leaves enough
    in spans-*.jsonl that its story — id, disposition, reason, how long
    it queued — reconstructs WITHOUT the server process."""
    logdir = str(tmp_path / "logs")
    telemetry.configure(logdir=logdir, host="serve-0", enabled=True)
    gate = threading.Event()

    def slow(payloads, opts_list):
        gate.wait(10)
        return payloads

    b = DynamicBatcher(slow, max_batch=1, max_delay_ms=0, queue_depth=8,
                       name="predict")
    first = b.submit(np.zeros(1), timeout_ms=60_000)
    time.sleep(0.05)
    doomed = b.submit(np.zeros(1), timeout_ms=30)
    with pytest.raises(RejectedError, match="deadline"):
        doomed.result(5)
    gate.set()
    first.result(5)
    b.close()
    telemetry.get_tracer().flush()

    path = os.path.join(logdir, "spans-serve-0.jsonl")
    recs = [json.loads(ln) for ln in open(path)]
    mine = [r for r in recs
            if r.get("request_id") == doomed.request_id]
    done = [r for r in mine if r["name"] == "req:done"]
    assert done and done[0]["disposition"] == "expired"
    assert "deadline" in done[0]["reason"]
    waits = [r for r in mine if r["name"] == "req:queue_wait"]
    assert waits and waits[0]["dur_s"] * 1e3 >= 25  # queued ~30ms
    # and the offline tool agrees, from the file alone
    from tools import req_report

    reqs = req_report.collect_requests(
        req_report.load_records(path))
    rq = reqs[doomed.request_id]
    assert rq["disposition"] == "expired"
    assert not req_report.incomplete_requests(
        {doomed.request_id: rq})


def test_inflight_timeout_carries_request_id(tmp_path, plane):
    """A request that times out CLIENT-side while still running keeps
    its id on the TimeoutError — the 504 is joinable to the audit
    record the request will eventually land in."""
    gate = threading.Event()

    def slow(payloads, opts_list):
        gate.wait(10)
        return payloads

    b = DynamicBatcher(slow, max_batch=1, max_delay_ms=0, queue_depth=8,
                       default_timeout_ms=60_000, name="predict")
    client = InProcessClient(predict_batcher=b)
    with pytest.raises(TimeoutError) as ei:
        client.predict_ex(np.zeros(1), wait_s=0.05,
                          request_id="req-slow-1")
    assert ei.value.request_id == "req-slow-1"
    gate.set()
    b.close()


def test_generate_decode_phase_and_ticks(lm_ckpt, plane):
    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    gb = DynamicBatcher(make_generate_runner(eng), max_batch=4,
                        max_delay_ms=1, queue_depth=8,
                        group_key=generate_group_key, name="generate")
    client = InProcessClient(generate_batcher=gb)
    toks, meta = client.generate_ex(np.arange(8, dtype=np.int32),
                                    max_new_tokens=6)
    assert len(toks) == 8 + 6
    assert meta["phases_ms"]["prefill"] > 0
    assert "decode" in meta["phases_ms"]
    rec = plane.audit[-1]
    assert rec["decode_ticks"] == 6
    assert rec["bucket"] == 8  # prompt-length shape bucket
    gb.close()


def test_seeded_generate_keeps_coherent_timeline(lm_ckpt, plane):
    """A seeded request batches alone (unique group) — its timeline
    must still be complete and its tokens still reproducible."""
    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    gb = DynamicBatcher(make_generate_runner(eng), max_batch=4,
                        max_delay_ms=1, queue_depth=16,
                        default_timeout_ms=60_000,
                        group_key=generate_group_key, name="generate")
    client = InProcessClient(generate_batcher=gb)
    prompt = np.arange(4, dtype=np.int32)
    t1, m1 = client.generate_ex(prompt, max_new_tokens=5,
                                temperature=1.0, seed=7)
    t2, m2 = client.generate_ex(prompt, max_new_tokens=5,
                                temperature=1.0, seed=7)
    assert np.array_equal(t1, t2)
    assert m1["request_id"] != m2["request_id"]
    for m in (m1, m2):
        assert m["disposition"] == "ok"
        assert set(m["phases_ms"]) >= {"admit", "queue_wait",
                                       "batch_assembly", "prefill",
                                       "decode", "respond"}
    gb.close()


def test_hot_reload_requests_keep_coherent_timelines(tmp_path, plane):
    """Timelines stay complete across a mid-traffic hot-swap: every
    request in the audit ring is 'ok' with exhaustive phases."""
    d = str(tmp_path)
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
                          num_heads=HEADS, num_blocks=BLOCKS)
    state = create_train_state(model, sgd(0.1), seed=0)
    save_checkpoint(d, state, 10)
    eng = InferenceEngine(model, d, max_batch=4)
    b = _predict_batcher(eng, max_batch=4, default_timeout_ms=60_000)
    client = InProcessClient(predict_batcher=b)
    x = np.zeros(SEQ, np.int32)
    stop = threading.Event()
    errors: list = []

    def traffic():
        while not stop.is_set():
            try:
                client.predict_ex(x)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=traffic, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    state2 = state._replace(
        params=jax.tree.map(lambda p: p * 1.05, state.params))
    save_checkpoint(d, state2, 20)
    rep = CheckpointWatcher(eng).check_now()
    time.sleep(0.1)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    b.close()
    assert rep["swapped"]
    assert not errors
    audit = list(plane.audit)
    assert audit
    for s in audit:
        assert s["disposition"] == "ok"
        assert sum(s["phases_ms"].values()) == pytest.approx(
            s["total_ms"], abs=0.05)


# ------------------------------------------- tail + SLO + /healthz 503


def test_injected_delay_dominates_the_right_phase(tmp_path, plane):
    """The acceptance drill shape: an injected serve_batch delay (fires
    between take and execution) must surface as a batch_assembly-
    dominated tail, live AND offline."""
    logdir = str(tmp_path / "logs")
    telemetry.configure(logdir=logdir, host="serve-0", enabled=True)
    eng, _ = _host_engine(tmp_path)
    faults.configure("serve_batch:mode=delay:delay=0.05:times=100")
    b = _predict_batcher(eng, default_timeout_ms=60_000)
    client = InProcessClient(predict_batcher=b)
    for _ in range(4):
        client.predict_ex(np.zeros(64, np.float32))
    b.close()
    telemetry.get_tracer().flush()
    tail = plane.tail_report()
    entry = tail["routes"]["predict"]["64"]
    assert entry["p99_dominant_phase"] == "batch_assembly"
    assert entry["phases"]["batch_assembly"]["p50_ms"] >= 40
    for ex in tail["exemplars"]:
        assert ex["dominant_phase"] == "batch_assembly"
    # offline agreement from the span file alone
    from tools import req_report

    reqs = req_report.collect_requests(req_report.load_records(
        os.path.join(logdir, "spans-serve-0.jsonl")))
    off = req_report.tail_attribution(reqs)
    assert off["predict"]["64"]["p99_dominant_phase"] == \
        "batch_assembly"


def test_slo_ledger_trips_and_healthz_503(tmp_path):
    plane = reqtrace.configure(enabled=True, slo_p99_ms=0.0001,
                               slo_target_pct=99.0)
    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng)
    client = InProcessClient(predict_batcher=b)
    srv = InferenceServer(eng, client, port=0).start_background()
    try:
        for _ in range(12):  # >= MIN_WINDOW_COUNT, all non-compliant
            client.predict_ex(np.zeros(64, np.float32))
        rep = plane.slo_report()
        assert rep["compliant_pct"] == 0.0
        assert rep["budget_remaining_pct"] == 0.0
        assert rep["burn_rate_fast"] >= rep["fast_burn_threshold"]
        assert rep["fast_burn_breach"] is True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.address + "/healthz", timeout=10)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["ok"] is False and body["slo_fast_burn"] is True
        m = json.loads(urllib.request.urlopen(
            srv.address + "/metrics", timeout=10).read())
        assert m["slo"]["fast_burn_breach"] is True
        assert m["tail"]["exemplars"], "tail exemplars missing"
    finally:
        srv.close()
        b.close(drain=False)


def test_slo_compliant_path_stays_healthy(tmp_path):
    plane = reqtrace.configure(enabled=True, slo_p99_ms=60_000.0)
    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng)
    client = InProcessClient(predict_batcher=b)
    srv = InferenceServer(eng, client, port=0).start_background()
    try:
        for _ in range(12):
            client.predict_ex(np.zeros(64, np.float32))
        rep = plane.slo_report()
        assert rep["compliant_pct"] == 100.0
        assert rep["budget_remaining_pct"] == 100.0
        assert rep["fast_burn_breach"] is False
        h = json.loads(urllib.request.urlopen(
            srv.address + "/healthz", timeout=10).read())
        assert h["ok"] is True and h["slo_fast_burn"] is False
    finally:
        srv.close()
        b.close(drain=False)


def test_serving_metrics_cadence_emits_slo_scalars(tmp_path):
    from distributed_tensorflow_tpu.serving.server import ServingMetrics
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger

    reqtrace.configure(enabled=True, slo_p99_ms=60_000.0)
    eng, _ = _host_engine(tmp_path)
    logdir = str(tmp_path / "logs")
    logger = MetricsLogger(logdir, job_name="serve",
                           filename="serve_metrics.jsonl")
    metrics = ServingMetrics(logger, eng, emit_every=1)
    b = _predict_batcher(eng, on_batch=metrics.on_batch)
    client = InProcessClient(predict_batcher=b)
    for _ in range(3):
        client.predict_ex(np.zeros(64, np.float32))
    b.close()
    logger.close()
    lines = [json.loads(ln) for ln in
             open(os.path.join(logdir, "serve_metrics.jsonl"))]
    keys = set(lines[-1])
    assert {"serve_slo_compliant_pct", "serve_slo_budget_remaining_pct",
            "serve_slo_burn_rate_fast"} <= keys
    assert lines[-1]["serve_slo_compliant_pct"] == 100.0


def test_metrics_blocks_none_when_plane_unconfigured(tmp_path):
    reqtrace.configure(enabled=False)
    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng)
    client = InProcessClient(predict_batcher=b)
    srv = InferenceServer(eng, client, port=0).start_background()
    try:
        m = srv.metrics()
        assert m["tail"] is None and m["slo"] is None
        assert srv.healthz()["slo_fast_burn"] is False
    finally:
        srv.close()
        b.close(drain=False)


# -------------------------------------------------------- req_report CLI


def _drive_some_traffic(tmp_path, logdir, n=20):
    telemetry.configure(logdir=logdir, host="serve-0", enabled=True)
    reqtrace.configure(enabled=True, slo_p99_ms=60_000.0)
    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng, default_timeout_ms=60_000)
    client = InProcessClient(predict_batcher=b)
    for _ in range(n):
        client.predict_ex(np.zeros(64, np.float32))
    b.close()
    telemetry.get_tracer().flush()


def test_req_report_json_chrome_and_exit_codes(tmp_path, capsys):
    from tools import req_report

    logdir = str(tmp_path / "logs")
    _drive_some_traffic(tmp_path, logdir, n=20)

    # exit 0 + json report
    rc = req_report.main([logdir, "--json", "--slo_p99_ms", "60000"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["requests_total"] == 20
    assert rep["by_disposition"] == {"ok": 20}
    assert rep["complete_pct"] == 100.0
    assert rep["tail"]["predict"]["64"]["phases"]["queue_wait"]["p99_ms"] >= 0
    assert rep["slo"]["compliant_pct"] == 100.0
    assert rep["exemplars"][0]["request_id"].startswith("req-")

    # chrome export: one track (thread_name metadata event) per request
    out = str(tmp_path / "req.json")
    rc = req_report.main([logdir, "--chrome", out])
    assert rc == 0
    trace = json.load(open(out))
    names = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(names) == 20
    assert len({e["tid"] for e in names}) == 20
    capsys.readouterr()

    # human report + single-request waterfall
    rc = req_report.main([logdir])
    assert rc == 0
    text = capsys.readouterr().out
    assert "tail attribution" in text and "worst exemplars" in text
    rid = rep["exemplars"][0]["request_id"]
    rc = req_report.main([logdir, "--request", rid])
    assert rc == 0
    assert rid in capsys.readouterr().out

    # exit 2: no request records
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    with open(os.path.join(empty, "spans-serve-0.jsonl"), "w") as f:
        f.write(json.dumps({"name": "serve_batch", "ts": 1.0,
                            "dur_s": 0.1}) + "\n")
    assert req_report.main([empty]) == 2
    assert req_report.main([str(tmp_path / "nowhere")]) == 2

    # exit 1: an incomplete timeline (phase spans but no req:done)
    broken = str(tmp_path / "broken")
    os.makedirs(broken)
    with open(os.path.join(broken, "spans-serve-0.jsonl"), "w") as f:
        f.write(json.dumps({"name": "req:admit", "ts": 1.0,
                            "dur_s": 0.001,
                            "request_id": "req-x-1"}) + "\n")
    assert req_report.main([broken, "--json"]) == 1


# ----------------------------------------------------- loadgen columns


def test_loadgen_closed_loop_phase_and_slo_columns(tmp_path, plane):
    from tools.serve_loadgen import run_closed_loop

    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng, default_timeout_ms=60_000)
    client = InProcessClient(predict_batcher=b)
    x = np.zeros(64, np.float32)

    def request():
        _out, meta = client.predict_ex(x)
        return meta

    rep = run_closed_loop(request, n_requests=30, concurrency=3,
                          slo_p99_ms=60_000.0)
    b.close()
    assert rep["ok"] == 30 and rep["errors"] == 0
    assert rep["id_echo_failures"] == 0
    assert rep["slo_compliant_pct"] == 100.0
    assert set(rep["phase_ms"]) >= {"admit", "queue_wait",
                                    "batch_assembly", "prefill",
                                    "respond"}
    for cols in rep["phase_ms"].values():
        assert cols["p50"] <= cols["p99"]


def test_loadgen_http_echo_verified(lm_ckpt, plane):
    from tools.serve_loadgen import http_request_fn, run_closed_loop

    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    pb = _predict_batcher(eng, max_batch=4, default_timeout_ms=60_000)
    client = InProcessClient(predict_batcher=pb)
    srv = InferenceServer(eng, client, port=0).start_background()
    try:
        fn = http_request_fn(srv.address, "predict", input_dim=SEQ)
        rep = run_closed_loop(fn, n_requests=12, concurrency=2,
                              slo_p99_ms=60_000.0)
        assert rep["ok"] == 12 and rep["id_echo_failures"] == 0
        assert rep["phase_ms"] is not None
    finally:
        srv.close()
        pb.close(drain=False)


# ---------------------------------------------------- flags + telemetry


@pytest.fixture
def fresh_flags():
    flags.define_reference_flags()
    flags.FLAGS._reset()
    yield
    flags.FLAGS._reset()


@pytest.mark.parametrize("argv,msg", [
    (["--slo_p99_ms=-1"], "slo_p99_ms"),
    (["--slo_target_pct=40"], "slo_target_pct"),
    (["--slo_target_pct=100.5"], "slo_target_pct"),
    (["--slo_target_pct=95"], "slo_target_pct without"),
    (["--reqtrace_ring=4"], "reqtrace_ring"),
    (["--reqtrace_exemplars=0"], "reqtrace_exemplars"),
    (["--telemetry=false", "--slo_p99_ms=100"], "telemetry"),
    (["--telemetry=false", "--reqtrace_ring=1024"], "telemetry"),
    (["--telemetry=false", "--reqtrace_exemplars=9"], "telemetry"),
])
def test_reqtrace_flag_validators_reject_at_parse(fresh_flags, argv,
                                                  msg):
    with pytest.raises(ValueError, match=msg):
        flags.FLAGS._parse(argv)


def test_reqtrace_flag_defaults_and_armed_pair(fresh_flags):
    flags.FLAGS._parse([])
    assert flags.FLAGS.slo_p99_ms == 0.0
    assert flags.FLAGS.reqtrace_ring == 512
    flags.FLAGS._reset()
    flags.FLAGS._parse(["--telemetry=false"])  # defaults stay legal
    flags.FLAGS._reset()
    flags.FLAGS._parse(["--slo_p99_ms=200", "--slo_target_pct=95"])
    assert flags.FLAGS.slo_target_pct == 95.0


def test_configure_from_flags_respects_telemetry(fresh_flags):
    flags.FLAGS._parse(["--slo_p99_ms=100"])
    plane = reqtrace.configure_from_flags(flags.FLAGS)
    assert plane is not None and plane.slo is not None
    assert plane.slo.p99_ms == 100.0
    flags.FLAGS._reset()
    flags.FLAGS._parse(["--telemetry=false"])
    assert reqtrace.configure_from_flags(flags.FLAGS) is None


def test_telemetry_off_leaves_ids_but_no_records(tmp_path):
    reqtrace.configure(enabled=False)
    eng, _ = _host_engine(tmp_path)
    b = _predict_batcher(eng)
    client = InProcessClient(predict_batcher=b)
    _out, meta = client.predict_ex(np.zeros(64, np.float32))
    assert meta["request_id"].startswith("req-")  # the wire contract
    assert "phases_ms" not in meta               # no plane, no record
    b.close()


# ----------------------------------------------------------- bench drill


def test_bench_reqtrace_phase_fields_non_null():
    import bench

    rec = bench.reqtrace_phase()
    assert rec.get("reqtrace_error") is None, rec
    assert rec["reqtrace_requests_total"] == bench.REQTRACE_REQUESTS
    assert rec["reqtrace_complete_pct"] == 100.0
    assert rec["reqtrace_p99_phase"] in reqtrace.PHASES
    assert rec["reqtrace_slo_compliant_pct"] is not None
    assert rec["reqtrace_overhead_pct"] is not None
    assert rec["reqtrace_overhead_pct"] < 2.0


def test_bench_degraded_record_keeps_reqtrace_fields():
    import bench

    rec = bench.degraded_record("UNAVAILABLE: forced")
    for k in ("reqtrace_requests_total", "reqtrace_complete_pct",
              "reqtrace_p99_phase", "reqtrace_slo_compliant_pct",
              "reqtrace_overhead_pct"):
        assert rec[k] is not None, (k, rec.get("reqtrace_error"))
