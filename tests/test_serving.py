"""serving/ — checkpoint-to-traffic: batcher semantics, KV-cache decode
bitwise parity, hot-reload under traffic, corrupt-newest fallback,
DP-vs-TP engine parity, flag validation, metrics plumbing."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.checkpoint import save_checkpoint
from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.serving import (
    CheckpointWatcher,
    DynamicBatcher,
    InferenceEngine,
    InferenceServer,
    InProcessClient,
    NoCheckpointError,
    RejectedError,
    generate_group_key,
    make_generate_runner,
    make_predict_runner,
    pow2_bucket,
    predict_group_key,
)
from distributed_tensorflow_tpu.serving import decode
from distributed_tensorflow_tpu.training import create_train_state, sgd
from distributed_tensorflow_tpu.utils import faults
from distributed_tensorflow_tpu.utils.metrics import StreamingHistogram

VOCAB, SEQ, DM, HEADS, BLOCKS = 32, 96, 32, 2, 2


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.reset()
    yield
    faults.reset()


def _model(**kw):
    cfg = dict(vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
               num_heads=HEADS, num_blocks=BLOCKS)
    cfg.update(kw)
    return TransformerLM(**cfg)


@pytest.fixture(scope="module")
def lm_ckpt(tmp_path_factory):
    """(logdir, model, state) — one trained-ish LM checkpoint at step 10
    shared by the engine tests."""
    d = str(tmp_path_factory.mktemp("serve-ckpt"))
    model = _model()
    state = create_train_state(model, sgd(0.1), seed=0)
    save_checkpoint(d, state, 10)
    return d, model, state


# --------------------------------------------------------------- batcher


def _echo_runner(payloads, opts_list):
    return [np.asarray(p) * 2 for p in payloads]


def test_batcher_batches_and_completes():
    hist = StreamingHistogram()
    b = DynamicBatcher(_echo_runner, max_batch=4, max_delay_ms=5,
                       queue_depth=16, latency=hist)
    futs = [b.submit(np.full(3, i, np.float32)) for i in range(6)]
    outs = [f.result(5) for f in futs]
    for i, o in enumerate(outs):
        assert np.array_equal(o, np.full(3, 2 * i, np.float32))
    assert b.stats.completed == 6
    assert b.stats.batches >= 2  # max_batch=4 forces at least two
    assert hist.count == 6
    b.close()


def test_batcher_full_queue_rejects_immediately():
    gate = threading.Event()

    def slow(payloads, opts_list):
        gate.wait(10)
        return payloads

    b = DynamicBatcher(slow, max_batch=1, max_delay_ms=0, queue_depth=2,
                       default_timeout_ms=60_000)
    futs = [b.submit(np.zeros(1))]  # taken by the worker, blocks
    time.sleep(0.05)
    futs += [b.submit(np.zeros(1)), b.submit(np.zeros(1))]  # fills queue
    t0 = time.monotonic()
    with pytest.raises(RejectedError, match="queue full"):
        b.submit(np.zeros(1))
    assert time.monotonic() - t0 < 0.5  # immediate, not a hang
    assert b.stats.rejected_full == 1
    gate.set()
    for f in futs:
        f.result(5)
    b.close()


def test_batcher_deadline_expires_queued_request():
    gate = threading.Event()

    def slow(payloads, opts_list):
        gate.wait(10)
        return payloads

    b = DynamicBatcher(slow, max_batch=1, max_delay_ms=0, queue_depth=8)
    first = b.submit(np.zeros(1), timeout_ms=60_000)  # occupies worker
    time.sleep(0.05)
    doomed = b.submit(np.zeros(1), timeout_ms=30)
    with pytest.raises(RejectedError, match="deadline"):
        doomed.result(5)
    assert b.stats.rejected_deadline == 1
    gate.set()
    first.result(5)
    b.close()


def test_batcher_worker_death_fails_pending_no_hang():
    def deadly(payloads, opts_list):
        raise SystemExit("worker killed")

    b = DynamicBatcher(deadly, max_batch=1, max_delay_ms=0,
                       queue_depth=8)
    futs = [b.submit(np.zeros(1)) for _ in range(3)]
    for f in futs:
        with pytest.raises(BaseException):
            f.result(5)  # bounded: errors, never hangs
    time.sleep(0.05)
    with pytest.raises(RejectedError, match="closed"):
        b.submit(np.zeros(1))


def test_batcher_injected_batch_fault_rejects_then_recovers():
    faults.configure("serve_batch:mode=error:times=1")
    b = DynamicBatcher(_echo_runner, max_batch=1, max_delay_ms=0,
                       queue_depth=8)
    bad = b.submit(np.ones(2))
    with pytest.raises(faults.InjectedFault):
        bad.result(5)
    good = b.submit(np.ones(2))
    assert np.array_equal(good.result(5), 2 * np.ones(2))
    assert b.stats.failed == 1 and b.stats.completed == 1
    b.close()


def test_batcher_admit_fault_is_visible_backpressure():
    faults.configure("serve_admit:mode=error:times=1")
    b = DynamicBatcher(_echo_runner, max_batch=1, max_delay_ms=0,
                       queue_depth=8)
    with pytest.raises(RejectedError, match="admission fault"):
        b.submit(np.ones(2))
    assert np.array_equal(b.submit(np.ones(2)).result(5), 2 * np.ones(2))
    b.close()


def test_batcher_groups_do_not_mix():
    seen = []

    def runner(payloads, opts_list):
        seen.append([len(p) for p in payloads])
        return payloads

    b = DynamicBatcher(runner, max_batch=8, max_delay_ms=20,
                       queue_depth=16,
                       group_key=lambda p, o: len(p))
    futs = [b.submit(np.zeros(3)), b.submit(np.zeros(5)),
            b.submit(np.zeros(3))]
    for f in futs:
        f.result(5)
    b.close()
    assert sorted(map(sorted, seen)) == [[3, 3], [5]]


def test_predict_group_key_isolates_mixed_shapes(lm_ckpt):
    """A different-shape request batches alone — it must not np.stack
    into (and 500) a microbatch of well-formed neighbors."""
    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    b = DynamicBatcher(make_predict_runner(eng), max_batch=4,
                       max_delay_ms=5, queue_depth=16,
                       group_key=predict_group_key)
    good = [b.submit(np.zeros(SEQ, np.int32)) for _ in range(2)]
    odd = b.submit(np.zeros(SEQ // 2, np.int32))  # wrong length
    for f in good:
        assert f.result(10).shape == (SEQ, VOCAB)
    with pytest.raises(Exception):  # fails alone (model rejects S != seq_len)
        odd.result(10)
    b.close()


def test_pow2_bucket():
    assert [pow2_bucket(n, 8) for n in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 8]
    with pytest.raises(ValueError):
        pow2_bucket(0, 8)


# ------------------------------------------------------- KV-cache decode


def test_kv_decode_bitwise_equals_full_recompute(lm_ckpt):
    """>= 64 generated tokens: every step's logits bitwise-match the
    full-prefix recompute at the same position (acceptance criterion)."""
    _, model, state = lm_ckpt
    P, N = 8, 64
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, VOCAB, size=(2, P)).astype(np.int32)
    out = decode.generate(model, state.params, prompts, N)
    toks = out["tokens"]
    assert toks.shape == (2, P + N)

    padded = np.zeros((2, SEQ), np.int32)
    padded[:, :P + N] = toks
    full = np.asarray(model.apply(state.params, jnp.asarray(padded)))
    ref = full[:, P - 1:P + N - 1]  # rows that produced tokens P..P+N-1
    assert np.array_equal(ref, out["logits"])  # BITWISE
    assert np.array_equal(ref.argmax(-1), toks[:, P:])


def test_kv_decode_bitwise_batch_one(lm_ckpt):
    """The GEMV-kernel edge case: a single sequence decodes through the
    row-duplicated path and stays bitwise."""
    _, model, state = lm_ckpt
    P, N = 5, 16
    prompts = np.arange(P, dtype=np.int32)[None, :] % VOCAB
    out = decode.generate(model, state.params, prompts, N)
    padded = np.zeros((2, SEQ), np.int32)
    padded[0, :P + N] = out["tokens"][0]
    padded[1] = padded[0]
    full = np.asarray(model.apply(state.params, jnp.asarray(padded)))[:1]
    assert np.array_equal(full[:, P - 1:P + N - 1], out["logits"])


def test_decode_temperature_and_context_guards(lm_ckpt):
    _, model, state = lm_ckpt
    prompts = np.zeros((2, 4), np.int32)
    out = decode.generate(model, state.params, prompts, 3,
                          temperature=0.7, rng=jax.random.PRNGKey(1))
    assert out["tokens"].shape == (2, 7)
    assert (out["tokens"] >= 0).all() and (out["tokens"] < VOCAB).all()
    with pytest.raises(ValueError, match="context window"):
        decode.generate(model, state.params, np.zeros((1, SEQ), np.int32),
                        1)
    with pytest.raises(ValueError, match="seq_axis"):
        decode.check_decodable(_model(seq_axis="model"))
    with pytest.raises(ValueError, match="MoE"):
        decode.check_decodable(_model(moe_experts=4))


# ---------------------------------------------------------------- engine


def test_client_enforces_new_token_defaults_and_cap(lm_ckpt):
    """--serve_max_new_tokens is the omitted-field default AND the cap:
    an over-budget request is rejected loudly, not run."""
    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    gb = DynamicBatcher(make_generate_runner(eng), max_batch=4,
                        max_delay_ms=1, queue_depth=8,
                        group_key=generate_group_key)
    client = InProcessClient(generate_batcher=gb,
                             default_max_new_tokens=5,
                             max_new_tokens_cap=5)
    toks = client.generate(np.arange(4, dtype=np.int32))  # omitted -> 5
    assert len(toks) == 4 + 5
    with pytest.raises(ValueError, match="cap"):
        client.generate(np.arange(4, dtype=np.int32), max_new_tokens=64)
    gb.close()


def test_seeded_generate_reproducible_under_concurrency(lm_ckpt):
    """An explicitly-seeded request returns the same tokens whether it
    arrives alone or alongside identical concurrent requests — seeded
    requests batch alone so batch composition cannot change the draw."""
    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    gb = DynamicBatcher(make_generate_runner(eng), max_batch=4,
                        max_delay_ms=1, queue_depth=16,
                        default_timeout_ms=60_000,
                        group_key=generate_group_key)
    client = InProcessClient(generate_batcher=gb)
    prompt = np.arange(4, dtype=np.int32)
    futs = [gb.submit(prompt, max_new_tokens=6, temperature=1.0, seed=7)
            for _ in range(3)]
    outs = [np.asarray(f.result(60)) for f in futs]
    solo = np.asarray(client.generate(prompt, max_new_tokens=6,
                                      temperature=1.0, seed=7))
    for o in outs:
        assert np.array_equal(o, solo)
    gb.close()


def test_engine_temperature_draws_fresh_entropy(lm_ckpt):
    """Unseeded sampling must differ call-to-call (identical prompts
    never get identical 'random' completions); an explicit seed is
    reproducible."""
    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    prompts = np.arange(4, dtype=np.int32)[None, :] % VOCAB
    outs = [eng.generate(prompts, 12, temperature=1.0)["tokens"].tolist()
            for _ in range(3)]
    assert not (outs[0] == outs[1] == outs[2]), "unseeded sampling froze"
    s1 = eng.generate(prompts, 12, temperature=1.0, seed=7)
    s2 = eng.generate(prompts, 12, temperature=1.0, seed=7)
    assert np.array_equal(s1["tokens"], s2["tokens"])


def test_restore_params_with_fallback_bare_leaf_subtree(tmp_path):
    """The params field being a single bare array still restores through
    the subtree selection (bare-leaf templates flatten to the empty
    path key)."""
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        restore_params_with_fallback,
    )

    arr = np.arange(6, dtype=np.float32)
    save_checkpoint(str(tmp_path), {"params": arr, "step": 3}, 5)
    out = restore_params_with_fallback(str(tmp_path),
                                       np.zeros_like(arr))
    assert out is not None
    params, step, _ = out
    assert step == 5 and np.array_equal(np.asarray(params), arr)


def test_engine_requires_checkpoint(tmp_path):
    with pytest.raises(NoCheckpointError):
        InferenceEngine(_model(), str(tmp_path))


def test_engine_predict_buckets_and_pads(lm_ckpt):
    d, model, state = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=8)
    x = np.zeros((3, SEQ), np.int32)
    direct = np.asarray(model.apply(state.params, jnp.asarray(
        np.zeros((4, SEQ), np.int32))))[:3]  # what the padded bucket runs
    out = eng.predict(x)
    assert out.shape == (3, SEQ, VOCAB)
    np.testing.assert_allclose(out, direct, rtol=0, atol=0)
    # bucketing: 3 -> 4 and 5 -> 8 pad to distinct shapes, 2 reuses the
    # size-2 bucket; all slice back to the request size
    assert eng.predict(np.zeros((5, SEQ), np.int32)).shape[0] == 5
    assert eng.predict(np.zeros((2, SEQ), np.int32)).shape[0] == 2


def test_engine_generate_parity_with_library_decode(lm_ckpt):
    d, model, state = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    prompts = np.arange(6, dtype=np.int32)[None, :] % VOCAB
    lib = decode.generate(model, state.params, prompts, 8)
    served = eng.generate(prompts, 8)
    assert np.array_equal(lib["tokens"], served["tokens"])


def test_engine_dp_tp_parity_same_checkpoint(lm_ckpt):
    """Acceptance: the same checkpoint served DP-replicated and
    TP-sharded answers identically (to float tolerance — TP's psum
    reassociates the contractions)."""
    d, model, _ = lm_ckpt
    from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=-1, model=2))
    x = np.arange(4 * SEQ, dtype=np.int32).reshape(4, SEQ) % VOCAB
    eng_dp = InferenceEngine(model, d, mesh=mesh, tp=False, max_batch=4)
    eng_tp = InferenceEngine(model, d, mesh=mesh, tp=True, max_batch=4)
    out_dp = eng_dp.predict(x)
    out_tp = eng_tp.predict(x)
    np.testing.assert_allclose(out_dp, out_tp, atol=2e-5, rtol=2e-5)
    g_dp = eng_dp.generate(x[:2, :8], 6)
    g_tp = eng_tp.generate(x[:2, :8], 6)
    assert np.array_equal(g_dp["tokens"], g_tp["tokens"])


def test_hot_reload_swaps_mid_traffic_zero_drops(tmp_path):
    """A newer checkpoint hot-swaps between microbatches while requests
    are in flight: every request answers, outputs flip to the new
    params, nothing drops (acceptance criterion)."""
    d = str(tmp_path)
    model = _model()
    state = create_train_state(model, sgd(0.1), seed=0)
    save_checkpoint(d, state, 10)
    eng = InferenceEngine(model, d, max_batch=4)
    batcher = DynamicBatcher(make_predict_runner(eng), max_batch=4,
                             max_delay_ms=1, queue_depth=64,
                             default_timeout_ms=60_000)
    x = np.zeros(SEQ, np.int32)
    before = batcher.submit(x).result(10)

    stop = threading.Event()
    errors: list = []
    results: list = []

    def traffic():
        while not stop.is_set():
            try:
                results.append(batcher.submit(x).result(10))
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
    batcher.close()

    assert rep["swapped"] and rep["step"] == 20
    assert not errors, f"dropped requests during hot-reload: {errors[:3]}"
    after = eng.predict(x[None])[0]
    assert not np.array_equal(before, after)  # the swap took
    assert results, "traffic never ran"


def test_corrupt_newest_reload_rides_fallback_ladder(tmp_path):
    """--fault_spec serve_reload:mode=torn_file tears the newest set at
    reload time: the ladder quarantines it, the engine keeps serving the
    fallback step, in-flight AND subsequent requests all answer
    (acceptance criterion)."""
    d = str(tmp_path)
    model = _model()
    state = create_train_state(model, sgd(0.1), seed=0)
    save_checkpoint(d, state, 10)
    eng = InferenceEngine(model, d, max_batch=4)
    batcher = DynamicBatcher(make_predict_runner(eng), max_batch=4,
                             max_delay_ms=1, queue_depth=64,
                             default_timeout_ms=60_000)
    x = np.zeros(SEQ, np.int32)
    baseline = batcher.submit(x).result(10)

    stop = threading.Event()
    errors: list = []
    served = [0]

    def traffic():
        while not stop.is_set():
            try:
                batcher.submit(x).result(10)
                served[0] += 1
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=traffic, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    state2 = state._replace(
        params=jax.tree.map(lambda p: p * 2.0, state.params))
    save_checkpoint(d, state2, 20)
    faults.configure("serve_reload:mode=torn_file")
    rep = eng.reload_if_newer()
    stop.set()
    for t in threads:
        t.join(timeout=10)

    assert rep is not None and not rep["swapped"]
    assert rep["fallback_depth"] >= 1
    assert eng.step == 10  # still serving the verified set
    corrupt = [n for n in os.listdir(d) if ".corrupt" in n]
    assert corrupt, "torn newest set was not quarantined"
    assert not errors, f"dropped requests during corrupt reload: {errors[:3]}"
    # zero dropped: traffic served before, during, and after
    after = batcher.submit(x).result(10)
    assert np.array_equal(baseline, after)
    assert served[0] > 0
    batcher.close()


# ------------------------------------------------- server + HTTP routes


def test_http_server_routes_and_backpressure(lm_ckpt):
    d, model, _ = lm_ckpt
    eng = InferenceEngine(model, d, max_batch=4)
    hist = StreamingHistogram()
    pb = DynamicBatcher(make_predict_runner(eng), max_batch=4,
                        max_delay_ms=1, queue_depth=8, latency=hist)
    gb = DynamicBatcher(make_generate_runner(eng), max_batch=4,
                        max_delay_ms=1, queue_depth=8,
                        group_key=generate_group_key)
    client = InProcessClient(pb, gb)
    srv = InferenceServer(eng, client, port=0).start_background()
    try:
        def post(path, obj):
            req = urllib.request.Request(
                srv.address + path, data=json.dumps(obj).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        health = json.loads(urllib.request.urlopen(
            srv.address + "/healthz", timeout=10).read())
        assert health["ok"] is True and health["step"] == 10
        assert health["params_step"] == 10
        assert health["closed_batchers"] == []

        toks = post("/v1/generate",
                    {"prompt": list(range(8)), "max_new_tokens": 4})
        assert len(toks["tokens"]) == 12

        out = post("/v1/predict",
                   {"inputs": np.zeros(SEQ).tolist()})
        assert np.asarray(out["outputs"]).shape == (SEQ, VOCAB)

        stats = json.loads(urllib.request.urlopen(
            srv.address + "/stats", timeout=10).read())
        assert stats["engine"]["step"] == 10
        assert stats["predict_batcher"]["completed"] >= 1
        assert "latency_ms_p99" in stats["predict_batcher"]

        # backpressure surfaces as HTTP 429 with the reason
        gb.close(drain=False)
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/v1/generate", {"prompt": [1, 2, 3]})
        assert ei.value.code == 429
    finally:
        srv.close()
        pb.close(drain=False)


# ----------------------------------------------- flags, metrics, profile


@pytest.fixture
def fresh_flags():
    flags.define_reference_flags()
    flags.FLAGS._reset()
    yield
    flags.FLAGS._reset()


@pytest.mark.parametrize("argv,msg", [
    (["--serve_max_batch=0"], "serve_max_batch"),
    (["--serve_max_batch=6"], "power of two"),
    (["--serve_queue_depth=2", "--serve_max_batch=8"], "queue_depth"),
    (["--serve_max_delay_ms=-1"], "serve_max_delay_ms"),
    (["--serve_timeout_ms=0"], "serve_timeout_ms"),
    (["--serve_tp=3", "--num_heads=4"], "num_heads"),
    (["--serve_tp=0"], "serve_tp"),
    (["--serve_max_new_tokens=0"], "serve_max_new_tokens"),
    (["--serve_profile_batches=-1"], "serve_profile_batches"),
])
def test_serving_flag_validators_reject_at_parse(fresh_flags, argv, msg):
    with pytest.raises(ValueError, match=msg):
        flags.FLAGS._parse(argv)


def test_serving_flag_defaults_parse_clean(fresh_flags):
    flags.FLAGS._parse([])
    assert flags.FLAGS.serve_max_batch == 8
    assert flags.FLAGS.serve_port == 8000
    # TP degree dividing heads passes
    flags.FLAGS._reset()
    flags.FLAGS._parse(["--serve_tp=2", "--num_heads=4"])
    assert flags.FLAGS.serve_tp == 2


def test_streaming_histogram_quantiles():
    h = StreamingHistogram()
    for v in range(1, 1001):  # 1..1000 ms uniform
        h.record(float(v))
    assert h.count == 1000
    assert abs(h.quantile(0.5) - 500) < 50   # within bucket resolution
    assert abs(h.quantile(0.99) - 990) < 100
    assert h.quantile(0.5) <= h.quantile(0.9) <= h.quantile(0.99)
    s = h.summary("serve_latency_ms_")
    assert set(s) == {"serve_latency_ms_p50", "serve_latency_ms_p90",
                      "serve_latency_ms_p99", "serve_latency_ms_mean",
                      "serve_latency_ms_count"}
    h.reset()
    assert h.count == 0 and h.quantile(0.5) == 0.0


def test_serving_metrics_land_in_jsonl_sinks(tmp_path, lm_ckpt):
    d, model, _ = lm_ckpt
    from distributed_tensorflow_tpu.serving.server import ServingMetrics
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger

    eng = InferenceEngine(model, d, max_batch=4)
    logdir = str(tmp_path / "logs")
    logger = MetricsLogger(logdir, job_name="serve",
                           filename="serve_metrics.jsonl")
    metrics = ServingMetrics(logger, eng, emit_every=1)
    hist = StreamingHistogram()
    b = DynamicBatcher(make_predict_runner(eng), max_batch=2,
                       max_delay_ms=1, queue_depth=16, latency=hist,
                       on_batch=metrics.on_batch)
    for _ in range(3):
        b.submit(np.zeros(SEQ, np.int32)).result(10)
    b.close()
    logger.close()
    lines = [json.loads(ln) for ln in
             open(os.path.join(logdir, "serve_metrics.jsonl"))]
    assert lines, "no serving scalars emitted"
    keys = set(lines[-1])
    assert {"serve_queue_depth", "serve_throughput_rps",
            "serve_reloads"} <= keys
    assert any(k.startswith("serve_latency_ms_p99") for k in keys)
    assert any(f.startswith("events.out.tfevents")
               for f in os.listdir(logdir))


@pytest.mark.slow  # compiles a full predict bucket just to fill the
# trace window — the capture machinery itself is covered without it
def test_serve_profile_trace_capture(tmp_path, lm_ckpt):
    d, model, _ = lm_ckpt
    from distributed_tensorflow_tpu.utils.profiling import (
        ServeTraceCapture,
    )

    eng = InferenceEngine(model, d, max_batch=2)
    cap = ServeTraceCapture(str(tmp_path / "trace"), 2)
    assert cap.on_batch() is None
    eng.predict(np.zeros((1, SEQ), np.int32))  # real work in the window
    path = cap.on_batch()
    assert path == str(tmp_path / "trace")
    assert cap.on_batch() is None  # one-shot
    assert os.path.isdir(path) and os.listdir(path)


# --------------------------------------------------- bench serving drill


def test_bench_serving_phase_fields_non_null():
    import bench

    rec = bench.serving_phase()
    assert rec.get("serving_error") is None, rec
    for k in ("serving_p50_ms", "serving_p99_ms",
              "serving_throughput_rps", "serving_reload_blip_ms",
              "serving_reload_fallback_depth"):
        assert rec[k] is not None, (k, rec)
    assert rec["serving_dropped"] == 0
    assert rec["serving_p50_ms"] <= rec["serving_p99_ms"]


@pytest.mark.slow  # runs every host-only bench drill end-to-end (~35 s);
# the per-phase field contracts have their own tier-1 tests
def test_bench_degraded_record_keeps_serving_fields(monkeypatch):
    import bench

    rec = bench.degraded_record("UNAVAILABLE: forced")
    assert rec["serving_p50_ms"] is not None
    assert rec["serving_reload_blip_ms"] is not None
    assert rec["serving_throughput_rps"] is not None
