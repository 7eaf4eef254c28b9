"""Serving entry point:

    python -m distributed_tensorflow_tpu.serving --logdir /tmp/train_logs \
        --dataset lm --model lm --seq_len 256 --vocab_size 64 \
        --serve_port 8000 [--serve_tp 2]

Builds the SAME model the training CLI's flags describe
(``training.loop.build_model_for``), restores the newest checkpoint's
params through the verified fallback ladder, and serves JSON-over-HTTP
(server.py routes) with dynamic batching, hot-reload on a checkpoint
watcher, and serving scalars in the logdir's metrics.jsonl + TB events.
"""

from __future__ import annotations

from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.flags import FLAGS, define_reference_flags


def _dataset_meta(FLAGS) -> dict:
    """The dataset facts model construction needs, WITHOUT loading any
    data (serving has no training split)."""
    if FLAGS.dataset == "lm":
        return {"kind": "lm", "seq_len": FLAGS.seq_len,
                "vocab_size": FLAGS.vocab_size}
    if FLAGS.dataset in ("mnist", "fashion_mnist"):
        return {"image_size": 28, "channels": 1, "num_classes": 10}
    if FLAGS.dataset == "cifar10":
        return {"image_size": 32, "channels": 3, "num_classes": 10}
    raise ValueError(f"unknown --dataset {FLAGS.dataset!r}")


def build_serving_stack(FLAGS):
    """(engine, client, watcher, metrics) from parsed flags — the
    testable core of main()."""
    from distributed_tensorflow_tpu.serving.batcher import DynamicBatcher
    from distributed_tensorflow_tpu.serving.engine import (
        CheckpointWatcher,
        InferenceEngine,
    )
    from distributed_tensorflow_tpu.serving.server import (
        InProcessClient,
        ServingMetrics,
        generate_group_key,
        make_generate_runner,
        make_predict_runner,
        predict_group_key,
    )
    from distributed_tensorflow_tpu.training.loop import build_model_for
    from distributed_tensorflow_tpu.utils import telemetry
    from distributed_tensorflow_tpu.utils.faults import configure_from_flags
    from distributed_tensorflow_tpu.utils.metrics import (
        MetricsLogger,
        StreamingHistogram,
    )

    configure_from_flags(FLAGS)
    # the serving engine registers with the telemetry spine too: spans
    # (serve_batch/serve_reload/ckpt_restore), the flight recorder, and
    # the optional --watchdog_s hang watchdog around batch execution.
    # job_name="serve": a replica pointed at the trainer's live logdir
    # must not collide with the trainer's spans/flightrec files
    telemetry.configure_from_flags(FLAGS, job_name="serve")
    # the request plane (r19) rides the same spine: per-request phase
    # timelines into spans-serve-N.jsonl, the audit ring behind the
    # /metrics tail block, and the --slo_* error-budget ledger
    from distributed_tensorflow_tpu.serving import reqtrace

    reqtrace.configure_from_flags(FLAGS)
    model = build_model_for(FLAGS, _dataset_meta(FLAGS))

    mesh = None
    tp = int(FLAGS.serve_tp) > 1
    import jax

    continuous = FLAGS.serve_scheduler == "continuous"
    if (tp or len(jax.devices()) > 1) and not continuous:
        from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec(data=-1, model=int(FLAGS.serve_tp)))
    # continuous mode serves one replica per device: no mesh, pools
    # live on the default device (the flag validator already rejects
    # --serve_tp > 1 with it) — said aloud, so that a host with more
    # devices is not taken to be serving on all of them
    if continuous and len(jax.devices()) > 1:
        print(f"continuous scheduler: this replica runs on "
              f"{jax.devices()[0]} alone; {len(jax.devices()) - 1} other "
              f"visible device(s) stay idle (one replica per device "
              f"behind serving/router.py uses them)")
    engine = InferenceEngine(model, FLAGS.logdir, mesh=mesh, tp=tp,
                             max_batch=FLAGS.serve_max_batch)
    # resource plane (r13): the replica's memory meter + compile sentry
    # (hbm_* scalars at the metrics cadence, the /metrics hbm block,
    # the --serve_hbm_headroom_pct health floor). Stashed on the engine
    # so the server and ServingMetrics share one monitor. No optimizer:
    # the budget prices the params the replica actually holds.
    from distributed_tensorflow_tpu.utils import resources

    engine.resources = resources.monitor_from_flags(
        FLAGS, model, None, FLAGS.serve_max_batch, len(jax.devices()),
        model_axis=int(FLAGS.serve_tp) if tp else None)
    print(f"serving step {engine.step} from {FLAGS.logdir} "
          f"(restore fallback depth "
          f"{engine.restore_report.fallback_depth})")

    profiler = None
    if FLAGS.serve_profile_batches > 0:
        import os

        from distributed_tensorflow_tpu.utils.profiling import (
            ServeTraceCapture,
        )

        profiler = ServeTraceCapture(
            FLAGS.serve_profile_dir
            or os.path.join(FLAGS.logdir, "serve_profile"),
            FLAGS.serve_profile_batches)

    logger = MetricsLogger(FLAGS.logdir, job_name="serve",
                           filename="serve_metrics.jsonl")
    # one ServingMetrics + latency histogram PER batcher: the emission
    # cadence tracks one completed-counter and the quantiles must not
    # mix routes (the profiler is shared — it locks internally)
    common = dict(max_batch=FLAGS.serve_max_batch,
                  max_delay_ms=FLAGS.serve_max_delay_ms,
                  queue_depth=FLAGS.serve_queue_depth,
                  default_timeout_ms=FLAGS.serve_timeout_ms)
    metrics = ServingMetrics(logger, engine, name="predict",
                             emit_every=FLAGS.serve_metrics_every,
                             profiler=profiler)
    predict_b = DynamicBatcher(make_predict_runner(engine),
                               group_key=predict_group_key,
                               latency=StreamingHistogram(),
                               on_batch=metrics.on_batch,
                               name="predict", **common)
    generate_b = None
    if FLAGS.model == "lm":
        gen_metrics = ServingMetrics(logger, engine, name="generate",
                                     emit_every=FLAGS.serve_metrics_every,
                                     profiler=profiler)
        if continuous:
            # r21: iteration-level slot scheduler over the paged KV
            # cache — same Future/stats/expiry surface, selected here
            # and nowhere else
            from distributed_tensorflow_tpu.serving.continuous import (
                ContinuousBatcher,
                EngineSlotBackend,
            )

            backend = EngineSlotBackend(
                engine, n_slots=FLAGS.serve_slots,
                page_size=FLAGS.serve_kv_page,
                num_pages=FLAGS.serve_kv_pages)
            generate_b = ContinuousBatcher(
                backend, queue_depth=FLAGS.serve_queue_depth,
                default_timeout_ms=FLAGS.serve_timeout_ms,
                latency=StreamingHistogram(),
                on_iteration=gen_metrics.on_batch,
                name="generate")
        else:
            generate_b = DynamicBatcher(
                make_generate_runner(engine),
                group_key=generate_group_key,
                latency=StreamingHistogram(),
                on_batch=gen_metrics.on_batch,
                name="generate", **common)
    # both batchers ride the CONSTRUCTOR: a post-construction attribute
    # write would race HTTP handler threads already reading the client
    # once the server starts (dttsan SAN002)
    client = InProcessClient(
        predict_batcher=predict_b,
        generate_batcher=generate_b,
        default_max_new_tokens=FLAGS.serve_max_new_tokens,
        max_new_tokens_cap=FLAGS.serve_max_new_tokens,
        default_temperature=FLAGS.serve_temperature)

    watcher = None
    if FLAGS.serve_reload_secs > 0:
        watcher = CheckpointWatcher(engine, FLAGS.serve_reload_secs)
    return engine, client, watcher, metrics


def main(argv):
    from distributed_tensorflow_tpu.serving.server import InferenceServer

    engine, client, watcher, _metrics = build_serving_stack(FLAGS)
    if watcher is not None:
        watcher.start()
    server = InferenceServer(
        engine, client, host=FLAGS.serve_host, port=FLAGS.serve_port,
        hbm_headroom_floor_pct=FLAGS.serve_hbm_headroom_pct)
    print(f"serving on {server.address} "
          f"(POST /v1/predict, /v1/generate; GET /healthz, /stats)")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        print("shutting down")
    finally:
        if watcher is not None:
            watcher.close()
        for b in (client.predict_batcher, client.generate_batcher):
            if b is not None:
                b.close(drain=False)
        server.close()
        # shutdown is the last guaranteed flush point: a short-lived
        # replica (fewer batches than the flush cadence) must not lose
        # its spans — the request plane's req:* records included
        from distributed_tensorflow_tpu.utils import telemetry

        telemetry.get_tracer().flush()
    return 0


if __name__ == "__main__":
    define_reference_flags()
    flags.run(main)
