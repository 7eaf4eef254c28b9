#!/usr/bin/env python
"""Distributed MNIST training — CLI-compatible TPU-native rebuild.

Drop-in entry point with the reference's exact flag surface
(``/root/reference/.idea/MNISTDist.py:13-31``) and role semantics
(``:93-107``): launch once per task with ``--job_name``/``--task_index``;
``--ps_hosts``/``--worker_hosts`` describe the cluster. What runs underneath
is a TPU-native stack:

  default (no ps_hosts, single worker): synchronous training over all local
    TPU chips — params replicated in HBM, batch split over the "data" mesh
    axis, psum over ICI. One chip degrades gracefully to single-device.
  --ps_hosts set: the reference's asynchronous parameter-server topology,
    emulated with a host-side parameter service — ps tasks serve params
    (the server.join() role, MNISTDist.py:105-106), workers train against
    them with stale-gradient SGD.

Examples:
  python mnist_dist.py                          # sync over local devices
  python mnist_dist.py --training_iter 1000 --optimizer adam
  python mnist_dist.py --job_name=ps --task_index=0 \
      --ps_hosts=localhost:2222 --worker_hosts=localhost:2223,localhost:2224
  python mnist_dist.py --job_name=worker --task_index=0 \
      --ps_hosts=localhost:2222 --worker_hosts=localhost:2223,localhost:2224
"""

import sys

from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.cluster import ClusterSpec, resolve_mode

flags.define_reference_flags()
FLAGS = flags.FLAGS


def set_prng_impl():
    """--prng: must land before any PRNG key is created; affects dropout
    masks and --device_data's on-device batch sampling."""
    if FLAGS.prng != "threefry":
        import jax

        jax.config.update("jax_default_prng_impl", FLAGS.prng)


def main(_):
    from distributed_tensorflow_tpu.utils import faults

    # arm deterministic fault injection (no-op with no --fault_spec /
    # DTT_FAULT_SPEC) before any path that carries injection points runs
    faults.configure_from_flags(FLAGS)
    if FLAGS.eval_only:
        # restore-and-measure, no training, any checkpoint layout — runs
        # before role dispatch so it works regardless of cluster flags
        from distributed_tensorflow_tpu.training.loop import evaluate_only

        evaluate_only(FLAGS)
        return 0
    set_prng_impl()
    mode = resolve_mode(FLAGS)

    if mode == "ps":
        cluster = ClusterSpec.from_flags(FLAGS)
        if FLAGS.job_name not in ("ps", "worker"):
            print(f"--job_name must be 'ps' or 'worker' when --ps_hosts is "
                  f"set (got {FLAGS.job_name!r})", file=sys.stderr)
            return 2
        from distributed_tensorflow_tpu.parallel import ps_emulation

        # fail EVERY role fast at dispatch — the run_worker guard alone
        # would leave ps processes blocked in serve_forever() while the
        # workers die at startup
        err = ps_emulation.ps_unsupported_flag_error(FLAGS)
        if err is not None:
            print(err, file=sys.stderr)
            return 2

        if FLAGS.job_name == "ps":
            # reference: server.join() — serve parameters until killed
            ps_emulation.run_parameter_server(cluster, FLAGS)
            return 0
        return ps_emulation.run_worker(cluster, FLAGS)

    from distributed_tensorflow_tpu.cluster import maybe_initialize_distributed
    from distributed_tensorflow_tpu.training.loop import train

    if mode == "sync":
        # multi-host sync DP: join the coordination service BEFORE any jax
        # device use, so every host sees the global mesh. The retry knobs
        # are the crash-restart recovery path: a relaunched worker waits
        # (bounded) for the coordinator to come back instead of dying on
        # the first connection refusal.
        cluster = ClusterSpec.from_flags(FLAGS)
        maybe_initialize_distributed(
            cluster, FLAGS.task_index,
            init_retries=FLAGS.init_retries,
            init_backoff_s=FLAGS.init_backoff_s,
            init_timeout_s=FLAGS.init_timeout_s)

    import jax

    if FLAGS.mode == "auto" and mode == "local" and len(jax.devices()) > 1:
        mode = "sync"  # auto-upgrade: use every local chip
    train(FLAGS, mode=("sync" if mode == "sync" else "local"))
    return 0


if __name__ == "__main__":
    flags.run(main)
