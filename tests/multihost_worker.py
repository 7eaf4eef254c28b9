"""Worker subprocess for the multi-host sync-DP test (not a pytest file).

Each invocation is one "host": a process owning 4 virtual CPU devices that
joins a 2-process jax.distributed cluster over localhost, builds the global
8-device mesh, feeds its own slice of every global batch, trains 5 sync-DP
steps, and dumps its final params. The pytest side asserts params are
identical across processes and equal to a single-process 8-device run —
the determinism property the reference's async mode gives up and this
build's sync mode guarantees (SURVEY.md §2c).

Usage: python multihost_worker.py <step|train> <process_id> <num_processes> <port> <outdir>

"step"  — hand-rolled 5-step run on deterministic batches (params dumped
          for cross-process / vs-single-process comparison)
"train" — the PRODUCTION loop: training.loop.train(mode="sync") end to end
          (prefetch pipeline, supervisor, per-process dataset seeds, the
          cross-process stop-vote), asserting it completes.
"""

import os
import sys
import time

import numpy as np

GLOBAL_BATCH = 16
STEPS = 5
LR = 0.05


def make_batch(i: int, n: int):
    """Deterministic global batch i — identical on every process."""
    rng = np.random.default_rng(1000 + i)
    x = rng.random((n, 784), np.float32)
    y = np.zeros((n, 10), np.float32)
    y[np.arange(n), rng.integers(0, 10, n)] = 1.0
    return x, y


def _init_cluster(process_id: int, num_processes: int, port: str,
                  local_devices: int = 4):
    # virtual CPU platform BEFORE backend init
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={local_devices}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    # jaxlib defaults CPU collectives to "none" — every cross-host psum
    # would raise; gloo is the multi-process CPU path
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.process_count() == num_processes
    assert jax.local_device_count() == local_devices
    assert jax.device_count() == local_devices * num_processes
    return jax


def run_train_loop(process_id: int, num_processes: int, port: str, outdir: str,
                   extra_flags: tuple = (), local_devices: int = 4,
                   training_iter: int = 12) -> None:
    """Production path: flags + train(mode="sync") across 2 processes."""
    jax = _init_cluster(process_id, num_processes, port, local_devices)

    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._parse([
        f"--logdir={outdir}/logs",
        f"--data_dir={outdir}/no-data",  # forces synthetic
        f"--training_iter={training_iter}",
        "--batch_size=32",
        "--display_step=4",
        "--optimizer=adam",
        "--learning_rate=0.002",
        "--save_model_secs=100000",
        f"--task_index={process_id}",
        *extra_flags,
    ])
    res = train(flags.FLAGS, mode="sync")
    assert res.final_step == training_iter, res
    assert res.n_chips == local_devices * num_processes, res
    print(f"TRAIN_OK p{process_id} step={res.final_step}", flush=True)
    jax.distributed.shutdown()


def run_train_device(process_id: int, num_processes: int, port: str, outdir: str) -> None:
    """--device_data across processes: the split replicated onto the global
    mesh via make_array_from_process_local_data, chunked on-device steps."""
    run_train_loop(process_id, num_processes, port, outdir,
                   ("--device_data", "--device_chunk=4"))


def run_train_straggler(process_id: int, num_processes: int, port: str,
                        outdir: str) -> None:
    """Straggler chaos (r12): a --fault_spec prefetch delay armed on
    process 1 ONLY makes every one of its host batches ~40 ms late —
    the slow-host signature. The vote's work_us column must then name
    process 1 in the chief's step_skew_s/straggler_host scalars, and
    both hosts' span files (+ coord_clock markers) must let
    tools/fleet_report.py attribute the same straggler offline."""
    extra = ["--coord_steps=4", "--model=mlp", "--keep_prob=1.0"]
    if process_id == 1:
        # 150 ms per staged batch: far above an MLP step, so the
        # prefetch queue can never hide it and host_wait balloons
        extra.append(
            "--fault_spec=prefetch:mode=delay:delay=0.15:times=0")
    run_train_loop(process_id, num_processes, port, outdir,
                   tuple(extra), training_iter=24)


def run_train_tp(process_id: int, num_processes: int, port: str, outdir: str) -> None:
    """--model_axis=2 across processes: TP+DP over the global mesh, state
    placed per-host via make_array_from_callback (shard_state_tp)."""
    run_train_loop(process_id, num_processes, port, outdir,
                   ("--model_axis=2",))


def run_train_tp_span(process_id: int, num_processes: int, port: str,
                      outdir: str) -> None:
    """The round-2 latent crash shape: 2 processes x 2 devices with
    --model_axis=4, so FC shards live on devices this process cannot
    address and NO host holds full local coverage. Exercises the
    coordinated checkpoint path end to end: the cadenced vote triggers a
    mid-run collective save (save_model_secs=1 elapses during compile;
    the first --coord_steps boundary lands it), and the managed-exit
    final save gathers the spanning leaves via process_allgather."""
    run_train_loop(process_id, num_processes, port, outdir,
                   ("--model_axis=4", "--save_model_secs=1",
                    "--coord_steps=4", "--eval_step=20"),
                   local_devices=2, training_iter=40)


def run_train_kill(process_id: int, num_processes: int, port: str,
                   outdir: str) -> None:
    """SIGTERM one host mid-run: the stop must propagate through the
    cadenced vote so BOTH processes exit at the same agreed step and the
    chief's final checkpoint lands at that step (the Supervisor
    survive-and-checkpoint contract under the post-round-2 cadenced
    protocol — no per-iteration allgather to lean on anymore)."""
    import signal
    import threading

    jax = _init_cluster(process_id, num_processes, port)

    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._parse([
        f"--logdir={outdir}/logs",
        f"--data_dir={outdir}/no-data",
        "--training_iter=20000",  # safety cap; the kill ends the run
        "--batch_size=32",
        "--display_step=10000",
        "--model=mlp",  # fast CPU steps: the test targets the protocol
        "--save_model_secs=100000",  # no cadenced saves: final save only
        "--coord_steps=5",
        "--test_eval=false",
        f"--task_index={process_id}",
    ])
    if process_id == 1:
        # the NON-chief gets the signal; only the vote can tell the chief.
        # Fire only once training is observably underway (the chief's
        # metrics file appears at the step-0 display, which both processes
        # have synced past via the display eval's collective) — a fixed
        # delay races managed()'s handler install and a SIGTERM landing
        # before it hits whatever disposition the environment left.
        metrics = os.path.join(outdir, "logs", "metrics.jsonl")

        def _kill_when_training():
            while not os.path.exists(metrics):
                time.sleep(0.25)
            time.sleep(2.0)
            os.kill(os.getpid(), signal.SIGTERM)

        threading.Thread(target=_kill_when_training, daemon=True).start()
    res = train(flags.FLAGS, mode="sync")
    assert res.final_step < 20000, f"kill did not interrupt: {res}"
    print(f"KILL_OK p{process_id} step={res.final_step}", flush=True)
    jax.distributed.shutdown()


def run_train_sp(process_id: int, num_processes: int, port: str,
                 outdir: str) -> None:
    """--seq_parallel across 2 processes: batch sliced per host (data
    axis spans processes), the token axis sharded within each host's 4
    devices, ring attention over the global mesh's "model" axis, batch
    slices assembled via make_array_from_process_local_data."""
    run_train_loop(process_id, num_processes, port, outdir,
                   ("--seq_parallel", "--model=transformer",
                    "--model_axis=4"))


def run_train_sp_lm(process_id: int, num_processes: int, port: str,
                    outdir: str) -> None:
    """--seq_parallel --model lm across 2 processes: per-token targets
    sharded WITH their tokens, causal ring attention over the
    within-host token axis, the per-token uniform-pmean reduction, and
    the chief's final checkpoint (SP state replicates, so this is the
    monolithic format — the sharded format's multihost coverage lives
    in train_tp_span, whose leaves actually span hosts)."""
    run_train_loop(process_id, num_processes, port, outdir,
                   ("--seq_parallel", "--model=lm", "--dataset=lm",
                    "--model_axis=4", "--seq_len=32", "--vocab_size=16",
                    "--d_model=32", "--num_heads=2", "--num_blocks=1"))


def run_train_sp_span(process_id: int, num_processes: int, port: str,
                      outdir: str) -> None:
    """--sp_span_hosts: the token axis SPANS both processes (model_axis=8
    over 2 procs x 4 devices — ring hops cross the process boundary on
    every attention), every process draws the SAME global batch and
    uploads only its tile. The pytest side compares the final
    checkpoint against a single-process 8-device run of the identical
    config — the span must be a pure layout change."""
    run_train_loop(process_id, num_processes, port, outdir,
                   ("--seq_parallel", "--sp_span_hosts", "--model=lm",
                    "--dataset=lm", "--model_axis=8", "--seq_len=32",
                    "--vocab_size=16", "--d_model=32", "--num_heads=2",
                    "--num_blocks=1", "--keep_prob=1.0", "--seed=7"))


def run_span_mixed_exit(process_id: int, num_processes: int, port: str,
                        outdir: str) -> None:
    """The r3 ADVICE mixed-exit hole: cross-host-sharded state, process 1
    raises inside managed() while process 0 exits cleanly. Before the
    exit-agreement gate, p0 entered the final save's process_allgather
    that p1 (skipping on error) never joined — hanging p0 forever. Now
    BOTH processes join one bounded agreement allgather of clean flags,
    see the mixed verdict, and skip the save symmetrically: p0 exits 0
    with the skip message, p1 exits nonzero with the original error."""
    jax = _init_cluster(process_id, num_processes, port, local_devices=2)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    mesh = make_mesh(MeshSpec(data=1, model=4))
    full = np.arange(8.0, dtype=np.float32)
    w = jax.make_array_from_callback(
        (8,), NamedSharding(mesh, P("model")), lambda idx: full[idx])

    sup = Supervisor(is_chief=(process_id == 0),
                     logdir=os.path.join(outdir, "logs"),
                     save_model_secs=10**6)
    try:
        with sup.managed({"w": w, "step": np.int64(0)}) as box:
            box.update({"w": w, "step": np.int64(3)}, 3)
            if process_id == 1:
                raise RuntimeError("injected failure before clean exit")
    except RuntimeError:
        print(f"MIXED_EXIT_RAISED p{process_id}", flush=True)
        jax.distributed.shutdown()
        sys.exit(7)
    print(f"MIXED_EXIT_CLEAN p{process_id}", flush=True)
    jax.distributed.shutdown()


def run_train_crash(process_id: int, num_processes: int, port: str,
                    outdir: str) -> None:
    """The r8 crash-restart chaos worker: the PRODUCT's cluster-join path
    (cluster.maybe_initialize_distributed with bounded retry/backoff —
    not the test-harness direct jax.distributed.initialize), then the
    --device_data production loop. Faults arrive via the DTT_FAULT_SPEC
    env var (the pytest side arms ckpt_write:mode=crash on the chief for
    the crash phase, init:mode=refuse:times=1 on the relaunched worker to
    pin the retry path). --device_data makes the trajectory a pure
    function of the checkpointed state (batches sampled on device from
    state.rng), so a crashed-and-relaunched run's final params must match
    an uninterrupted run's BITWISE."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")

    from distributed_tensorflow_tpu.cluster import (
        ClusterSpec,
        maybe_initialize_distributed,
    )

    # only workers[0] (the coordinator address) and the count matter
    spec = ClusterSpec({"ps": [], "worker": [
        f"127.0.0.1:{port}"] + ["127.0.0.1:1"] * (num_processes - 1)})
    maybe_initialize_distributed(spec, process_id, init_retries=12,
                                 init_backoff_s=0.5, init_timeout_s=20)
    assert jax.process_count() == num_processes

    from distributed_tensorflow_tpu import flags
    from distributed_tensorflow_tpu.training.loop import train

    flags.define_reference_flags()
    flags.FLAGS._parse([
        f"--logdir={outdir}/logs",
        f"--data_dir={outdir}/no-data",
        "--training_iter=24",
        "--batch_size=32",
        "--display_step=4",
        "--model=mlp",
        "--device_data",
        "--device_chunk=4",
        "--optimizer=adam",
        "--learning_rate=0.002",
        "--save_model_secs=1",  # first coord boundary lands a save
        "--coord_steps=4",
        "--test_eval=false",
        f"--task_index={process_id}",
    ])
    res = train(flags.FLAGS, mode="sync")
    assert res.final_step == 24, res
    print(f"CRASH_RUN_OK p{process_id} step={res.final_step}", flush=True)
    jax.distributed.shutdown()


def run(process_id: int, num_processes: int, port: str, outdir: str) -> None:
    jax = _init_cluster(process_id, num_processes, port)

    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.parallel import (
        MeshSpec,
        make_dp_train_step,
        make_mesh,
        shard_batch,
    )
    from distributed_tensorflow_tpu.parallel.data_parallel import (
        local_batch_size,
        replicate_state,
    )
    from distributed_tensorflow_tpu.training import create_train_state, sgd

    mesh = make_mesh(MeshSpec(data=jax.device_count(), model=1))
    model = DeepCNN()
    opt = sgd(LR)
    state = replicate_state(mesh, create_train_state(model, opt, seed=0))
    step_fn = make_dp_train_step(model, opt, mesh, keep_prob=1.0, donate=False)

    local = local_batch_size(GLOBAL_BATCH)
    lo = process_id * local
    snapshots = {}
    for i in range(STEPS):
        x, y = make_batch(i, GLOBAL_BATCH)
        # this process's slice only — shard_batch assembles the global array
        batch = shard_batch(mesh, (x[lo : lo + local], y[lo : lo + local]))
        state, metrics = step_fn(state, batch)
        if i == 0:
            # step-1 snapshot: compared tightly against the single-process
            # run (before chaotic float divergence can amplify the
            # all-reduce's different reduction order)
            leaves, _ = jax.tree_util.tree_flatten(jax.device_get(state.params))
            snapshots.update(
                {f"step1_leaf_{j}": np.asarray(l) for j, l in enumerate(leaves)}
            )
    jax.block_until_ready(state.params)
    assert int(state.step) == STEPS

    leaves, _ = jax.tree_util.tree_flatten(jax.device_get(state.params))
    snapshots.update({f"leaf_{j}": np.asarray(l) for j, l in enumerate(leaves)})
    np.savez(
        os.path.join(outdir, f"params_p{process_id}.npz"),
        **snapshots,
        loss=np.float32(metrics["loss"]),
    )
    jax.distributed.shutdown()


if __name__ == "__main__":
    mode = sys.argv[1]
    fn = {"step": run, "train": run_train_loop,
          "train_straggler": run_train_straggler,
          "train_device": run_train_device, "train_tp": run_train_tp,
          "train_tp_span": run_train_tp_span,
          "train_sp": run_train_sp,
          "train_sp_lm": run_train_sp_lm,
          "train_sp_span": run_train_sp_span,
          "span_mixed_exit": run_span_mixed_exit,
          "train_kill": run_train_kill,
          "train_crash": run_train_crash}[mode]
    fn(int(sys.argv[2]), int(sys.argv[3]), sys.argv[4], sys.argv[5])
