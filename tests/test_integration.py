"""End-to-end integration: train() in both modes, resume, CLI, PS cluster."""

import glob
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

import pytest

from distributed_tensorflow_tpu import flags
from distributed_tensorflow_tpu.training.loop import train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


@pytest.fixture(autouse=True)
def fresh_flags():
    flags.define_reference_flags()
    flags.FLAGS._reset()
    yield
    flags.FLAGS._reset()


def _parse(tmp_path, *extra):
    flags.FLAGS._parse([
        f"--logdir={tmp_path}/logs",
        f"--data_dir={tmp_path}/no-data",  # forces synthetic
        "--training_iter=30",
        "--batch_size=32",
        "--display_step=10",
        "--optimizer=adam",
        "--learning_rate=0.002",
        "--save_model_secs=100000",
        *extra,
    ])
    return flags.FLAGS


def test_train_local_end_to_end(tmp_path, capsys):
    F = _parse(tmp_path)
    res = train(F, mode="local")
    assert res.final_step == 30
    out = capsys.readouterr().out
    # reference stdout format (MNISTDist.py:183-186)
    assert re.search(r"job: worker/0 step: {2}\d+ mini_batch loss: ", out)
    assert "Optimization Finished!" in out
    assert res.test_metrics is not None
    # final checkpoint written by managed() exit
    assert os.path.exists(f"{tmp_path}/logs/checkpoint")
    # metrics jsonl written
    lines = open(f"{tmp_path}/logs/metrics.jsonl").read().splitlines()
    assert any("test_accuracy" in l for l in lines)
    assert all(json.loads(l) for l in lines)


def test_train_sync_mode_8_devices(tmp_path):
    F = _parse(tmp_path)
    res = train(F, mode="sync")
    assert res.n_chips == 8
    assert res.final_step == 30
    assert res.train_metrics["loss"] > 0


def test_sync_mode_rejects_indivisible_batch(tmp_path):
    F = _parse(tmp_path, "--batch_size=30")
    with pytest.raises(ValueError, match="divisible"):
        train(F, mode="sync")


def test_checkpoint_resume_continues_from_step(tmp_path):
    F = _parse(tmp_path, "--training_iter=10", "--save_model_secs=0")
    res1 = train(F, mode="local")
    assert res1.final_step == 10
    # managed() exit wrote ckpt-10; a second run to 20 resumes from 10
    flags.FLAGS._reset()
    F = _parse(tmp_path, "--training_iter=20", "--save_model_secs=0")
    res2 = train(F, mode="local")
    assert res2.final_step == 20


def test_training_iter_already_reached_noop(tmp_path):
    F = _parse(tmp_path, "--training_iter=10")
    train(F, mode="local")
    flags.FLAGS._reset()
    F = _parse(tmp_path, "--training_iter=5")
    res = train(F, mode="local")
    assert res.final_step == 10  # restored past target: loop body never runs


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_cli_local(tmp_path):
    out = subprocess.run(
        [sys.executable, "mnist_dist.py", "--training_iter=3",
         "--batch_size=16", "--display_step=1",
         f"--logdir={tmp_path}/logs", f"--data_dir={tmp_path}/none"],
        cwd=REPO, env=CPU_ENV, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Optimization Finished!" in out.stdout
    assert "mini_batch loss" in out.stdout


def test_cli_bad_job_name(tmp_path):
    out = subprocess.run(
        [sys.executable, "mnist_dist.py", "--job_name=chief",
         "--ps_hosts=localhost:1", "--worker_hosts=localhost:2"],
        cwd=REPO, env=CPU_ENV, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 2
    assert "job_name" in out.stderr


def test_sigterm_graceful_stop_then_resume(tmp_path):
    """Supervisor recovery contract (MNISTDist.py:169-191): SIGTERM mid-run
    -> request_stop -> final checkpoint; a restart resumes from that step."""
    args = [
        sys.executable, "-u", "mnist_dist.py", "--mode=local",
        "--training_iter=1000000", "--batch_size=16", "--display_step=20",
        f"--logdir={tmp_path}/logs", f"--data_dir={tmp_path}/none",
        "--save_model_secs=100000", "--test_eval=false",
    ]
    p = subprocess.Popen(args, cwd=REPO, env=CPU_ENV, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    try:
        # wait until the training loop is demonstrably past compile; read
        # stdout from a thread so a silent hang can't block readline forever
        import queue as queue_mod
        import threading

        lines: queue_mod.Queue = queue_mod.Queue()
        threading.Thread(
            target=lambda: [lines.put(l) for l in p.stdout], daemon=True
        ).start()
        deadline = time.time() + 180
        seen = []
        progressed = False
        while time.time() < deadline and not progressed:
            try:
                line = lines.get(timeout=5)
            except queue_mod.Empty:
                continue
            seen.append(line)
            progressed = "mini_batch loss" in line and "step:  0" not in line
        if not progressed:
            pytest.fail(f"no progress before SIGTERM: {''.join(seen)[-2000:]}")
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=180)
        time.sleep(0.5)  # let the reader thread drain the tail
        while not lines.empty():
            seen.append(lines.get_nowait())
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    full = "".join(seen)
    assert p.returncode == 0, full[-2000:]
    assert "stop requested" in full
    assert "Optimization Finished!" in full

    from distributed_tensorflow_tpu.checkpoint.checkpoint import latest_checkpoint

    found = latest_checkpoint(f"{tmp_path}/logs")
    assert found is not None
    _, saved_step = found
    assert saved_step > 0

    # restart for a few more steps: must resume from saved_step, not 0
    out2 = subprocess.run(
        [sys.executable, "mnist_dist.py", "--mode=local",
         f"--training_iter={saved_step + 5}", "--batch_size=16",
         "--display_step=1", f"--logdir={tmp_path}/logs",
         f"--data_dir={tmp_path}/none", "--save_model_secs=100000"],
        cwd=REPO, env=CPU_ENV, capture_output=True, text=True, timeout=300,
    )
    assert out2.returncode == 0, out2.stderr[-2000:]
    steps = [int(m) for m in re.findall(r"step: {2}(\d+)", out2.stdout)]
    assert steps and min(steps) >= saved_step
    found2 = latest_checkpoint(f"{tmp_path}/logs")
    assert found2 is not None and found2[1] == saved_step + 5


def test_profile_dir_writes_trace(tmp_path):
    """--profile_dir captures a jax.profiler trace of a post-compile step
    window (SURVEY.md §5 tracing obligation)."""
    F = _parse(tmp_path, f"--profile_dir={tmp_path}/prof",
               "--profile_steps=3", "--training_iter=8")
    train(F, mode="local")
    produced = [
        f for f in glob.glob(f"{tmp_path}/prof/**/*", recursive=True)
        if os.path.isfile(f)
    ]
    assert produced, "profiler produced no trace files"


def test_ps_cluster_multiprocess(tmp_path):
    """The reference's launch recipe: one ps + two workers, separate
    processes, shared global step terminates the job (MNISTDist.py §3.1)."""
    ps_port, = [_free_port()]
    ps_addr = f"localhost:{ps_port}"
    common = [
        f"--ps_hosts={ps_addr}", "--worker_hosts=localhost:1,localhost:2",
        "--training_iter=12", "--batch_size=16", "--display_step=4",
        f"--logdir={tmp_path}/logs", f"--data_dir={tmp_path}/none",
        "--learning_rate=0.01", "--save_model_secs=100000",
    ]
    ps = subprocess.Popen(
        [sys.executable, "mnist_dist.py", "--job_name=ps", "--task_index=0", *common],
        cwd=REPO, env=CPU_ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        workers = [
            subprocess.Popen(
                [sys.executable, "mnist_dist.py", "--job_name=worker",
                 f"--task_index={i}", *common],
                cwd=REPO, env=CPU_ENV, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
            for i in range(2)
        ]
        outs = []
        for w in workers:
            so, se = w.communicate(timeout=300)
            outs.append((w.returncode, so, se))
        for rc, so, se in outs:
            assert rc == 0, se[-2000:]
            assert "Optimization Finished!" in so
        # chief printed test accuracy
        assert any("test accuracy" in so for _, so, _ in outs)
        # ps keeps serving (server.join parity) until killed
        assert ps.poll() is None
    finally:
        ps.kill()
        ps.wait()


def test_ps_cluster_bf16_wire_serial_cycle(tmp_path):
    """The same one-ps/two-worker cluster over the bf16 wire with the
    serial (mirror-off, prefetch-off) full-pull cycle: the half-width
    transport and the reference cycle ordering both train to completion.
    --ps_mirror=false is load-bearing — without it the default sgd run
    takes the mirror branch and the serial bf16 pull path goes untested."""
    ps_addr = f"localhost:{_free_port()}"
    common = [
        f"--ps_hosts={ps_addr}", "--worker_hosts=localhost:1,localhost:2",
        "--training_iter=12", "--batch_size=16", "--display_step=4",
        f"--logdir={tmp_path}/logs", f"--data_dir={tmp_path}/none",
        "--learning_rate=0.01", "--save_model_secs=100000",
        "--ps_wire=bf16", "--ps_prefetch=false", "--ps_mirror=false",
    ]
    ps = subprocess.Popen(
        [sys.executable, "mnist_dist.py", "--job_name=ps", "--task_index=0", *common],
        cwd=REPO, env=CPU_ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        workers = [
            subprocess.Popen(
                [sys.executable, "mnist_dist.py", "--job_name=worker",
                 f"--task_index={i}", *common],
                cwd=REPO, env=CPU_ENV, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
            for i in range(2)
        ]
        for w in workers:
            so, se = w.communicate(timeout=300)
            assert w.returncode == 0, se[-2000:]
            assert "Optimization Finished!" in so
    finally:
        ps.kill()
        ps.wait()
