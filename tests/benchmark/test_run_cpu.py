"""The command itself on the CPU: it refuses to measure without the chip,
and, taken past that look, the whole of a run decides ``correct`` - true for
the sound program, false for each fault the cell can have, planted in the
timed path underneath.

Each run is a process of its own (the trainer takes SIGTERM on its main
thread); they all start together."""

import json
import os
import subprocess
import sys

import pytest

from tests.benchmark import tiny

REPO = tiny.REPO
# fault -> the chips of the tiny cell it is planted in
FAULTS = {"none": 1, "state_unchanged": 1, "half_batch": 1, "no_exchange": 4}


def test_without_a_tpu_the_command_fails_and_names_what_it_found():
    cell = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert p.returncode not in (0, None)
    assert "'platform': 'cpu'" in p.stderr and "TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_in_a_directory_with_the_benchmark_alone_it_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"][0]["name"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, env=env, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    base = tmp_path_factory.mktemp("bench")
    roots = {n: tiny.make_root(str(base / f"root{n}"), chips=n, mode="auto")
             for n in set(FAULTS.values())}
    procs = {f: subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests", "benchmark", "run_tiny.py"),
         roots[n], "2147483659", f],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=REPO,
        env=dict(os.environ,
                 XLA_FLAGS=f"--xla_force_host_platform_device_count={n}"))
        for f, n in FAULTS.items()}
    out = {}
    for f, p in procs.items():
        stdout, stderr = p.communicate(timeout=600)
        assert p.returncode == 0, stderr[-3000:]
        out[f] = (json.loads(stdout.strip().splitlines()[-1]), stderr)
    return out


def test_the_sound_program_is_correct_and_the_line_is_whole(runs):
    line, stderr = runs["none"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["window"]["compiles_in_window"] == 0
    # where the slowest interval lay and what the host did in it
    slow = line["window"]["slowest_interval"]
    assert slow["to_step"] - slow["from_step"] == 5
    assert sum(slow["host_s"].values()) == pytest.approx(slow["seconds"], rel=0.05)
    assert "slowest interval: steps " in stderr
    assert line["checks"]["ckpt_mismatch"]["value"] == 0
    # every number beside its limit, as the last lines of standard error
    tail = stderr.strip().splitlines()[-9:]
    assert tail[-1] == "correct True"
    assert sum(1 for t in tail if t.startswith("check ") and " limit " in t) == 7


def test_a_step_that_returns_its_state_unchanged_is_not_correct(runs):
    line, stderr = runs["state_unchanged"]
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert not line["checks"]["change_norm_gap"]["ok"]
    assert stderr.strip().splitlines()[-1] == "correct False"


def test_half_of_the_batch_left_out_is_not_correct(runs):
    line, _ = runs["half_batch"]
    assert line["correct"] is False
    assert not line["checks"]["grad_norm_gap"]["ok"]
    assert not line["checks"]["grad_difference_median"]["ok"]


def test_the_exchange_between_chips_left_out_is_not_correct(runs):
    line, _ = runs["no_exchange"]
    assert line["device"]["count"] == 4
    assert line["correct"] is False
    assert not line["checks"]["grad_norm_gap"]["ok"]
    assert not line["checks"]["grad_difference_median"]["ok"]
