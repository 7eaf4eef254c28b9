"""The family ``sdar_moe`` (``benchmark/reference/sdar_moe.py``: the
block-diffusion mixture of experts, one chip's share) through the whole of a
run on the CPU at a tiny size: its configuration cut to d 64 with 4 of 16
experts held, its own limits, the tiny mix. Harness, ``run.py`` and readers
are the checkout's own; the root made here adds a configuration file, a
limits file and two entries. ``correct`` comes out true for the sound
program on several seeds and false for each fault planted in the timed path
underneath (``run_tiny.py``: half of the batch left out, the state left
unchanged), as ``test_run_cpu_family.py`` shows for the Switch family.

Each run is a process of its own (the trainer takes SIGTERM on its main
thread); the four are started together."""

import json
import os
import subprocess
import sys

import pytest

from tests.benchmark import tiny

REPO = tiny.REPO
CELL = "tiny-sdar.train-tiny"
# bf16 against the f32 reference at d 64 (tests/test_routed_diffusion.py:
# the median leaf's gradient differs by about 1 %, a routed leaf's by 4 %
# where rows' fourth and fifth experts swap)
LIMITS = {"loss_gap_step1": 5e-3, "loss_gap_step2": 5e-3,
          "loss_gap_step3": 5e-3, "grad_norm_gap": 0.05,
          "grad_difference_median": 0.05, "change_norm_gap": 0.1,
          "ckpt_mismatch": 0}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.make_root(str(tmp_path_factory.mktemp("sdar") / "root"),
                          chips=1, mode="auto")
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                  head_dim=16, num_hidden_layers=2, num_experts=4,
                  num_experts_per_tok=4, moe_intermediate_size=32,
                  vocab_size=300, max_position_embeddings=64)
    config["experts_held"] = {"first": 4, "count": 4, "router_width": 16}
    config["trainer"].update(attn_block=16, ce_block=16, learning_rate=1e-3,
                             moe_capacity=4.0)
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "tiny-sdar", "source": "a test",
                                "file": "benchmark/configs/tiny-sdar.json",
                                "reduced": [], "why": "a test"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-sdar",
                                  "traffic": "train-tiny", "chips": 1,
                                  "why": "a test"})
    tiny._write(dest, {"benchmark/configs/tiny-sdar.json": config,
                       f"benchmark/limits/{CELL}.json": LIMITS,
                       "BENCHMARK.json": manifest})
    return dest


RUNS = [(2147483659, "none"), (3000000019, "none"),
        (2147483659, "half_batch"), (2147483659, "state_unchanged")]


@pytest.fixture(scope="module")
def runs(root):
    """The four runs, started together (as ``test_run_cpu.py`` starts its
    four): each is a process of its own."""
    procs = {
        key: subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "tests", "benchmark", "run_tiny.py"),
             root, str(key[0]), key[1], CELL],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=REPO, env=dict(
                os.environ,
                XLA_FLAGS="--xla_force_host_platform_device_count=1"))
        for key in RUNS}
    out = {}
    for key, p in procs.items():
        stdout, stderr = p.communicate(timeout=900)
        assert p.returncode == 0, stderr[-3000:]
        out[key] = (json.loads(stdout.strip().splitlines()[-1]), stderr)
    return out


@pytest.mark.parametrize("seed", [2147483659, 3000000019])
def test_the_family_is_correct_from_flags_named_by_mechanism(root, runs, seed):
    line, stderr = runs[(seed, "none")]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["window"]["compiles_in_window"] == 0
    for flag in ("--objective=masked_diffusion", "--moe_top_k=4",
                 "--moe_first_expert=4", "--moe_held_experts=4",
                 "--num_kv_heads=2", "--rope_theta=1000000.0", "--norm=rmsnorm",
                 "--qk_norm=true", "--biases=false"):
        assert flag in stderr
    assert len(line["checks"]) == 7 and all(c["ok"] for c in line["checks"].values())
    assert not os.path.exists(os.path.join(root, "benchmark", "harness"))


def test_with_half_of_the_batch_left_out_it_is_not_correct(runs):
    line, _ = runs[(2147483659, "half_batch")]
    assert line["correct"] is False
    assert not line["checks"]["grad_difference_median"]["ok"]


def test_with_its_state_unchanged_it_is_not_correct(runs):
    line, stderr = runs[(2147483659, "state_unchanged")]
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert stderr.strip().splitlines()[-1] == "correct False"
