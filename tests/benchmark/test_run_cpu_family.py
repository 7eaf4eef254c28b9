"""A second architecture through the whole of a run on the CPU: the
Switch-routed LM, whose family (reference, flags, counts) is one file that
``tiny.add_switch_family`` brings to a root with its configuration, limits
and entries, while harness, ``run.py``, tools and readers are the checkout's
own. ``correct`` comes out true for the sound program and false for each
fault planted in the timed path underneath, as for the first family in
``test_run_cpu.py``.

Each run is a process of its own (the trainer takes SIGTERM on its main
thread), one after another: ``test_run_cpu.py`` starts four at once, and the
suite's other files hold the host to timing budgets."""

import filecmp
import json
import os
import subprocess
import sys

import pytest

from tests.benchmark import tiny

REPO = tiny.REPO
FAULTS = ("none", "half_batch", "state_unchanged")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = tiny.add_switch_family(tiny.make_root(
        str(tmp_path_factory.mktemp("family") / "root"), chips=1, mode="auto"))
    out = {}
    for f in FAULTS:
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tests", "benchmark", "run_tiny.py"),
             root, "2147483659", f, tiny.SWITCH_CELL],
            capture_output=True, text=True, cwd=REPO, timeout=600,
            env=dict(os.environ,
                     XLA_FLAGS="--xla_force_host_platform_device_count=1"))
        assert p.returncode == 0, p.stderr[-3000:]
        out[f] = (json.loads(p.stdout.strip().splitlines()[-1]), p.stderr)
    return root, out


def test_the_second_family_is_correct_and_trained_its_own_model(runs):
    root, out = runs
    line, stderr = out["none"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["window"]["compiles_in_window"] == 0
    assert "--moe_experts=4" in stderr and "--moe_aux=0.01" in stderr
    # the routed leaves were compared, and all seven numbers held
    assert len(line["checks"]) == 7 and all(c["ok"] for c in line["checks"].values())
    assert "86 arrays" in line["checks"]["ckpt_mismatch"]["detail"]
    # nothing of the first family's files in the root was edited for it
    for rel in ("reference/opt_lm.py", "configs/opt-125m.json",
                "limits/opt-125m.train-s2048.json", "layer_metrics/step_mfu.py"):
        assert filecmp.cmp(os.path.join(root, "benchmark", rel),
                           os.path.join(REPO, "benchmark", rel), shallow=False)
    assert not os.path.exists(os.path.join(root, "benchmark", "harness"))


def test_the_second_family_with_half_of_the_batch_left_out_is_not_correct(runs):
    line, _ = runs[1]["half_batch"]
    assert line["correct"] is False
    assert not line["checks"]["grad_norm_gap"]["ok"]
    assert not line["checks"]["grad_difference_median"]["ok"]


def test_the_second_family_with_its_state_unchanged_is_not_correct(runs):
    line, stderr = runs[1]["state_unchanged"]
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert stderr.strip().splitlines()[-1] == "correct False"
