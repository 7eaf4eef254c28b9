"""The family ``ouro`` (``benchmark/reference/ouro.py``: a stack of layers
run several times over shared weights, a loss after every pass weighted by a
learned exit distribution, sandwich norms) through the whole of a run on the
CPU at a tiny size: its configuration cut to d 64 and two layers, its own
limits, the tiny mix. Harness, ``run.py`` and readers are the checkout's own;
the root made here adds a configuration file, a limits file and two entries.
``correct`` comes out true for the sound program on two seeds and false for
each fault planted in the timed path underneath (``run_tiny.py``: half of the
batch left out, the state left unchanged) and for a program that runs three
passes where the reference runs four, as ``test_run_cpu_laguna.py`` shows for
the family of layers that differ.

Each run is a process of its own (the trainer takes SIGTERM on its main
thread); the five are started three and two at a time."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest
from tests.benchmark import tiny

REPO = tiny.REPO
CELL = "tiny-ouro.train-tiny"
THREE = "tiny-ouro-three.train-tiny"
# bf16 against the f32 reference at d 64 (tests/test_looped_lm.py holds the
# f32 program to 1e-4); the half batch reads 0.5 and more on the gradients
LIMITS = {"loss_gap_step1": 5e-3, "loss_gap_step2": 5e-3,
          "loss_gap_step3": 5e-3, "grad_norm_gap": 0.1,
          "grad_difference_median": 0.05, "change_norm_gap": 0.1,
          "ckpt_mismatch": 0}


def tiny_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, intermediate_size=176, head_dim=16,
                  num_attention_heads=4, num_key_value_heads=4,
                  num_hidden_layers=2, vocab_size=300,
                  max_position_embeddings=64)
    config["trainer"].update(attn_block=16, ce_block=16, learning_rate=1e-3)
    return config


# the family's module with the trainer told to run one pass fewer than the
# configuration's ``total_ut_steps``; the reference still runs all
THREE_PASSES = '''
from benchmark.reference.ouro import *  # noqa: F401,F403
from benchmark.reference import ouro as _whole


def trainer_flags(config, mix):
    flags = _whole.trainer_flags(config, mix)
    return dict(flags, loop_passes=flags["loop_passes"] - 1)
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.make_root(str(tmp_path_factory.mktemp("ouro") / "root"),
                          chips=1, mode="auto")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, family in (("tiny-ouro", "ouro"),
                         ("tiny-ouro-three", "ouro_three")):
        bench["configs"].append({"name": name, "source": "a test",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "a test"})
        bench["workloads"].append({"name": f"{name}.train-tiny",
                                   "config": name, "traffic": "train-tiny",
                                   "chips": 1, "why": "a test"})
        tiny._write(dest, {
            f"benchmark/configs/{name}.json": dict(tiny_config(),
                                                   family=family),
            f"benchmark/limits/{name}.train-tiny.json": LIMITS})
    tiny._write(dest, {"BENCHMARK.json": bench})
    with open(os.path.join(dest, "benchmark", "reference",
                           "ouro_three.py"), "w") as f:
        f.write(THREE_PASSES)
    return dest


RUNS = [(2147483659, "none", CELL), (3000000019, "none", CELL),
        (2147483659, "half_batch", CELL),
        (2147483659, "state_unchanged", CELL), (2147483659, "none", THREE)]


@pytest.fixture(scope="module")
def runs(root):
    out = {}
    for wave in (RUNS[:3], RUNS[3:]):  # three at a time: the suite's other
        procs = {                      # files time their own children
            key: subprocess.Popen(
                [sys.executable,
                 os.path.join(REPO, "tests", "benchmark", "run_tiny.py"),
                 root, str(key[0]), key[1], key[2]],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                cwd=REPO, env=dict(
                    os.environ,
                    XLA_FLAGS="--xla_force_host_platform_device_count=1"))
            for key in wave}
        for key, p in procs.items():
            stdout, stderr = p.communicate(timeout=900)
            assert p.returncode == 0, stderr[-3000:]
            out[key] = (json.loads(stdout.strip().splitlines()[-1]), stderr)
    return out


@pytest.mark.parametrize("seed", [2147483659, 3000000019])
def test_the_family_is_correct_from_flags_named_by_mechanism(root, runs, seed):
    line, stderr = runs[(seed, "none", CELL)]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["window"]["compiles_in_window"] == 0
    for flag in ("--loop_passes=4", "--loop_exit_beta=0.05",
                 "--sandwich_norm=true", "--mlp_dim=176", "--mlp_gated=true",
                 "--norm=rmsnorm", "--norm_eps=1e-06", "--rope_theta=1000000.0",
                 "--head_dim=16", "--biases=false", "--num_blocks=2"):
        assert flag in stderr, flag
    assert len(line["checks"]) == 7 and all(c["ok"] for c in line["checks"].values())
    assert not os.path.exists(os.path.join(root, "benchmark", "harness"))


def test_with_half_of_the_batch_left_out_it_is_not_correct(runs):
    line, _ = runs[(2147483659, "half_batch", CELL)]
    assert line["correct"] is False
    assert not line["checks"]["grad_difference_median"]["ok"]


def test_with_its_state_unchanged_it_is_not_correct(runs):
    line, stderr = runs[(2147483659, "state_unchanged", CELL)]
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert stderr.strip().splitlines()[-1] == "correct False"


def test_three_passes_against_the_references_four_are_not_correct(runs):
    line, stderr = runs[(2147483659, "none", THREE)]
    assert "--loop_passes=3" in stderr
    assert line["correct"] is False
    assert not line["checks"]["grad_difference_median"]["ok"]


# ---- the family's counts, from the sizes alone ------------------------------

def _published():
    cell = manifest.load_cell("ouro-2.6b.train-b2-s4096")
    return cell, cell.family(), cell.sizes


def test_the_counts_by_scope_add_up_and_carry_the_passes():
    cell, family, sizes = _published()
    parts = family.scope_flops_per_token(sizes)
    assert sum(parts.values()) == family.train_flops_per_token(sizes)
    # a layer 4 x 2,048^2 + 3 x 2,048 x 5,632 + 4 x 2,048; eight; the
    # tables; the final norm; the gate
    assert 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert family.total_params(sizes) == 8 * 51_388_416 + 201_326_592 \
        + 2048 + 2049 == 612_438_017
    assert family.state_bytes(sizes) == 12 * 612_438_017
    assert cell.config["bytes"]["parameters"] == 612_438_017
    assert parts["attention"] == 6 * 4 * 8 * 2048 * 4096
    assert parts["attn_proj"] == 6 * 4 * 8 * 4 * 2048 ** 2
    assert parts["mlp"] == 6 * 4 * 8 * 3 * 2048 * 5632
    assert parts["lm_head"] == 6 * 4 * 2048 * 49152
    assert parts["loop_exit"] == 6 * 4 * 2048 and parts["embed"] == 0
    assert cell.tokens_per_step == 8192
    assert round(family.train_flops_per_token(sizes) / 1e9, 2) == 13.89
    # one pass costs a quarter of all but the optimizer's share
    once = family.scope_flops_per_token(dict(sizes, passes=1))
    assert all(parts[k] == 4 * once[k] for k in parts)
    assert family.total_params(dict(sizes, passes=1)) == 612_438_017


def test_the_cell_resolves_to_its_files_as_every_cell_does():
    """What ``test_manifest.py`` holds every cell to, but for its 16,384
    tokens a step: ISSUE 36 fixes this mix at 2 x 4,096."""
    cell, family, sizes = _published()
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "tokens_per_s_per_chip"}
    names = {m["name"] for m in cell.per_layer}
    assert {"loop_exit_device_pct", "attention_roofline", "step_mfu",
            "head_device_pct", "unscoped_device_pct"} <= names
    assert not names & {"moe_experts_device_pct", "attention_window_roofline"}
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
    assert cell.reader("loop_exit_device_pct")({"trace": None}) is None
    assert set(cell.limits()) >= {"loss_gap_step1", "grad_norm_gap",
                                  "change_norm_gap", "ckpt_mismatch"}
    argv = manifest.trainer_argv(cell, 7, "/tmp/x")
    assert argv[:13] == [
        "--d_model=2048", "--num_heads=16", "--num_blocks=8",
        "--vocab_size=49152", "--norm=rmsnorm", "--norm_eps=1e-06",
        "--head_dim=128", "--rope_theta=1000000.0", "--mlp_gated=true",
        "--mlp_dim=5632", "--biases=false", "--sandwich_norm=true",
        "--loop_passes=4"]
    assert "--seq_len=4096" in argv and "--batch_size=2" in argv
    assert "--loop_exit_beta=0.05" in argv and "--remat=true" in argv
    entry = {c["name"]: c for c in manifest.load_manifest()["configs"]}[
        cell.config_name]
    assert entry["reduced"] == cell.config["reduced"] == ["num_hidden_layers"]
    assert cell.config["num_hidden_layers"] == 8
    assert cell.config["published"]["num_hidden_layers"] == 48
    assert len(cell.config["layer_types"]) == 48
    assert cell.config["total_ut_steps"] == 4
    for key in ("assumed", "bytes", "trainer", "deployment"):
        assert key in cell.config


def test_the_configuration_holds_the_catalogs_numbers_but_the_depth():
    """Every number of the published ``config.json`` as the catalog beside
    the ``model-configs`` guide has it, under the same key."""
    cell, _, _ = _published()
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000, "total_ut_steps": 4,
        "early_exit_threshold": 1, "vocab_size": 49152}
    for key, value in published.items():
        assert cell.config[key] == value, key
    assert cell.config["model_type"] == "ouro"
    assert cell.config["tie_word_embeddings"] is False
    assert cell.config["sliding_window"] is None


def test_the_family_fails_at_once_on_a_trainer_without_its_mechanisms(
        monkeypatch):
    cell, family, _ = _published()

    class Old:
        class FLAGS:
            d_model = num_heads = num_blocks = vocab_size = norm = 0
            norm_eps = head_dim = rope_theta = mlp_gated = biases = 0

    monkeypatch.setitem(sys.modules, "mnist_dist", Old)
    with pytest.raises(ValueError, match="no flag for .*loop_passes"):
        family.trainer_flags(cell.config, cell.mix)
    with pytest.raises(ValueError, match="key/value heads"):
        family.sizes(dict(cell.config, num_key_value_heads=4), cell.mix)
    with pytest.raises(ValueError, match="every pass"):
        family.trainer_flags(dict(cell.config, early_exit_threshold=0.5),
                             cell.mix)


def test_the_references_row_blocks_change_no_value(monkeypatch):
    """What acts on a row alone runs over blocks of ``ROW_BLOCK`` rows at
    the published size (memory); at 16 rows a block, S 64, loss and
    gradient are what whole sequences at once give."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _, family, _ = _published()
    cell_sizes = family.sizes(tiny_config(), {"seq_len": 64})
    sizes_t = tuple(sorted(cell_sizes.items()))
    params = family.init_params(3, cell_sizes)
    tokens = jnp.asarray(family.first_batches(3, 1, cell_sizes, 2, 1)[0])
    with jax.default_matmul_precision("highest"):
        whole = jax.value_and_grad(family.summed_loss)(params, tokens, sizes_t)
        monkeypatch.setattr(family, "ROW_BLOCK", 16)
        blocks = jax.value_and_grad(family.summed_loss)(params, tokens, sizes_t)
    assert float(whole[0]) == pytest.approx(float(blocks[0]), rel=1e-6)
    for a, b in zip(jax.tree.leaves(whole[1]), jax.tree.leaves(blocks[1])):
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b) + 1e-12
