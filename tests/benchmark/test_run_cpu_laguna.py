"""The family ``laguna`` (``benchmark/reference/laguna.py``: window and full
attention layers of different head counts in one stack, a leading dense
layer, sigmoid-routed experts beside a shared one, one chip's share) through
the whole of a run on the CPU at a tiny size: its configuration cut to d 64
with 4 of 8 experts held, its own limits, the tiny mix. Harness, ``run.py``
and readers are the checkout's own; the root made here adds a configuration
file, a limits file and two entries. ``correct`` comes out true for the sound
program on two seeds and false for each fault planted in the timed path
underneath (``run_tiny.py``: half of the batch left out, the state left
unchanged), as ``test_run_cpu_sdar.py`` shows for the routed diffusion family.

Each run is a process of its own (the trainer takes SIGTERM on its main
thread); the four are started together."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest
from tests.benchmark import tiny

REPO = tiny.REPO
CELL = "tiny-laguna.train-tiny"
# bf16 against the f32 reference at d 64 (tests/test_layer_plan.py: the
# median leaf's gradient differs by about 1 %, a routed leaf's by more where
# rows' second and third experts swap)
LIMITS = {"loss_gap_step1": 5e-3, "loss_gap_step2": 5e-3,
          "loss_gap_step3": 5e-3, "grad_norm_gap": 0.1,
          "grad_difference_median": 0.05, "change_norm_gap": 0.1,
          "ckpt_mismatch": 0}


def tiny_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna-xs2.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, intermediate_size=256, head_dim=16,
                  num_attention_heads=6, num_key_value_heads=2,
                  num_experts=4, num_experts_per_tok=2,
                  moe_intermediate_size=32,
                  shared_expert_intermediate_size=32, vocab_size=300,
                  sliding_window=8, max_position_embeddings=64)
    config["num_attention_heads_per_layer"] = [6, 8, 8, 8] * 10
    config["experts_held"] = {"first": 2, "count": 4, "router_width": 8}
    config["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    config["trainer"].update(attn_block=16, ce_block=16, learning_rate=1e-3,
                             moe_capacity=4.0)
    return config


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.make_root(str(tmp_path_factory.mktemp("laguna") / "root"),
                          chips=1, mode="auto")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-laguna", "source": "a test",
                             "file": "benchmark/configs/tiny-laguna.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-laguna",
                               "traffic": "train-tiny", "chips": 1,
                               "why": "a test"})
    tiny._write(dest, {"benchmark/configs/tiny-laguna.json": tiny_config(),
                       f"benchmark/limits/{CELL}.json": LIMITS,
                       "BENCHMARK.json": bench})
    return dest


RUNS = [(2147483659, "none"), (3000000019, "none"),
        (2147483659, "half_batch"), (2147483659, "state_unchanged")]


@pytest.fixture(scope="module")
def runs(root):
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
    for flag in ("--layer_plan=full:6:dense,window:8:routed,window:8:routed,"
                 "window:8:routed,full:6:routed", "--attn_window=8",
                 "--window_rope_theta=10000.0", "--rope_fraction=0.5",
                 "--rope_yarn=64.0,16.0,64.0,1.0,1.4158883083359672",
                 "--attn_gate=true", "--moe_shared_dim=32",
                 "--moe_scoring=sigmoid", "--moe_scale=2.5",
                 "--moe_first_expert=2", "--moe_held_experts=4",
                 "--num_kv_heads=2", "--norm=rmsnorm", "--biases=false"):
        assert flag in stderr, flag
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


# ---- the family's counts, from the sizes alone ------------------------------

def _published():
    cell = manifest.load_cell("laguna-xs2.train-s8192")
    return cell, cell.family(), cell.sizes


def test_the_counts_by_scope_add_up_and_are_the_cuts_arithmetic():
    cell, family, sizes = _published()
    parts = family.scope_flops_per_token(sizes)
    assert sum(parts.values()) == family.train_flops_per_token(sizes)
    assert family.total_params(sizes) == 490_298_624
    assert family.state_bytes(sizes) == 5_883_583_488
    # a full layer's attention 29,462,784 parameters, a sliding one's
    # 37,884,160: q and the output projection, k and v, the gate, the gains
    assert 2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48 \
        + 2 * 2048 + 2 * 128 == 29_462_784
    assert parts["attention"] == 2 * 6 * 48 * 128 * 8192
    assert parts["mlp"] == 6 * 3 * 2048 * 8192
    assert parts["moe_shared"] == 4 * 6 * 3 * 2048 * 512
    assert parts["moe_experts"] == parts["moe_shared"] * 8 * 16 / 256
    assert parts["lm_head"] == 6 * 2048 * 12544 and parts["embed"] == 0
    assert cell.tokens_per_step == 16384
    assert round(family.train_flops_per_token(sizes) / 1e9, 3) == 2.368


@pytest.mark.parametrize("seq,window", [(64, 8), (64, 64), (96, 200),
                                        (8192, 512)])
def test_the_window_count_is_the_count_of_visible_pairs(seq, window):
    _, family, sizes = _published()
    sizes = dict(sizes, seq_len=seq, window=window)
    pairs = sum(min(i + 1, window) for i in range(seq))  # brute force
    sliding = [h for t, h in zip(sizes["layer_types"], sizes["layer_heads"])
               if t == "sliding_attention"]
    want = sum(12.0 * h * sizes["head_dim"] * pairs / seq for h in sliding)
    got = family.scope_flops_per_token(sizes)["attention_window"]
    assert got == pytest.approx(want, rel=1e-12)


def test_the_family_fails_at_once_on_a_trainer_without_its_mechanisms(
        monkeypatch):
    cell, family, _ = _published()

    class Old:
        class FLAGS:
            d_model = num_heads = num_blocks = vocab_size = 0

    monkeypatch.setitem(sys.modules, "mnist_dist", Old)
    with pytest.raises(ValueError, match="no flag for .*layer_plan"):
        family.trainer_flags(cell.config, cell.mix)
    with pytest.raises(ValueError, match="4 x d_model"):
        family.trainer_flags(dict(cell.config, intermediate_size=4096),
                             cell.mix)


def test_the_references_row_blocks_change_no_value(monkeypatch):
    """What acts on a row alone runs over blocks of ``ROW_BLOCK`` rows at
    the published size (memory); at 16 rows a block, S 64, loss and
    gradient are what the whole sequence at once gives."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _, family, _ = _published()
    cell_sizes = family.sizes(tiny_config(), {"seq_len": 64})
    sizes_t = tuple(sorted(cell_sizes.items()))
    params = family.init_params(3, cell_sizes)
    tokens = jnp.asarray(family.first_batches(3, 1, cell_sizes, 1, 1)[0][0])
    with jax.default_matmul_precision("highest"):
        whole = jax.value_and_grad(family.summed_loss)(params, tokens, sizes_t)
        monkeypatch.setattr(family, "ROW_BLOCK", 16)
        blocks = jax.value_and_grad(family.summed_loss)(params, tokens, sizes_t)
    assert float(whole[0]) == pytest.approx(float(blocks[0]), rel=1e-6)
    for a, b in zip(jax.tree.leaves(whole[1]), jax.tree.leaves(blocks[1])):
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b) + 1e-12
