"""The family ``qwen3_next`` (``benchmark/reference/qwen3_next.py``: linear
attention layers of the gated delta rule beside gated full attention, every
layer a mixture of small experts beside a gated shared one) through the
whole of a run on the CPU at a tiny size: its configuration cut to d 32 and
one period of four layers, its own limits, the tiny mix. Harness, ``run.py``
and readers are the checkout's own; the root made here adds a configuration
file, a limits file and two entries. ``correct`` comes out true for the
sound program on two seeds and false for each fault planted in the timed
path underneath (``run_tiny.py``: half of the batch left out, the state left
unchanged), as ``test_run_cpu_ouro.py`` shows for the family of a stack run
several times.

Each run is a process of its own (the trainer takes SIGTERM on its main
thread); the four are started two at a time."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest
from tests.benchmark import tiny

REPO = tiny.REPO
CELL = "tiny-qwen3-next.train-tiny"
QWEN = "qwen3-next-80b-a3b.train-b1-s16384"
# bf16 against the f32 reference at d 32 (tests/test_linear_attention.py
# holds the f32 program to 1e-5); the half batch reads 0.5 and more on the
# gradients
LIMITS = {"loss_gap_step1": 5e-3, "loss_gap_step2": 5e-3,
          "loss_gap_step3": 5e-3, "grad_norm_gap": 0.1,
          "grad_difference_median": 0.05, "change_norm_gap": 0.1,
          "ckpt_mismatch": 0}


def tiny_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=32, head_dim=16, num_attention_heads=4,
                  num_key_value_heads=2, linear_num_key_heads=2,
                  linear_num_value_heads=4, linear_key_head_dim=8,
                  linear_value_head_dim=8, moe_intermediate_size=16,
                  shared_expert_intermediate_size=16, num_experts=4,
                  num_experts_per_tok=2, vocab_size=300,
                  max_position_embeddings=64)
    config["experts_held"] = {"first": 2, "count": 4, "router_width": 8}
    config["trainer"].update(attn_block=16, ce_block=16, learning_rate=1e-3)
    return config


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.make_root(str(tmp_path_factory.mktemp("qwen") / "root"),
                          chips=1, mode="auto")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-qwen3-next", "source": "a test",
                             "file": "benchmark/configs/tiny-qwen3-next.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-qwen3-next",
                               "traffic": "train-tiny", "chips": 1,
                               "why": "a test"})
    tiny._write(dest, {
        "benchmark/configs/tiny-qwen3-next.json": tiny_config(),
        f"benchmark/limits/{CELL}.json": LIMITS, "BENCHMARK.json": bench})
    return dest


RUNS = [(2147483659, "none"), (3000000019, "none"),
        (2147483659, "half_batch"), (2147483659, "state_unchanged")]


@pytest.fixture(scope="module")
def runs(root):
    out = {}
    for wave in (RUNS[:2], RUNS[2:]):  # two at a time: the suite's other
        procs = {                      # files time their own children
            key: subprocess.Popen(
                [sys.executable,
                 os.path.join(REPO, "tests", "benchmark", "run_tiny.py"),
                 root, str(key[0]), key[1], CELL],
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
    line, stderr = runs[(seed, "none")]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["window"]["compiles_in_window"] == 0
    for flag in ("--layer_plan=linear:4:routed,linear:4:routed,"
                 "linear:4:routed,full:4:routed",
                 "--norm=rmsnorm_zero_centred", "--attn_gate_elementwise=true",
                 "--linear_key_heads=2", "--linear_key_dim=8",
                 "--linear_value_dim=8", "--linear_conv=4",
                 "--moe_shared_gate=true", "--moe_shared_dim=16",
                 "--rope_fraction=0.25", "--rope_theta=10000000.0",
                 "--moe_experts=8", "--moe_held_experts=4",
                 "--moe_first_expert=2"):
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
    cell = manifest.load_cell(QWEN)
    return cell, cell.family(), cell.sizes


def test_the_counts_by_scope_add_up():
    cell, family, sizes = _published()
    parts = family.scope_flops_per_token(sizes)
    assert sum(parts.values()) == family.train_flops_per_token(sizes)
    # a linear layer outside its experts; the full one; a layer's mixture
    # (router, 16 experts, the shared one and its gate); the tables and the
    # final norm
    linear = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 \
        + 4096 * 2048 + 2 * 2048
    full = 2048 * 8192 + 2048 * 1024 + 4096 * 2048 + 2 * 256 + 2 * 2048
    mixture = 2048 * 512 + 16 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048
    assert (linear, full) == (33_722_560, 27_267_584)
    assert family.total_params(sizes) == 3 * linear + full + 4 * mixture \
        + 2 * 18992 * 2048 + 2048 == 424_340_544
    assert cell.config["bytes"]["parameters"] == 424_340_544
    assert family.state_bytes(sizes) == 12 * 424_340_544
    # 21 dk dv a value head, token and linear layer: forward and backward
    assert parts["linear_attention"] == 3 * 21 * 32 * 128 * 128
    assert parts["attention"] == 6 * 16 * 256 * 16384
    assert parts["lm_head"] == 6 * 2048 * 18992 and parts["embed"] == 0
    assert parts["moe_experts"] == 4 * 6 * 3 * 2048 * 512 * 10 * 16 / 512
    assert round(family.train_flops_per_token(sizes) / 1e6) == 1563
    # the core's bytes: in (8,192 + 4,096 + 64) and out (4,096), bf16
    assert family.scope_bytes_per_token(sizes) == {
        "linear_attention": 3 * 2 * (3 * 12352 + 2 * 4096)}
    assert cell.tokens_per_step == 16384


def test_the_cell_resolves_to_its_files_as_every_cell_does():
    cell, family, sizes = _published()
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "tokens_per_s_per_chip"}
    names = {m["name"] for m in cell.per_layer}
    assert {"linear_attention_device_pct", "linear_attention_roofline",
            "attention_roofline", "moe_experts_roofline",
            "moe_shared_device_pct", "step_mfu", "step_device_ms",
            "unscoped_device_pct", "xla_compile_s"} <= names
    assert not names & {"mlp_device_pct", "attention_window_roofline",
                        "loop_exit_device_pct", "collective_exposed_pct"}
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
    for name in ("linear_attention_device_pct", "linear_attention_roofline"):
        assert cell.reader(name)({"trace": None}) is None
    assert set(cell.limits()) >= {"loss_gap_step1", "grad_norm_gap",
                                  "change_norm_gap", "ckpt_mismatch"}
    argv = manifest.trainer_argv(cell, 7, "/tmp/x")
    assert "--seq_len=16384" in argv and "--batch_size=1" in argv
    assert "--remat=true" in argv and "--moe_held_experts=16" in argv
    entry = {c["name"]: c for c in manifest.load_manifest()["configs"]}[
        cell.config_name]
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cell.config["published"] | {"paper": None} == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "paper": None}
    for key in ("assumed", "bytes", "trainer", "deployment", "experts_held"):
        assert key in cell.config


def test_the_configuration_holds_the_catalogs_numbers_but_the_cut():
    """Every number of the published ``config.json`` as the catalog beside
    the ``model-configs`` guide has it, under the same key, but the three
    keys of ``reduced``."""
    cell, _, _ = _published()
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False}
    for key, value in published.items():
        assert cell.config[key] == value, key
    assert (cell.config["num_hidden_layers"], cell.config["num_experts"],
            cell.config["vocab_size"]) == (4, 16, 18992)
    assert cell.config["experts_held"] == dict(
        cell.config["experts_held"], first=0, count=16, router_width=512)


def test_the_family_fails_at_once_on_a_trainer_without_its_mechanisms(
        monkeypatch):
    cell, family, _ = _published()

    class Old:
        class FLAGS:
            d_model = num_heads = num_blocks = vocab_size = norm = 0
            norm_eps = head_dim = rope_theta = mlp_gated = biases = 0
            layer_plan = moe_experts = moe_top_k = 0

    monkeypatch.setitem(sys.modules, "mnist_dist", Old)
    with pytest.raises(ValueError, match="no flag for .*linear_key_heads"):
        family.trainer_flags(cell.config, cell.mix)
    with pytest.raises(ValueError, match="every layer"):
        family.sizes(dict(cell.config, mlp_only_layers=[0]), cell.mix)
    with pytest.raises(ValueError, match="untied"):
        family.trainer_flags(dict(cell.config, norm_topk_prob=False),
                             cell.mix)
