"""A copy of the benchmark's data files with one tiny cell added as files and
entries only: what a later PR would do, and what the CPU tests run; and a
second cell of another architecture, its family brought as one more file."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "tiny.train-tiny"
SWITCH_CELL = "tiny-switch.train-tiny"
LIMITS = {"loss_gap_step1": 5e-3, "loss_gap_step2": 5e-3,
          "loss_gap_step3": 5e-3, "grad_norm_gap": 0.05,
          "grad_difference_median": 0.05, "change_norm_gap": 0.1,
          "ckpt_mismatch": 0}


def make_root(dest: str, *, chips: int = 1, mode: str = "local") -> str:
    os.makedirs(os.path.join(dest, "benchmark"))
    for d in ("configs", "traffic", "limits", "layer_metrics", "reference"):
        shutil.copytree(os.path.join(REPO, "benchmark", d),
                        os.path.join(dest, "benchmark", d))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs", "opt-125m.json")) as f:
        config = json.load(f)
    config.update(hidden_size=32, ffn_dim=128, num_attention_heads=2,
                  num_hidden_layers=2, vocab_size=300,
                  max_position_embeddings=64, word_embed_proj_dim=32)
    config["trainer"].update(attn_block=16, ce_block=16, learning_rate=1e-3)
    mix = {"kind": "train", "chips": chips, "mode": mode, "batch_per_chip": 4,
           "seq_len": 64, "display_step": 5, "device_chunk": 1,
           "device_data": True, "trace_rows": 2}
    files = {"benchmark/configs/tiny.json": config,
             "benchmark/traffic/train-tiny.json": mix,
             f"benchmark/limits/{CELL}.json": LIMITS}
    manifest["configs"].append({"name": "tiny", "source": "a test",
                                "file": "benchmark/configs/tiny.json",
                                "reduced": [], "why": "a test"})
    manifest["workloads"].append({"name": CELL, "config": "tiny",
                                  "traffic": "train-tiny", "chips": chips,
                                  "why": "a test"})
    files["BENCHMARK.json"] = manifest
    _write(dest, files)
    return dest


def _write(root: str, files: dict) -> None:
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)


def add_switch_family(root: str) -> str:
    """To a root of ``make_root``: the family ``switch_lm`` (its module,
    ``tests/benchmark/switch_lm.py``, copied to where a family is looked
    for), a d 32 configuration of four experts that names it, limits for
    its cell under the tiny mix, and the two entries. Nothing that is there
    is edited but ``BENCHMARK.json``, which gains the entries."""
    shutil.copy(os.path.join(REPO, "tests", "benchmark", "switch_lm.py"),
                os.path.join(root, "benchmark", "reference", "switch_lm.py"))
    with open(os.path.join(root, "benchmark", "configs", "tiny.json")) as f:
        config = json.load(f)
    config.update(family="switch_lm", num_experts=4, capacity_factor=1.25,
                  router_aux_loss_coef=0.01)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    chips = next(w["chips"] for w in manifest["workloads"] if w["name"] == CELL)
    manifest["configs"].append({"name": "tiny-switch", "source": "a test",
                                "file": "benchmark/configs/tiny-switch.json",
                                "reduced": [], "why": "a test"})
    manifest["workloads"].append({"name": SWITCH_CELL, "config": "tiny-switch",
                                  "traffic": "train-tiny", "chips": chips,
                                  "why": "a test"})
    _write(root, {"benchmark/configs/tiny-switch.json": config,
                  f"benchmark/limits/{SWITCH_CELL}.json": LIMITS,
                  "BENCHMARK.json": manifest})
    return root
