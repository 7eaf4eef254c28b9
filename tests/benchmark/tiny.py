"""A copy of the benchmark's data files with one tiny cell added as files and
entries only: what a later PR would do, and what the CPU tests run."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "tiny.train-tiny"
LIMITS = {"loss_gap_step1": 5e-3, "loss_gap_step2": 5e-3,
          "loss_gap_step3": 5e-3, "grad_norm_gap": 0.05,
          "grad_difference_median": 0.05, "change_norm_gap": 0.1,
          "ckpt_mismatch": 0}


def make_root(dest: str, *, chips: int = 1, mode: str = "local") -> str:
    os.makedirs(os.path.join(dest, "benchmark"))
    for d in ("configs", "traffic", "limits", "layer_metrics"):
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
    for rel, obj in files.items():
        with open(os.path.join(dest, rel), "w") as f:
            json.dump(obj, f)
    return dest
