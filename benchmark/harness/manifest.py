"""``BENCHMARK.json`` and the files it names, found by name.

The harness holds no list of its own. A cell names a configuration and a
traffic mix; the configuration's entry names its file; the mix is
``benchmark/traffic/<traffic>.json``; a per-layer metric is
``benchmark/layer_metrics/<name>.py`` with one function ``read(run)``; the
limits that decide ``correct`` in a cell are ``benchmark/limits/<cell>.json``;
the architecture a configuration runs is the ``family`` its file names,
``benchmark/reference/<family>.py``, which brings the plain reference, the
trainer's flags for the model and the operation counts (``FAMILY_NAMES``).
A later PR adds such files and one entry each, and edits nothing.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# what a family's module provides, under these names (benchmark/README.md)
FAMILY_NAMES = (
    # configuration and mix -> the sizes the reference and the counts take,
    # and the trainer's flags that are the model's own
    "sizes", "trainer_flags",
    # the plain reference
    "first_batches", "first_steps", "leaf_names",
    # the counts, each a function of the sizes alone
    "train_flops_per_token", "scope_flops_per_token", "total_params",
    "state_bytes", "adam_bytes_per_step", "allreduce_bytes_per_step")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _module_at(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def family_path(config: dict, config_file: str, root: str = ROOT) -> str:
    """The file of the architecture a configuration names. A configuration
    that names none, or one that no file provides, is an error, never a
    default."""
    name = config.get("family")
    if not name:
        raise KeyError(f"{config_file} names no \"family\": the architecture "
                       f"whose module is benchmark/reference/<family>.py")
    path = os.path.join(root, "benchmark", "reference", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{config_file} names the family {name!r}; "
                                f"there is no {path}")
    return path


def load_family(path: str):
    """The family's module, loaded once a file; one that lacks a name the
    harness calls is an error here and not in the middle of a run."""
    stem = os.path.splitext(os.path.basename(path))[0]
    module = _module_at(path, f"benchmark_family_{stem}")
    missing = [n for n in FAMILY_NAMES if not callable(getattr(module, n, None))]
    if missing:
        raise AttributeError(f"{path} does not provide {missing}")
    return module


def load_manifest(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks. A device that is not in the table is an
    error, never a default."""
    table = _load(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/harness/peaks.json")
    return table[device_kind]


def metric_cells(metric: dict, manifest: dict, moved: dict | None = None) -> list:
    """Names of the cells a metric is reported in: its ``workloads`` key,
    else (per-layer) every cell that reports the end-to-end metric it
    moves, else every cell."""
    if "workloads" in metric:
        return list(metric["workloads"])
    if moved is not None:
        return metric_cells(moved, manifest)
    return [w["name"] for w in manifest["workloads"]]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list
    root: str
    family_file: str

    def family(self):
        """The module of the configuration's architecture (imported at the
        first call: it brings JAX with it)."""
        return load_family(self.family_file)

    @property
    def sizes(self) -> dict:
        """The sizes the family's counts and reference take."""
        return self.family().sizes(self.config, self.mix)

    @property
    def tokens_per_step(self) -> int:
        return self.mix["batch_per_chip"] * self.chips * self.mix["seq_len"]

    def limits(self) -> dict:
        return _load(os.path.join(self.root, "benchmark", "limits",
                                  f"{self.name}.json"))

    def reader(self, metric_name: str):
        """The ``read(run)`` function of a per-layer metric."""
        path = os.path.join(self.root, "benchmark", "layer_metrics",
                            f"{metric_name}.py")
        return _module_at(
            path, f"benchmark_layer_metric_{metric_name.replace('.', '_')}"
        ).read


def load_cell(name: str, root: str = ROOT) -> Cell:
    manifest = load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config_file = configs[w["config"]]["file"]
    config = _load(os.path.join(root, config_file))
    family_file = family_path(config, config_file, root)
    mix = _load(os.path.join(root, "benchmark", "traffic",
                             f"{w['traffic']}.json"))
    if mix["chips"] != w["chips"]:
        raise ValueError(f"cell {name}: BENCHMARK.json says {w['chips']} "
                         f"chips, its mix {w['traffic']} says {mix['chips']}")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    end_to_end = [m for m in manifest["end_to_end"]
                  if name in metric_cells(m, manifest)]
    per_layer = [m for m in manifest["per_layer"]
                 if name in metric_cells(m, manifest, e2e[m["moves"]])]
    return Cell(name, w["chips"], w["config"], w["traffic"], config, mix,
                end_to_end, per_layer, root, family_file)


def trainer_argv(cell: Cell, seed: int, logdir: str) -> list[str]:
    """The trainer's command line for this cell: what ``mnist_dist.py``
    would be given by a user who runs this configuration under this mix."""
    c, mix = cell.config, cell.mix
    if mix["seq_len"] > c["max_position_embeddings"]:
        raise ValueError("the mix's sequences outrun the positions table")
    # the model's own flags first, as the family maps its configuration to
    # them; then what the mix and the harness set
    flags = dict(cell.family().trainer_flags(c, mix))
    flags.update({
        "seq_len": mix["seq_len"],
        "batch_size": mix["batch_per_chip"] * cell.chips,
        "mode": mix["mode"],
        "display_step": mix["display_step"],
        "device_chunk": mix["device_chunk"],
        "device_data": mix["device_data"],
        "seed": seed,
        "logdir": logdir,
        "data_dir": os.path.join(logdir, "data"),
        # beyond reach: the window, not a step count, ends the run, and no
        # cadenced save falls inside it
        "training_iter": 10 ** 9,
        "save_model_secs": 10 ** 6,
        "test_eval": False,
    })
    flags.update(c["trainer"])
    flags.update(mix.get("flags", {}))
    argv = []
    for k, v in flags.items():
        if isinstance(v, bool):
            argv.append(f"--{k}={'true' if v else 'false'}")
        else:
            argv.append(f"--{k}={v}")
    return argv
