"""``BENCHMARK.json`` keeps to the contract's shapes and names, every cell
resolves to its files by name, and a cell added as files only is found."""

import json
import os
import re

import pytest

from benchmark.harness import manifest
from tests.benchmark import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert len(bench["command"]) <= 32
    assert all(_one_line(w) for w in bench["command"])
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    # 2 + 14 runs a cell, each run_seconds + 60 s, 180 s a cell to compile,
    # 1200 s spare, for the full 24 cells
    assert 1200 + (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 <= 43200


def test_names_units_and_entries(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["source"]) and _one_line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _one_line(w["why"])
        assert w["config"] in {c["name"] for c in bench["configs"]}
        names.append(w["name"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in {"host_clock", "device_trace"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _one_line(m["layer"])
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_roofline_and_mfu_naming(bench):
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # a kernel's share of its roofline is <kernel>_roofline in %; the whole
    # step's share of the peak, with mfu as a part of its name, moves the
    # same end-to-end metric as any of them (today the trace names no
    # kernel, so there is none: PERF.md, Open questions)
    rooflines = [n for n in per_layer if n.endswith("_roofline")]
    assert all(per_layer[n]["unit"] == "%" for n in rooflines)
    mfu = [n for n in per_layer if "mfu" in re.split(r"[_.\-]", n)]
    assert mfu and all(per_layer[n]["unit"] == "%" for n in mfu)
    assert {per_layer[n]["moves"] for n in rooflines} <= {per_layer[n]["moves"] for n in mfu}


@pytest.mark.parametrize("cell_name", [w["name"] for w in manifest.load_manifest()["workloads"]])
def test_every_cell_resolves_to_its_files(cell_name):
    cell = manifest.load_cell(cell_name)
    assert cell.sizes["ffn_dim"] == 4 * cell.sizes["d_model"]
    assert cell.tokens_per_step == 8 * 2048 * cell.chips
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "tokens_per_s_per_chip"}
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
    assert set(cell.limits()) >= {"loss_gap_step1", "grad_norm_gap",
                                  "change_norm_gap", "ckpt_mismatch"}
    argv = manifest.trainer_argv(cell, 7, "/tmp/x")
    assert "--device_chunk=1" in argv and "--seed=7" in argv
    assert f"--d_model={cell.config['hidden_size']}" in argv
    # no width of the published model is changed; only listed keys differ
    entry = {c["name"]: c for c in manifest.load_manifest()["configs"]}[cell.config_name]
    for key, value in cell.config.get("published", {}).items():
        if key != "paper":
            assert key in entry["reduced"] and cell.config[key] != value
    for key in ("assumed", "bytes", "trainer", "deployment"):
        assert key in cell.config


def test_a_cell_added_as_files_only_is_found(tmp_path):
    root = tiny.make_root(str(tmp_path / "root"))
    cell = manifest.load_cell(tiny.CELL, root)
    assert cell.sizes["d_model"] == 32 and cell.mix["batch_per_chip"] == 4
    assert cell.limits() == tiny.LIMITS
    # a per-layer metric added as one file and one entry
    with open(os.path.join(root, "benchmark", "layer_metrics", "rows_in_window.py"), "w") as f:
        f.write("def read(run):\n    return float(run['window']['rows'])\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append({"name": "rows_in_window", "unit": "rows",
                               "better": "higher", "source": "program_counter",
                               "layer": "training loop",
                               "moves": "tokens_per_s_per_chip",
                               "workloads": [tiny.CELL]})
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = manifest.load_cell(tiny.CELL, root)
    assert "rows_in_window" in {m["name"] for m in cell.per_layer}
    assert cell.reader("rows_in_window")({"window": {"rows": 9}}) == 9.0
    other = manifest.load_cell(bench["workloads"][0]["name"], root)
    assert "rows_in_window" not in {m["name"] for m in other.per_layer}


def test_unknown_device_kind_is_an_error():
    assert manifest.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        manifest.peaks_for("source")
