"""``BENCHMARK.json`` keeps to the contract's shapes and names, every cell
resolves to its files by name, a cell added as files only is found, and so
is a configuration of another architecture that brings its family's module."""

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
    # same end-to-end metric as any of them
    rooflines = [n for n in per_layer if n.endswith("_roofline")]
    assert "attention_roofline" in rooflines
    assert all(per_layer[n]["unit"] == "%" for n in rooflines)
    mfu = [n for n in per_layer if "mfu" in re.split(r"[_.\-]", n)]
    assert mfu and all(per_layer[n]["unit"] == "%" for n in mfu)
    assert {per_layer[n]["moves"] for n in rooflines} <= {per_layer[n]["moves"] for n in mfu}


@pytest.mark.parametrize("cell_name", [w["name"] for w in manifest.load_manifest()["workloads"]])
def test_every_cell_resolves_to_its_files(cell_name):
    cell = manifest.load_cell(cell_name)
    assert os.path.samefile(cell.family_file, os.path.join(
        manifest.BENCH_DIR, "reference", f"{cell.config['family']}.py"))
    assert cell.sizes == cell.family().sizes(cell.config, cell.mix)
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


# the trainer's command line of both cells, as PR 24 to PR 26 built it
SHARED = ["--seq_len=2048", "--batch_size=8", "--mode=auto", "--display_step=5",
          "--device_chunk=1", "--device_data=true", "--seed=7",
          "--logdir=/tmp/x", "--data_dir=/tmp/x/data",
          "--training_iter=1000000000", "--save_model_secs=1000000",
          "--test_eval=false", "--model=lm", "--dataset=lm",
          "--optimizer=adam", "--learning_rate=0.0001", "--bf16=true"]
LAST = ["--attn_block=512", "--ce_block=2048", "--keep_prob=1.0",
        "--prng=threefry"]
ARGV = {
    "opt-1.3b.train-s2048": [
        "--d_model=2048", "--num_heads=32", "--num_blocks=8",
        "--vocab_size=50272"] + SHARED + ["--remat=true"] + LAST,
    "opt-125m.train-s2048": [
        "--d_model=768", "--num_heads=12", "--num_blocks=12",
        "--vocab_size=50272"] + SHARED + ["--remat=false"] + LAST,
}


@pytest.mark.parametrize("cell_name", sorted(ARGV))
def test_the_trainers_command_line_is_the_list_it_was(cell_name):
    cell = manifest.load_cell(cell_name)
    assert manifest.trainer_argv(cell, 7, "/tmp/x") == ARGV[cell_name]


def test_the_opt_family_refuses_an_mlp_that_is_not_four_times_the_width():
    cell = manifest.load_cell("opt-125m.train-s2048")
    family = cell.family()
    assert family.trainer_flags(cell.config, cell.mix) == {
        "d_model": 768, "num_heads": 12, "num_blocks": 12, "vocab_size": 50272}
    assert family.sizes(cell.config, cell.mix)["ffn_dim"] == 4 * 768
    with pytest.raises(ValueError, match="4 x d_model"):
        family.trainer_flags(dict(cell.config, ffn_dim=2048), cell.mix)
    # the positions table is the harness's to hold the mix to
    cell.mix["seq_len"] = 4096
    with pytest.raises(ValueError, match="positions"):
        manifest.trainer_argv(cell, 7, "/tmp/x")


@pytest.mark.parametrize("family,error,looked_for", [
    (None, KeyError, "benchmark/configs/tiny.json"),
    ("", KeyError, "benchmark/configs/tiny.json"),
    ("no_such_lm", FileNotFoundError, os.path.join("benchmark", "reference", "no_such_lm.py")),
])
def test_a_configuration_without_a_family_is_an_error_that_names_the_file(
        tmp_path, family, error, looked_for):
    root = tiny.make_root(str(tmp_path / "root"))
    path = os.path.join(root, "benchmark", "configs", "tiny.json")
    with open(path) as f:
        config = json.load(f)
    if family is None:
        del config["family"]
    else:
        config["family"] = family
    with open(path, "w") as f:
        json.dump(config, f)
    with pytest.raises(error) as raised:
        manifest.load_cell(tiny.CELL, root)
    assert looked_for in str(raised.value)


def test_a_family_that_lacks_a_name_the_harness_calls_is_an_error(tmp_path):
    root = tiny.make_root(str(tmp_path / "root"))
    path = os.path.join(root, "benchmark", "reference", "opt_lm.py")
    with open(path) as f:
        source = f.read()
    with open(path, "w") as f:
        f.write(source.replace("def state_bytes(", "def _state_bytes("))
    cell = manifest.load_cell(tiny.CELL, root)  # found; read at first use
    with pytest.raises(AttributeError, match="state_bytes") as raised:
        cell.family()
    assert path in str(raised.value)


def test_a_second_architecture_is_added_as_files_and_entries_only(tmp_path):
    """The Switch-routed LM: its family's module, a configuration that names
    it, limits and two entries; the harness's files are the checkout's."""
    root = tiny.add_switch_family(tiny.make_root(str(tmp_path / "root")))
    cell = manifest.load_cell(tiny.SWITCH_CELL, root)
    assert cell.family_file == os.path.join(root, "benchmark", "reference",
                                            "switch_lm.py")
    assert cell.sizes["num_experts"] == 4 and cell.sizes["d_model"] == 32
    argv = manifest.trainer_argv(cell, 7, "/tmp/x")
    assert argv[:7] == ["--d_model=32", "--num_heads=2", "--num_blocks=2",
                        "--vocab_size=300", "--moe_experts=4",
                        "--moe_capacity=1.25", "--moe_aux=0.01"]
    assert argv[7:10] == ["--seq_len=64", "--batch_size=4", "--mode=local"]
    # the whole step's share of a peak given by hand (a CPU run has none)
    # is the second family's count: 231,168 operations a token (test_flops)
    run = {"cell": cell, "peaks": {"bf16_flops_per_s": 1e12},
           "window": {"tokens_per_s_per_chip": 1000.0}}
    assert cell.reader("step_mfu")(run) == pytest.approx(
        100.0 * 231_168 * 1000.0 / 1e12, rel=1e-12)
    assert cell.reader("step_mfu")(dict(run, peaks=None)) is None
    # the first family's cell beside it reads its own
    other = manifest.load_cell(tiny.CELL, root)
    assert "num_experts" not in other.sizes
    assert other.reader("step_mfu")(dict(run, cell=other)) < \
        cell.reader("step_mfu")(run)


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
