"""The trainer's own spans, as a run of the tiny cell leaves them: the
command of ``test_run_cpu.py`` once more on the CPU, with ``--keep``, and the
span metrics read from the records it kept.

The run is a process of its own (the trainer takes SIGTERM on its main
thread)."""

import json
import os
import subprocess
import sys

import pytest

from tests.benchmark import tiny

REPO = tiny.REPO

KEEP = """
import sys
sys.path.insert(0, {repo!r})
from benchmark import run
from tests.benchmark import tiny
sys.exit(run.main(["--workload", tiny.CELL, "--seed", "2147483693",
                   "--seconds", "1", "--trace", "0", "--keep", {keep!r}],
                  require_tpu=False, root={root!r}))
"""


@pytest.fixture(scope="module")
def kept(tmp_path_factory):
    """(result line, the run's records as ``--keep`` leaves them)."""
    base = tmp_path_factory.mktemp("kept")
    root = tiny.make_root(str(base / "root"), chips=1, mode="auto")
    keep = str(base / "records")
    p = subprocess.run(
        [sys.executable, "-c", KEEP.format(repo=REPO, keep=keep, root=root)],
        capture_output=True, text=True, cwd=REPO, timeout=600,
        env=dict(os.environ,
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), keep


def test_the_spans_of_set_up_and_of_a_display_in_their_order(kept):
    _, keep = kept
    with open(os.path.join(keep, "spans-worker-0.jsonl")) as f:
        records = [json.loads(line) for line in f]
    assert records[0]["kind"] == "header" and records[0]["pid"] > 0
    top = [r for r in records[1:] if r["parent"] is None
           and r["thread"] == "MainThread"]
    names = [r["name"] for r in top]
    first = ["train_start", "data_build", "state_init", "data_put"]
    display = ["display_stage", "display_wait", "display_eval", "display_log"]
    assert [n for n in names if n in first + display][:8] == first + display
    assert all(r["depth"] == 0 for r in top)
    assert len({r["id"] for r in records[1:]}) == len(records) - 1
    # what is written inside a span names that span as its parent
    ids = {r["id"]: r for r in records[1:]}
    nested = [r for r in records[1:] if r["parent"] is not None]
    assert nested and all(r["depth"] == ids[r["parent"]]["depth"] + 1
                          and r["thread"] == ids[r["parent"]]["thread"]
                          for r in nested)
    # every display of the run is the four spans, the row written in the last
    with open(os.path.join(keep, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    synced = {r["step"]: r["time"] for r in rows if "mini_batch_loss" in r}
    by_step = {}
    for r in top:
        if r["name"] in display:
            by_step.setdefault(r["step"], []).append(r)
    assert len(by_step) >= 20 and set(by_step) == set(synced)
    for step, spans in by_step.items():
        assert [s["name"] for s in spans] == display
        end = lambda s: s["ts"] + s["dur_s"]
        assert end(spans[2]) - 1e-3 <= synced[step] <= end(spans[3]) + 1e-3


def test_set_up_is_accounted_for_by_the_spans_of_the_kept_run(kept):
    """The span metrics on real records: launch, the split, the state and
    what no span covers add up to ``setup_s`` with the other top-level
    spans, and what no span covers is a small part of it."""
    from benchmark.harness import manifest, spans, window

    line, keep = kept
    rows = window.read_rows(os.path.join(keep, "metrics.jsonl"))
    run = {"logdir": keep, "spans": spans.read_spans(keep),
           "setup_s": line["metrics"]["setup_s"]["value"],
           "window": window.reduce_window(rows, 1.0, 5, 256, 1)}
    cell = manifest.load_cell(
        manifest.load_manifest()["workloads"][0]["name"])
    read = {m: cell.reader(m)(run) for m in (
        "launch_s", "data_build_s", "state_init_s", "setup_unattributed_s",
        "display_host_ms_worst")}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert read["launch_s"] + read["data_build_s"] + read["state_init_s"] \
        < run["setup_s"]
    assert read["setup_unattributed_s"] < 0.1 * run["setup_s"], read
    assert read["display_host_ms_worst"] < 1e3
