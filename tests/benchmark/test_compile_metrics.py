"""The four set-up metrics that read the compile spans (``jit_trace_s``,
``jit_lower_s``, ``xla_compile_s``, ``cache_load_s``): on span lists written
by hand, on the records of a program without the spans, and on the records a
run of the tiny cell leaves on the CPU, where the spans and the trainer's
``compile_time_s`` come from the same events."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest
from tests.benchmark import tiny

T0 = 1_000_000.0
OPEN = T0 + 50.0
METRICS = ("jit_trace_s", "jit_lower_s", "xla_compile_s", "cache_load_s")


def reader(name):
    cell = manifest.load_cell(manifest.load_manifest()["workloads"][0]["name"])
    assert name in {m["name"] for m in cell.per_layer}
    return cell.reader(name)


def span(name, start, dur, ident, parent=7, thread="MainThread", **kw):
    return dict(name=name, ts=T0 + start, dur_s=dur, id=ident,
                parent=parent, thread=thread, depth=1, **kw)


def a_run(spans):
    return {"spans": spans, "setup_s": 50.0,
            "window": {"open": {"time": OPEN, "step": 5}}}


def compiled_spans():
    return [
        span("device_chunk", 20.0, 12.0, 7, parent=None),
        # the step: its trace holds two nested ones, which overlap
        span("compile_trace", 20.5, 0.2, 8, fun="attention"),
        span("compile_trace", 20.6, 0.3, 9, fun="mlp"),
        span("compile_trace", 20.0, 2.0, 10, fun="chunk_fn"),
        span("compile_lower", 22.0, 1.0, 11, fun="jit(chunk_fn)"),
        span("compile_backend", 23.0, 8.0, 12, fun="jit(chunk_fn)",
             cache="miss", stored=True),
        # the display's eval: loaded
        span("compile_trace", 40.0, 0.5, 13, fun="eval_fn"),
        span("compile_lower", 40.5, 0.25, 14, fun="jit(eval_fn)"),
        span("compile_backend", 40.75, 0.5, 15, fun="jit(eval_fn)",
             cache="hit", retrieval_s=0.4),
        # a small program with no persistent cache
        span("compile_trace", 45.0, 0.01, 16, fun="_norm"),
        span("compile_lower", 45.01, 0.02, 17, fun="jit(_norm)"),
        span("compile_backend", 45.03, 0.03, 18, fun="jit(_norm)",
             cache="off"),
        # ends after the opening row: the reference's, say
        span("compile_trace", 49.9, 0.2, 19, fun="late"),
        span("compile_lower", 60.0, 1.0, 20, fun="jit(late)"),
        span("compile_backend", 61.0, 9.0, 21, fun="jit(late)",
             cache="miss", stored=True),
    ]


EXPECTED = {"jit_trace_s": 2.0 + 0.5 + 0.01, "jit_lower_s": 1.0 + 0.25 + 0.02,
            "xla_compile_s": 8.0 + 0.03, "cache_load_s": 0.5}


@pytest.mark.parametrize("metric", METRICS)
def test_compile_metrics_on_a_hand_built_run(metric):
    assert reader(metric)(a_run(compiled_spans())) == pytest.approx(
        EXPECTED[metric], abs=1e-9)


def test_the_dearest_programs_and_the_counts_on_stderr(capsys):
    reader("xla_compile_s")(a_run(compiled_spans()))
    err = capsys.readouterr().err.strip().splitlines()
    assert len(err) == 1
    line = err[0]
    assert "2 programs compiled, 1 loaded" in line
    # by the time each cost, the program's trace, lower and compile together
    assert line.index("chunk_fn 2.000 / 1.000 / 8.000 miss") \
        < line.index("eval_fn 0.500 / 0.250 / 0.500 hit") \
        < line.index("_norm 0.010 / 0.020 / 0.030 off")
    # nested traces are part of their program, the late one is after it
    assert "attention" not in line and "late" not in line


@pytest.mark.parametrize("metric", ("jit_trace_s", "jit_lower_s"))
def test_traces_and_lowerings_are_a_union(metric):
    """Two threads' phases that overlap, and one inside the other, count
    the seconds in which any ran, once."""
    name = {"jit_trace_s": "compile_trace", "jit_lower_s": "compile_lower"}
    spans = [span(name[metric], 1.0, 4.0, 1, fun="a"),
             span(name[metric], 2.0, 1.0, 2, fun="b"),
             span(name[metric], 4.0, 2.0, 3, fun="c", thread="prefetch"),
             span(name[metric], 10.0, 1.0, 4, fun="d")]
    assert reader(metric)(a_run(spans)) == pytest.approx(6.0, abs=1e-9)


def test_a_run_where_every_program_loaded_compiled_nothing():
    spans = [s for s in compiled_spans() if s.get("cache") != "miss"
             or s["ts"] > OPEN]
    for s in spans:
        if s.get("cache") in ("miss", "off"):
            s["cache"] = "hit"
    assert reader("xla_compile_s")(a_run(spans)) == 0.0
    assert reader("cache_load_s")(a_run(spans)) == pytest.approx(0.53)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_spans_reads_none(metric):
    """The parent of the compile spans writes set-up spans and no others."""
    spans = [span("data_build", 8.0, 6.0, 2, parent=None),
             span("state_init", 14.0, 10.0, 3, parent=None),
             span("device_chunk", 24.0, 10.0, 4, parent=None)]
    assert reader(metric)(a_run(spans)) is None
    late = [s for s in compiled_spans() if s["ts"] + s["dur_s"] > OPEN]
    assert reader(metric)(a_run(spans + late)) is None


KEEP = """
import sys
sys.path.insert(0, {repo!r})
from benchmark import run
from tests.benchmark import tiny
sys.exit(run.main(["--workload", tiny.CELL, "--seed", "3000003901",
                   "--seconds", "1", "--trace", "0", "--keep", {keep!r}],
                  require_tpu=False, root={root!r}))
"""


def test_the_spans_of_a_cpu_run_add_up_to_the_trainers_compile_seconds(
        tmp_path):
    """The tiny cell once on the CPU: every compile before the window has
    its three phases as spans, each inside a span of the set-up, and the
    backend phases add up to the trainer's ``compile_time_s`` at the
    opening row, which ``compile_s`` reads."""
    from benchmark.harness import spans as spans_mod
    from benchmark.harness import window

    root = tiny.make_root(str(tmp_path / "root"), chips=1, mode="auto")
    keep = str(tmp_path / "records")
    p = subprocess.run(
        [sys.executable, "-c", KEEP.format(repo=tiny.REPO, keep=keep,
                                          root=root)],
        capture_output=True, text=True, cwd=tiny.REPO, timeout=600,
        env=dict(os.environ,
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    rows = window.read_rows(os.path.join(keep, "metrics.jsonl"))
    run = {"logdir": keep, "spans": spans_mod.read_spans(keep),
           "setup_s": line["metrics"]["setup_s"]["value"],
           "window": window.reduce_window(rows, 1.0, 5, 256, 1)}
    read = {m: reader(m)(run) for m in METRICS + ("compile_s",)}
    assert all(read[m] is not None and read[m] >= 0 for m in read), read
    assert read["jit_trace_s"] > 0 and read["jit_lower_s"] > 0
    # the tests run with the persistent cache off: everything compiled
    assert read["cache_load_s"] == 0.0
    assert read["xla_compile_s"] == pytest.approx(read["compile_s"],
                                                  abs=1e-3)
    phases = [s for s in run["spans"] if s["name"].startswith("compile_")]
    ids = {s["id"]: s for s in run["spans"]}
    assert phases and all(s["parent"] in ids for s in phases)
    programs = {s["fun"] for s in phases if s["name"] == "compile_backend"}
    assert "jit(chunk_fn)" in programs
