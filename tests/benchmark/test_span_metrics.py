"""The five metrics that read the trainer's spans (``launch_s``,
``data_build_s``, ``state_init_s``, ``setup_unattributed_s``,
``display_host_ms_worst``) on a ``run`` written out by hand, and on the
records of a program from before these spans, where each is left out."""

import json

import pytest

from benchmark.harness import manifest

T0 = 1_000_000.0  # the process started here
OPEN, CLOSE = T0 + 50.0, T0 + 80.0


def span(name, start, dur, ident, parent=None, thread="MainThread", **kw):
    return dict(name=name, ts=T0 + start, dur_s=dur, id=ident,
                parent=parent, thread=thread, depth=0 if parent is None else 1,
                **kw)


def a_run(tmp_path, with_new_spans=True):
    spans = [
        span("data_build", 8.0, 6.0, 2),
        span("state_init", 14.0, 10.0, 3),
        span("data_put", 24.0, 1.0, 4),
        span("ckpt_write", 24.2, 0.5, 5, parent=4),        # a child: not top
        span("prefetch_stage", 9.0, 30.0, 6, thread="prefetch"),  # not main
        span("display_stage", 25.0, 0.5, 7, step=0),
        span("display_wait", 25.5, 0.1, 8, step=0),
        span("display_eval", 25.6, 4.4, 9, step=0),
        span("display_log", 30.0, 0.01, 10, step=0),
        span("device_chunk", 30.01, 15.0, 11, step=0),
        span("display_stage", 45.01, 0.002, 12, step=5),
        span("display_wait", 45.012, 4.0, 13, step=5),
        span("display_eval", 49.012, 0.3, 14, step=5),
        span("display_log", 49.9, 0.2, 15, step=5),        # ends after OPEN
        # inside the window: three displays
        span("display_stage", 55.0, 0.002, 20, step=10),
        span("display_log", 56.0, 0.003, 21, step=10),
        span("display_stage", 60.0, 0.004, 22, step=15),
        span("display_log", 61.0, 3.0, 23, step=15),       # the stall
        span("display_stage", 79.0, 0.001, 24, step=20),
        span("display_log", 79.9, 0.001, 25, step=20),
        span("display_stage", 85.0, 9.0, 26, step=25),     # after the close
        span("display_log", 95.0, 9.0, 27, step=25),
    ]
    records = [{"kind": "header", "name": "spans_header", "run": "ab",
                "pid": 1, "epoch": T0 + 7.9, "perf_counter": 3.0},
               dict(name="train_start", ts=T0 + 7.5, dur_s=0.0, instant=True,
                    id=1, parent=None, thread="MainThread", depth=0)] + spans
    if not with_new_spans:
        old = ("device_chunk", "ckpt_write", "prefetch_stage", "display_eval")
        spans = [{k: v for k, v in s.items() if k not in ("id", "parent")}
                 for s in spans if s["name"] in old]
        records = spans
    with open(tmp_path / "spans-worker-0.jsonl", "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return {"logdir": str(tmp_path), "spans": spans, "setup_s": 50.0,
            "window": {"open": {"time": OPEN, "step": 5},
                       "close": {"time": CLOSE, "step": 20}}}


def reader(name):
    cell = manifest.load_cell(manifest.load_manifest()["workloads"][0]["name"])
    assert name in {m["name"] for m in cell.per_layer}
    return cell.reader(name)


# top-level main-thread spans that ended before the opening row: 6 + 10 + 1
# + (0.5 + 0.1 + 4.4 + 0.01) + 15 + (0.002 + 4.0 + 0.3) = 41.312
EXPECTED = {"launch_s": 7.5, "data_build_s": 6.0, "state_init_s": 10.0,
            "setup_unattributed_s": 50.0 - 7.5 - 41.312,
            "display_host_ms_worst": 3004.0}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_span_metrics_on_a_hand_built_run(tmp_path, capsys, metric):
    value = reader(metric)(a_run(tmp_path))
    assert value == pytest.approx(EXPECTED[metric], abs=1e-6)
    if metric == "display_host_ms_worst":
        err = capsys.readouterr().err
        assert "step 15" in err and "the longer is display_log" in err


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_span_metrics_are_left_out_for_a_program_without_the_spans(
        tmp_path, metric):
    assert reader(metric)(a_run(tmp_path, with_new_spans=False)) is None


def test_no_spans_file_at_all_reads_none(tmp_path):
    run = a_run(tmp_path)
    run["logdir"] = str(tmp_path / "gone")
    assert reader("launch_s")(run) is None
    assert reader("setup_unattributed_s")(run) is None
