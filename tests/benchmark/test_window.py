"""The window reducer on recorded rows: a rate is all the work over all the
time, so a stall in one interval moves it and the slowest step, not only a
median."""

import json

import pytest

from benchmark.harness import window


def rows(intervals, every=5, t0=1000.0, compiles=(3, 3)):
    """The trainer's pairs of rows: the recovery row, then at every display
    the loss row and the scalars row; ``intervals`` are seconds between
    displays, the first of them the compile's."""
    out = [{"step": 0, "time": t0 - 1, "recovery_restore_step": -1.0}]
    t, step = t0, 0
    for i, dt in enumerate([0.0] + list(intervals)):
        t += dt
        step = i * every
        out.append({"step": step, "time": t, "mini_batch_loss": 10.8, "training_accuracy": 0.0})
        out.append({"step": step, "time": t + 0.0004, "step_dispatch_s": 0.002,
                    "step_host_wait_s": 0.001, "compile_time_s": 31.5,
                    "compiles_total": compiles[0] if i < 2 else compiles[1]})
    return out


def text(r):
    return "".join(json.dumps(x) + "\n" for x in r)


def test_rate_and_slowest_over_the_whole_window():
    r = window.parse_rows(text(rows([60.0] + [2.5] * 6)))
    w = window.reduce_window(r, seconds=10.0, open_step=5, tokens_per_step=16384, chips=1)
    assert w["open"]["step"] == 5 and w["close"]["step"] == 25
    assert w["steps"] == 20 and w["seconds"] == pytest.approx(10.0)
    assert w["tokens_per_s_per_chip"] == pytest.approx(20 * 16384 / 10.0)
    assert w["step_ms_slowest"] == pytest.approx(500.0)
    assert w["rows"] == 5 and w["compiles_in_window"] == 0
    assert w["scalars_open"]["compile_time_s"] == 31.5


def test_a_stall_in_one_interval_moves_both():
    steady = window.reduce_window(rows([60.0] + [2.5] * 6), 10.0, 5, 16384, 1)
    stalled = window.reduce_window(rows([60.0, 2.5, 5.0, 2.5, 2.5, 2.5]), 10.0, 5, 16384, 1)
    # the window still closes at the first row at or after open + 10 s
    assert stalled["steps"] == 15 and stalled["seconds"] == pytest.approx(10.0)
    assert stalled["tokens_per_s_per_chip"] == pytest.approx(0.75 * steady["tokens_per_s_per_chip"])
    assert stalled["step_ms_slowest"] == pytest.approx(1000.0)
    assert stalled["step_ms_mean"] > steady["step_ms_mean"]
    # and the reducer says which interval it was
    assert stalled["slowest_interval"] == {
        "from_step": 10, "to_step": 15, "start": pytest.approx(1062.5),
        "seconds": pytest.approx(5.0)}


def test_what_the_host_did_in_an_interval_by_its_top_level_spans():
    from benchmark.harness import spans

    def span(name, ts, dur, **kw):
        return dict({"name": name, "ts": ts, "dur_s": dur, "parent": None,
                     "thread": "MainThread"}, **kw)

    recorded = [span("device_chunk", 99.0, 1.5),       # half of it inside
                span("display_wait", 100.5, 4.0),
                span("hbm_sample", 101.0, 0.5, parent=7),  # nested: its parent counts
                span("ckpt_write", 100.0, 5.0, thread="ckpt-writer"),
                span("display_eval", 104.5, 0.25),
                span("display_log", 104.75, 1.0)]      # runs past the end
    found = spans.inside(recorded, 100.0, 105.0)
    assert found == {"device_chunk": pytest.approx(0.5), "display_wait": pytest.approx(4.0),
                     "display_eval": pytest.approx(0.25), "display_log": pytest.approx(0.25),
                     "no_span": pytest.approx(0.0)}
    assert spans.inside(recorded[:1], 100.0, 105.0)["no_span"] == pytest.approx(4.5)


def test_per_chip_rate_divides_by_the_chips():
    one = window.reduce_window(rows([60.0] + [2.5] * 6), 10.0, 5, 16384, 1)
    four = window.reduce_window(rows([60.0] + [2.5] * 6), 10.0, 5, 4 * 16384, 4)
    assert four["tokens_per_s_per_chip"] == pytest.approx(one["tokens_per_s_per_chip"])


def test_the_compile_interval_lies_before_the_window_and_a_compile_inside_shows():
    r = rows([60.0] + [2.5] * 6, compiles=(3, 4))
    w = window.reduce_window(r, 10.0, 5, 16384, 1)
    assert w["open"]["time"] == pytest.approx(1060.0)
    assert w["compiles_in_window"] == 1


def test_a_window_that_never_closed_is_an_error_and_a_torn_row_is_skipped():
    r = window.parse_rows(text(rows([60.0, 2.5, 2.5])) + '{"step": 15, "time": 10')
    assert window.find_window(r, 10.0, 5)[1] is None
    with pytest.raises(RuntimeError):
        window.reduce_window(r, 10.0, 5, 16384, 1)
