"""``attention_roofline``: the family's attention count over the
``attention`` scope's own time inside one whole run of the step's program,
on a trace written out by hand (three whole steps of differing length, one
cut by the trace's end, a display eval between them) and on 400 ms recorded
on a TPU v5e, whose one whole program is the display eval."""

import os
import shutil

import pytest

from benchmark.harness import manifest, scopes, trace
from tests.benchmark.test_scopes import CATALOG, SCOPED

CELL = "opt-125m.train-s2048"
PEAKS = {"bf16_flops_per_s": 197e12}
STEP, EVAL = "jit_chunk_fn(1)", "jit_eval_fn(2)"
ATTENTION = "jit(chunk_fn)/while/body/closed_call/jvp(attn_proj)/attention/exp"
# (program, start ns, duration ns, own ns of its one attention operation)
RUNS = [(STEP, 0, 900, 0), (STEP, 1000, 1000, 400), (EVAL, 2100, 500, 100),
        (STEP, 2700, 1000, 440), (STEP, 3800, 1000, 800),
        (STEP, 4900, 1000, 100)]  # the first and the last are cut


def written(tmp_path):
    from jax.profiler import ProfileData

    ops, modules = [], []
    for i, (program, start, dur, attention) in enumerate(RUNS):
        modules.append(f"events {{ metadata_id: {1 if program == STEP else 2} "
                       f"offset_ps: {start * 1000} duration_ps: {dur * 1000} }}")
        # an operation under no scope fills the run; attention sits in it
        if i == 0:
            start, dur = start + 100, dur - 100  # the trace began inside it
        if i == len(RUNS) - 1:
            dur = 500  # and stopped inside this one
        ops.append(f"events {{ metadata_id: 3 offset_ps: {start * 1000} "
                   f"duration_ps: {dur * 1000} }}")
        if attention:
            ops.append(f"events {{ metadata_id: 4 offset_ps: {(start + 50) * 1000} "
                       f"duration_ps: {attention * 1000} }}")
    text = "\n".join([
        'planes { name: "/device:TPU:0"',
        f'lines {{ id: 0 name: "{trace.OPS_LINE}" {" ".join(ops)} }}',
        f'lines {{ id: 1 name: "{trace.MODULES_LINE}" {" ".join(modules)} }}',
        f'event_metadata {{ key: 1 value {{ id: 1 name: "{STEP}" }} }}',
        f'event_metadata {{ key: 2 value {{ id: 2 name: "{EVAL}" }} }}',
        'event_metadata { key: 3 value { id: 3 name: "%while.1 = () while()" '
        'stats { metadata_id: 1 str_value: "jit(chunk_fn)/while:" } } }',
        'event_metadata { key: 4 value { id: 4 name: "%exp.1 = f32[8]{0} exp()" '
        f'stats {{ metadata_id: 1 str_value: "{ATTENTION}:" }} }} }}',
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } }', "}"])
    where = tmp_path / "trace"
    where.mkdir(parents=True)
    (where / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def run_of(logdir, step_module=STEP, **kw):
    return dict({"logdir": logdir, "cell": manifest.load_cell(CELL),
                 "peaks": PEAKS,
                 "trace": {"window_s": 1.0, "step_module": step_module}}, **kw)


@pytest.fixture(scope="module")
def read():
    cell = manifest.load_cell(CELL)
    assert "attention_roofline" in {m["name"] for m in cell.per_layer}
    return cell.reader("attention_roofline")


def test_by_hand_the_median_whole_step_and_nothing_of_the_eval(tmp_path, read):
    logdir = written(tmp_path)
    (device,) = scopes.reduce_file(os.path.join(
        logdir, "trace", "h.xplane.pb"), CATALOG).values()
    attention = device["scopes"]["attention"]
    # every operation is in own_ns; by_run holds the whole runs alone
    assert attention["own_ns"] == 400 + 100 + 440 + 800 + 100
    assert attention["by_run"] == {STEP: [400, 440, 800], EVAL: [100]}
    assert device["scopes"][scopes.UNSCOPED]["by_run"][STEP] == [600, 560, 200]
    # 6 L d S = 6 x 12 x 768 x 2,048 operations a token, 8 x 2,048 tokens a
    # step, over the median step's 440 ns
    flops = 6 * 12 * 768 * 2048 * 8 * 2048
    assert read(run_of(logdir)) == pytest.approx(
        100.0 * flops / 440e-9 / 197e12, rel=1e-12)


def test_nothing_to_read_is_none_and_never_nought(tmp_path, read, monkeypatch):
    logdir = written(tmp_path)
    assert read(run_of(logdir, peaks=None)) is None           # a CPU run
    assert read(run_of(logdir, trace=None)) is None           # --trace 0
    assert read(run_of(logdir, "jit_other(3)")) is None       # no such program
    assert read(run_of(str(tmp_path / "none"))) is None       # no file
    cell = manifest.load_cell(CELL)
    counts = cell.family().scope_flops_per_token
    monkeypatch.setattr(cell.family(), "scope_flops_per_token",
                        lambda sizes: {k: v for k, v in counts(sizes).items()
                                       if k != "attention"})
    assert read(run_of(logdir)) is None      # the family gives no such count
    monkeypatch.undo()
    monkeypatch.setattr(scopes, "catalog", lambda: ())
    assert read(run_of(logdir)) is None      # the program names no scopes


def test_on_the_recorded_trace_no_step_ran_whole_and_the_eval_reads_its_share(
        tmp_path, read):
    where = tmp_path / "trace"
    where.mkdir()
    shutil.copy(SCOPED, where / "recorded.xplane.pb")
    planes = trace.reduce_planes(trace.read_planes(SCOPED))
    assert planes["steps"] == 0 and planes["step_module"].startswith("jit_chunk_fn(")
    run = run_of(str(tmp_path), planes["step_module"])
    assert read(run) is None
    # the display eval between the two cut steps is a whole forward pass of
    # 8 x 2,048 tokens: 83.83 ms under the attention scope (PR 25's scan).
    # Read as if it were the step it is held to three times its own count
    # (forward and backward), so its forward share of the peak is a third:
    # 2 L d S x 16,384 tokens = 0.618 TFLOP in 83.83 ms, 7.4 TFLOP/s
    (eval_fn,) = planes["other_programs"]
    (device,) = scopes.of_run(run).values()
    assert device["scopes"]["attention"]["by_run"] == {eval_fn: [83_831_298]}
    as_step = read(run_of(str(tmp_path), eval_fn))
    forward = 2 * 12 * 768 * 2048 * 16384
    assert as_step / 3 == pytest.approx(
        100.0 * forward / 83_831_298e-9 / 197e12, rel=1e-12)
    assert as_step / 3 == pytest.approx(3.744, abs=1e-3)
