"""The reduction from the profiler's trace to busy time, idle share, a
step's device time, the other programs' share and the exposed collective:
exact on a trace written out by hand, and on 400 ms cut from a trace
recorded on a TPU v5e (opt-1.3b.train-s2048, PR 24: the end of a step, a
whole display eval, the start of the next step)."""

import os
import tempfile

import pytest

from benchmark.harness import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def plane(name, lines):
    """One XPlane in text form; ``lines`` maps a line's name to its
    (event name, start ns, duration ns)."""
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ name: "{name}"']
    for i, (line, evs) in enumerate(lines.items()):
        out.append(f'  lines {{ id: {i} name: "{line}" timestamp_ns: 0')
        for n, start, dur in evs:
            out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                       f"{start * 1000} duration_ps: {dur * 1000} }}")
        out.append("  }")
    for n, i in ids.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}')
    out.append("}")
    return "\n".join(out)


def planes_of(text):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(text)
    with tempfile.NamedTemporaryFile(suffix=".pb") as f:
        f.write(raw)
        f.flush()
        return trace.read_planes(f.name)


FUSION = "%fusion.1 = bf16[8,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8,2048]{1,0} %p), kind=kLoop"
WHILE = "%while.2 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), condition=%c, body=%b"
START = "%all-reduce-start.3 = f32[1024]{0:T(1024)} all-reduce-start(f32[1024]{0} %g), replica_groups={{0,1}}"
DONE = "%all-reduce-done.3 = f32[1024]{0:T(1024)} all-reduce-done(f32[1024]{0} %all-reduce-start.3)"


def device(shift=0, done_until=900):
    # 0..400 a while holding two fusions (100..200, 250..400); idle 400..500;
    # the all-reduce starts (10 ns), a fusion runs beside it for 190 ns,
    # then the core waits in all-reduce-done until ``done_until``
    ops = [(WHILE, 0, 400), (FUSION, 100, 100), (FUSION, 250, 150),
           (START, 500 + shift, 10), (FUSION, 510 + shift, 190),
           (DONE, 700 + shift, done_until - 700 - shift)]
    modules = [("jit_chunk_fn(1)", 0, 400), ("jit_eval_fn(2)", 500, 100),
               ("jit_chunk_fn(1)", 600, 300)]
    return {"XLA Ops": ops, "XLA Modules": modules}


def test_by_hand_busy_union_idle_and_exposed_collective():
    host = plane("/host:CPU", {"python3": [("bench_epoch_mark:1000.5", 0, 10)]})
    text = "\n".join([plane("/device:TPU:0", device()),
                      plane("/device:TPU:1", device(shift=50, done_until=1000)),
                      host])
    spans = [{"name": "display_eval", "ts": 1000.5 + 390e-9, "dur_s": 150e-9}]
    r = trace.reduce_planes(planes_of(text), spans)
    assert r["window_s"] == pytest.approx(1000e-9)
    # device 0: 0..400 and 500..900 busy; device 1: 0..400 and 550..1000
    assert r["busy_s_least"] == pytest.approx(800e-9)
    assert r["busy_s_worst"] == pytest.approx(850e-9)
    assert r["busy_s"] == pytest.approx(825e-9)
    # device 0 starts the all-reduce in 10 ns and waits 700..900 for it;
    # device 1 waits 750..1000: 10 + 250 ns in a collective, the worst
    assert r["collective_exposed_s"] == pytest.approx(260e-9)
    # the step is the program with most device time; the first touches the
    # trace's edge and is no whole step
    assert r["step_module"] == "jit_chunk_fn(1)"
    assert r["steps"] == 1 and r["step_s"] == pytest.approx(300e-9)
    assert r["other_programs"] == ["jit_eval_fn(2)"]
    assert r["other_programs_s"] == pytest.approx(100e-9)
    ops = dict(r["breakdown"]["device_ops"])
    # a while's own time is its interval less its body's operations
    assert ops["while (s32[], f32[4]) x1"] == pytest.approx(150e-9)
    assert ops["fusion bf16[8,2048] x3"] == pytest.approx(440e-9)
    assert ops["all-reduce-done f32[1024] x1"] == pytest.approx(200e-9)
    # device 0's gaps: 400..500 falls in the host's display_eval span;
    # 900..1000, while device 1 still waits, in none
    assert r["breakdown"]["idle_gaps"] == [["display_eval", pytest.approx(100e-9)],
                                           ["none", pytest.approx(100e-9)]]


def test_no_collective_reads_nothing_and_no_device_is_an_error():
    one = device()
    one["XLA Ops"] = [e for e in one["XLA Ops"] if e[0] not in (START, DONE)]
    r = trace.reduce_planes(planes_of(plane("/device:TPU:0", one)))
    assert r["collective_exposed_s"] is None
    assert r["breakdown"]["idle_gaps"][0][0] == "unknown"  # no mark in the trace
    with pytest.raises(RuntimeError):
        trace.reduce_planes(planes_of(plane("/host:CPU", {"python3": [("x", 0, 1)]})))


def test_recorded_on_a_v5e():
    planes = trace.read_planes(os.path.join(DATA, "v5e-opt-1.3b-400ms.xplane.pb"))
    assert planes["mark"] == (41149329, 1790772264.07367)
    r = trace.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(0.398527509)
    assert r["busy_s"] == pytest.approx(0.394058895)
    assert 100 * (1 - r["busy_s_least"] / r["window_s"]) == pytest.approx(1.1213, abs=1e-3)
    # the one whole program in the cut is the display eval: 286.8 ms
    assert r["step_module"].startswith("jit_eval_fn") and r["steps"] == 1
    assert r["step_s"] == pytest.approx(0.286773418)
    assert r["collective_exposed_s"] is None
    top, seconds = r["breakdown"]["device_ops"][0]
    assert top == "fusion f32[8,32,2048,64] x38" and seconds == pytest.approx(0.070298811)
    # the longest gap: 4.4 ms between the step's last operation and the eval
    assert r["breakdown"]["idle_gaps"][0][1] == pytest.approx(0.004439937)


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert trace.total(trace.subtract([(0, 4), (6, 9)], [(1, 7)])) == 3
    assert trace.short_name(FUSION) == "%fusion.1 fusion bf16[8,2048]"
    assert trace.is_collective(trace.short_name(START))
    assert trace.is_collective(trace.short_name(DONE))
    assert trace.is_collective("%all-reduce.9 all-reduce f32[8]")
    assert not trace.is_collective(trace.short_name(FUSION))
