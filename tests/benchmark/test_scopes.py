"""``harness/scopes.py``: the wire-format reader against a trace written out
by hand (two scopes, one nested in the other, a ``transpose(jvp(...))``
wrapper, a recomputed operation, an operation under no scope, a ``while``
whose own time is its interval less its children's, a string given as a
``ref_value``) and against ``ProfileData`` on 400 ms recorded on a TPU v5e
(PR 24's cut, which kept the events and dropped their metadata's stats: no
path, so all of it is unscoped); and the six scope metrics on a hand-built
``run``."""

import os

import pytest

from benchmark.harness import manifest, scopes, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "v5e-opt-1.3b-400ms.xplane.pb")
# 400 ms of opt-125m.train-s2048 on a v5e with the scopes in the program (PR
# 25): the end of a step, a whole display eval, the start of the next step;
# the device plane's two lines, and of each operation's metadata the four
# stats the reader keeps (HLO text cut to 240 characters)
SCOPED = os.path.join(DATA, "v5e-opt-125m-scopes-400ms.xplane.pb")
CATALOG = ("attention", "attn_proj", "mlp", "lm_head", "embed", "optimizer",
           "sample_batch")
BODY = "jit(chunk_fn)/while/body/closed_call"
REF = 9  # a stat metadata whose NAME is the string a ref_value stands for

# name -> (op_name path, hlo category, flops, bytes accessed)
OPS = {
    "while": ("jit(chunk_fn)/while", "while", 999, 999),
    "exp": (f"{BODY}/jvp(attn_proj)/attention/exp", "loop fusion", 10, 40),
    "qkv": (f"{BODY}/jvp(attn_proj)/bsd,dthe->tbshe", "convolution fusion",
            100, 20),
    "mlp_bwd": (f"{BODY}/transpose(jvp(mlp))/dot_general",
                "convolution fusion", 300, 30),
    "mlp_again": (f"{BODY}/transpose(jvp(jvp()))/checkpoint/"
                  "rematted_computation/mlp/mul", "loop fusion", 5, 8),
    "plumbing": ("jit(chunk_fn)/while/body/add", "non-fusion elementwise",
                 1, 4),
    "not_a_scope": ("jit(eval_fn)/jit(mlp)/mul", "loop fusion", 2, 6),
    "head": (None, "reduce", 7, 9),  # its path is the ref_value
}
HEAD_PATH = "jit(eval_fn)/lm_head/reduce_sum"


def plane(name, events):
    """One device plane in text form; ``events`` are (operation, start ns,
    duration ns) on the ``XLA Ops`` line."""
    ids = {n: i + 1 for i, n in enumerate(OPS)}
    out = [f'planes {{ name: "{name}"',
           f'  lines {{ id: 0 name: "{trace.OPS_LINE}" timestamp_ns: 0']
    for n, start, dur in events:
        out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                   f"{start * 1000 + 7} duration_ps: {dur * 1000} "
                   f"stats {{ metadata_id: 5 uint64_value: {start} }} }}")
    out.append("  }")
    for n, (path, category, flops, nbytes) in OPS.items():
        tf_op = (f"ref_value: {REF}" if path is None
                 else f'str_value: "{path}:"')
        out.append(
            f'  event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} '
            f'name: "%{n}.1 = f32[8]{{0}} {n}(f32[8]{{0}} %p)" '
            f'stats {{ metadata_id: 2 str_value: "{category}" }} '
            f"stats {{ metadata_id: 1 {tf_op} }} "
            f"stats {{ metadata_id: 3 int64_value: {flops} }} "
            f"stats {{ metadata_id: 4 int64_value: {nbytes} }} }} }}")
    for i, n in enumerate(("tf_op", "hlo_category", "flops",
                           "bytes_accessed", "device_offset_ps")):
        out.append(f'  stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}')
    out.append(f'  stat_metadata {{ key: {REF} value {{ id: {REF} '
               f'name: "{HEAD_PATH}:" }} }}')
    out.append("}")
    return "\n".join(out)


def device(attention=200):
    # a while over 0..1000 holding five operations (own time: what they
    # leave), then the eval's two operations; idle 1000..1100
    return [("while", 0, 1000), ("exp", 100, attention),
            ("qkv", 100 + attention, 150), ("mlp_bwd", 500, 300),
            ("mlp_again", 800, 100), ("plumbing", 900, 50),
            ("not_a_scope", 1100, 100), ("head", 1200, 100)]


@pytest.fixture
def written(tmp_path):
    from jax.profiler import ProfileData

    text = "\n".join([plane("/device:TPU:0", device()),
                      plane("/device:TPU:1", device(attention=250)),
                      'planes { name: "/host:CPU" lines { id: 1 name: "x" } }'])
    where = tmp_path / "trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    path = where / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_path_elements_see_through_transforms_but_not_functions():
    assert scopes.path_elements("jit(f)/transpose(jvp(mlp))/dot") == [
        "jit(f)", "mlp", "dot"]
    assert scopes.path_elements("a/transpose(jvp(jvp()))/checkpoint/x") == [
        "a", "", "checkpoint", "x"]
    assert scopes.scope_of(f"{BODY}/jvp(attn_proj)/attention/exp",
                           CATALOG) == "attention"
    assert scopes.scope_of("jit(attention)/pjit(mlp)/mul",
                           CATALOG) == scopes.UNSCOPED
    assert scopes.scope_of("", CATALOG) == scopes.UNSCOPED


def test_by_hand_own_times_scopes_remat_and_kept_stats(written):
    planes = scopes.read_device_planes(written)
    assert sorted(planes) == ["/device:TPU:0", "/device:TPU:1"]
    # whole nanoseconds, as ProfileData cuts them
    assert sorted((s, e) for s, e, _ in planes["/device:TPU:0"]["ops"])[:2] \
        == [(0, 1000), (100, 300)]
    meta = {m["name"].split(".")[0]: m
            for m in planes["/device:TPU:0"]["meta"].values()}
    assert meta["%head"]["op_name"] == HEAD_PATH
    assert meta["%qkv"]["op_name"].endswith("bsd,dthe->tbshe")
    assert meta["%qkv"]["hlo_category"] == "convolution fusion"

    d0 = scopes.reduce_device(planes["/device:TPU:0"], CATALOG)
    own = {k: v["own_ns"] for k, v in d0["scopes"].items()}
    # the while keeps 1000 - (200 + 150 + 300 + 100 + 50) = 200 for itself
    assert own == {"attention": 200, "attn_proj": 150, "mlp": 400,
                   "lm_head": 100, scopes.UNSCOPED: 200 + 50 + 100}
    assert d0["busy_ns"] == 1200 == sum(own.values())
    assert d0["remat_ns"] == 100 and d0["has_remat"]
    assert d0["scopes"]["mlp"]["flops"] == 305
    assert d0["scopes"]["mlp"]["bytes_accessed"] == 38
    assert d0["scopes"]["mlp"]["by_category"] == {
        "convolution fusion": 300, "loop fusion": 100}
    # the while's own numbers repeat its body's and are not added
    assert d0["scopes"][scopes.UNSCOPED]["flops"] == 1 + 2
    assert d0["scopes"][scopes.UNSCOPED]["by_category"]["while"] == 200


def run_of(written, window_ns=1300):
    logdir = written.partition(os.sep + "trace" + os.sep)[0]
    return {"logdir": logdir, "trace": {"window_s": window_ns / 1e9}}


def reader(name):
    cell = manifest.load_cell(manifest.load_manifest()["workloads"][0]["name"])
    assert name in {m["name"] for m in cell.per_layer}
    return cell.reader(name)


@pytest.mark.parametrize("metric,ns", [
    ("attention_device_pct", 250),  # the worst device
    ("mlp_device_pct", 400), ("head_device_pct", 100),
    ("optimizer_device_pct", 0), ("remat_device_pct", 100),
    ("unscoped_device_pct", 350)])
def test_scope_metrics_on_a_hand_built_run(written, capsys, metric, ns):
    value = reader(metric)(run_of(written))
    assert value == pytest.approx(100.0 * ns / 1300)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if l.startswith("scopes /device:TPU:")]
    assert len(lines) == 2
    for line in lines:  # every scope, unscoped and idle sum to 100
        shares = [float(p.split()[-1]) for p in
                  line.split(": ", 1)[1].split(" (")[0].split(", ")]
        assert len(shares) == len(scopes.catalog()) + 2
        assert sum(shares) == pytest.approx(100.0, abs=0.1)


@pytest.mark.parametrize("metric", [
    "attention_device_pct", "mlp_device_pct", "head_device_pct",
    "optimizer_device_pct", "remat_device_pct", "unscoped_device_pct"])
def test_scope_metrics_are_left_out_where_nothing_is_to_read(
        written, tmp_path, monkeypatch, metric):
    read = reader(metric)
    assert read({"logdir": run_of(written)["logdir"], "trace": None}) is None
    assert read({"logdir": str(tmp_path / "none"),
                 "trace": {"window_s": 1.0}}) is None
    # a program from before the scopes has no catalog to import
    monkeypatch.setattr(scopes, "catalog", lambda: ())
    assert read(run_of(written)) is None


def test_no_remat_in_the_program_reads_none(tmp_path):
    from jax.profiler import ProfileData

    events = [e for e in device() if e[0] != "mlp_again"]
    where = tmp_path / "trace"
    where.mkdir()
    (where / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            plane("/device:TPU:0", events)))
    run = {"logdir": str(tmp_path), "trace": {"window_s": 1300e-9}}
    assert reader("remat_device_pct")(run) is None
    assert reader("mlp_device_pct")(run) == pytest.approx(100 * 300 / 1300)


def test_on_a_recorded_trace_the_reader_agrees_with_profile_data():
    """The same operations at the same nanoseconds as ``ProfileData`` gives
    ``harness/trace.py``, every one of them unscoped (the file holds no
    path), and their own times add up to its busy time."""
    mine = scopes.read_device_planes(RECORDED)
    theirs = trace.read_planes(RECORDED)["devices"]
    assert sorted(mine) == sorted(theirs)
    for name in mine:
        assert sorted((s, e) for s, e, _ in mine[name]["ops"]) == \
            sorted((s, e) for s, e, _ in theirs[name]["ops"])
        short = {trace.short_name(m["name"])
                 for m in mine[name]["meta"].values()}
        assert short == {n for _, _, n in theirs[name]["ops"]}
    reduced = scopes.reduce_file(RECORDED, scopes.catalog())
    busy = trace.reduce_planes(trace.read_planes(RECORDED))["busy_s"]
    (device_0,) = reduced.values()
    assert list(device_0["scopes"]) == [scopes.UNSCOPED]
    assert device_0["busy_ns"] == round(busy * 1e9)
    assert not device_0["has_remat"]  # the cut kept no metadata stats


def test_on_a_trace_recorded_with_scopes_every_scope_has_its_time():
    reduced = scopes.reduce_file(SCOPED, CATALOG)
    planes = trace.read_planes(SCOPED)
    busy = trace.reduce_planes(planes)["busy_s"]
    (device_0,) = reduced.values()
    own = {k: v["own_ns"] for k, v in device_0["scopes"].items()}
    assert set(own) == set(CATALOG) | {scopes.UNSCOPED}
    assert sum(own.values()) == device_0["busy_ns"] == round(busy * 1e9)
    assert own["attention"] == 274_245_790  # 69.5 % of the busy time
    assert own[scopes.UNSCOPED] / device_0["busy_ns"] < 0.02
    assert not device_0["has_remat"]  # opt-125m runs without --remat
    # the kept stats: the attention core's matmuls run in f32 and reach
    # 4.85e12 operations in 0.274 s, a tenth of the chip's bf16 peak
    attention = device_0["scopes"]["attention"]
    assert attention["flops"] == 4_852_768_900_608
    assert attention["bytes_accessed"] > 0
    assert max(attention["by_category"],
               key=attention["by_category"].get) == "convolution fusion"
    paths = {m["op_name"] for m in
             scopes.read_device_planes(SCOPED)["/device:TPU:0"]["meta"].values()}
    assert any(p.startswith("jit(eval_fn)/") and "/attention/" in p
               for p in paths)  # the display eval inherits the names
    assert any("transpose(jvp(" in p and scopes.scope_of(p, CATALOG) ==
               "lm_head" for p in paths)
