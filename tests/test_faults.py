"""Deterministic fault injection + the verified-restore fallback ladder.

The r8 robustness tentpole under test: every failure mode the recovery
code claims to survive is exercised through --fault_spec rules (or direct
file surgery where a machine crash is being forged), and restore is
proven to quarantine the damaged set and walk back instead of crashing
or training on garbage.
"""

import io
import json
import os
import subprocess
import sys
import zipfile

import numpy as np
import pytest

from distributed_tensorflow_tpu.checkpoint.checkpoint import (
    CheckpointCorruptError,
    latest_checkpoint,
    restore_latest,
    restore_with_fallback,
    save_checkpoint,
    save_checkpoint_sharded,
)
from distributed_tensorflow_tpu.utils import faults
from distributed_tensorflow_tpu.utils.events import (
    _crc32c,
    _crc32c_numpy,
    crc32c,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test starts and ends with no rules armed (and the env-var
    check forgotten), so specs cannot leak between tests."""
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------------ spec grammar


def test_spec_parses_the_documented_examples():
    rules = faults.parse_fault_spec(
        "ckpt_write:at_step=40:mode=crash,restore:mode=torn_file,"
        "init:mode=refuse:times=2")
    assert [r.point for r in rules] == ["ckpt_write", "restore", "init"]
    assert rules[0].mode == "crash" and rules[0].at_step == 40
    assert rules[1].mode == "torn_file"
    assert rules[2].mode == "refuse" and rules[2].times == 2


@pytest.mark.parametrize("bad,match", [
    ("bogus:mode=crash", "unknown injection point"),
    ("restore:mode=explode", "unknown mode"),
    ("restore:frequency=2", "unknown key"),
    ("restore:at_step=x", "expected an integer"),
    ("restore:mode", "key=value"),
])
def test_spec_rejects_mistakes_with_the_grammar(bad, match):
    with pytest.raises(faults.FaultSpecError, match=match):
        faults.parse_fault_spec(bad)


def test_flag_validator_rejects_bad_spec_at_parse_time():
    from distributed_tensorflow_tpu import flags

    flags.define_reference_flags()
    flags.FLAGS._reset()
    try:
        with pytest.raises(ValueError, match="--fault_spec"):
            flags.FLAGS._parse(["--fault_spec=nonsense:mode=crash"])
    finally:
        flags.FLAGS._reset()


def test_every_registered_point_is_described():
    text = faults.describe_points()
    for point in faults.INJECTION_POINTS:
        assert point in text


def test_trace_ops_lists_faults():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_ops.py"),
         "--faults"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert r.returncode == 0, r.stderr
    for point in faults.INJECTION_POINTS:
        assert point in r.stdout


# ------------------------------------------------------- firing semantics


def test_fault_point_noop_when_unarmed():
    faults.fault_point("restore", path="/nope", step=1)  # must not raise


def test_error_mode_fires_with_matching_filters():
    faults.configure("prefetch:at_count=2:mode=error")
    faults.fault_point("prefetch", count=0)
    faults.fault_point("prefetch", count=1)
    with pytest.raises(faults.InjectedFault):
        faults.fault_point("prefetch", count=2)
    # times defaults to 1: the same count passing again does not re-fire
    faults.fault_point("prefetch", count=2)


def test_times_and_after_budgets():
    faults.configure("init:mode=refuse:times=2:after=1")
    faults.fault_point("init", attempt=0)  # consumed by after=1
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            faults.fault_point("init")
    faults.fault_point("init")  # budget exhausted


def test_env_var_arms_subprocessless_callers(monkeypatch):
    monkeypatch.setenv("DTT_FAULT_SPEC", "ckpt_gc:mode=error")
    faults.reset()  # forget the env check so the var is re-read
    with pytest.raises(faults.InjectedFault):
        faults.fault_point("ckpt_gc")


def test_torn_file_mode_truncates_named_file(tmp_path):
    p = tmp_path / "x.bin"
    p.write_bytes(b"a" * 100)
    faults.configure("restore:mode=torn_file")
    faults.fault_point("restore", path=str(p), step=1)
    assert p.stat().st_size == 50


# ------------------------------------------------------------------ crc32c


def test_crc32c_check_value_and_numpy_path_match_scalar():
    # the CRC-32C standard check value
    assert _crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"123456789") == 0xE3069283
    rng = np.random.default_rng(0)
    for n in (0, 1, 17, 1023, 1024, 1025, 4096, 100_000):
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        want = _crc32c(data)
        assert crc32c(data) == want, n
        assert _crc32c_numpy(np.frombuffer(data, np.uint8)) == want, n


def test_crc32c_accepts_ndarrays_any_dtype():
    a = np.arange(1000, dtype=np.float32).reshape(10, 100)
    assert crc32c(a) == crc32c(a.tobytes())


# --------------------------------------------- the verified-restore ladder


def _flip_member_byte(path: str, member_suffix: str = ".npy"):
    """Flip one bit INSIDE a stored array's data region (zip padding and
    headers would shrug a random flip off — this aims at the payload)."""
    with zipfile.ZipFile(path) as z:
        info = max((i for i in z.infolist()
                    if i.filename.endswith(member_suffix)),
                   key=lambda i: i.file_size)
        with open(path, "rb") as f:
            f.seek(info.header_offset)
            hdr = f.read(30)
        name_len = int.from_bytes(hdr[26:28], "little")
        extra_len = int.from_bytes(hdr[28:30], "little")
        # past the .npy magic/header into the raw array bytes
        data_off = (info.header_offset + 30 + name_len + extra_len
                    + min(256, info.file_size - 1))
    with open(path, "r+b") as f:
        f.seek(data_off)
        b = f.read(1)
        f.seek(data_off)
        f.write(bytes([b[0] ^ 0x01]))


def _template():
    return {"params": {"w": np.zeros(512, np.float32),
                       "b": np.zeros(16, np.float32)},
            "step": np.int64(0)}


def _state(step: int, fill: float = 1.0):
    return {"params": {"w": np.full(512, fill, np.float32),
                       "b": np.full(16, fill, np.float32)},
            "step": np.int64(step)}


def test_torn_newest_monolithic_quarantines_and_falls_back(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, _state(10, 1.0), 10)
    save_checkpoint(d, _state(20, 2.0), 20)
    p = os.path.join(d, "ckpt-20.npz")
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    state, step, report = restore_with_fallback(d, _template())
    assert step == 10
    np.testing.assert_array_equal(state["params"]["w"],
                                  np.full(512, 1.0, np.float32))
    assert report.fallback_depth == 1
    assert len(report.quarantined) == 1
    assert report.quarantined[0].endswith(".corrupt")
    assert report.time_s >= 0
    # the corrupt set is invisible to selection AND still on disk
    assert latest_checkpoint(d)[1] == 10
    assert os.path.exists(p + ".corrupt") and not os.path.exists(p)


def test_bitflipped_newest_monolithic_detected_and_skipped(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, _state(10, 1.0), 10)
    save_checkpoint(d, _state(20, 2.0), 20)
    _flip_member_byte(os.path.join(d, "ckpt-20.npz"))
    state, step, report = restore_with_fallback(d, _template())
    assert step == 10 and report.fallback_depth == 1


def test_zero_length_newest_detected_and_skipped(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, _state(10), 10)
    save_checkpoint(d, _state(20), 20)
    open(os.path.join(d, "ckpt-20.npz"), "wb").close()
    _, step, report = restore_with_fallback(d, _template())
    assert step == 10 and len(report.quarantined) == 1


def test_torn_newest_sharded_set_quarantines_and_falls_back(tmp_path):
    import glob

    d = str(tmp_path)
    save_checkpoint_sharded(d, _state(10, 1.0), 10)
    save_checkpoint_sharded(d, _state(20, 2.0), 20)
    p = glob.glob(os.path.join(d, "ckpt-20.shard0-of-1*.npz"))[0]
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    state, step, report = restore_with_fallback(d, _template())
    assert step == 10 and report.fallback_depth == 1
    np.testing.assert_array_equal(state["params"]["w"],
                                  np.full(512, 1.0, np.float32))
    assert os.path.exists(p + ".corrupt")


def test_bitflipped_sharded_shard_fails_crc_and_falls_back(tmp_path):
    import glob

    d = str(tmp_path)
    save_checkpoint_sharded(d, _state(10, 1.0), 10)
    save_checkpoint_sharded(d, _state(20, 2.0), 20)
    _flip_member_byte(glob.glob(
        os.path.join(d, "ckpt-20.shard0-of-1*.npz"))[0])
    _, step, report = restore_with_fallback(d, _template())
    assert step == 10 and report.fallback_depth == 1


def test_mixed_coverage_set_quarantined(tmp_path):
    """A forged set whose entries overlap (the mixed-save-attempt
    signature load_flat_sharded detects positionally) is quarantined by
    the ladder, not a crash."""
    import glob

    d = str(tmp_path)
    save_checkpoint_sharded(d, {"w": np.arange(4.0, dtype=np.float32)},
                            step=3)
    path = save_checkpoint_sharded(
        d, {"w": np.arange(4.0, dtype=np.float32)}, step=9,
        attempt="cafecafe")
    from distributed_tensorflow_tpu.checkpoint.checkpoint import _SHARDMETA

    with np.load(path) as z:
        meta = json.loads(bytes(z[_SHARDMETA]).decode())
        arrays = {k: z[k] for k in z.files if k != _SHARDMETA}
    (e,) = meta["leaves"]["w"]["entries"]
    e2 = dict(e, npz="w@1")
    e["index"] = [[0, 2]]
    e2["index"] = [[0, 2]]
    meta["leaves"]["w"]["entries"] = [e, e2]
    arrays["w@1"] = arrays[e["npz"]][:2].copy()
    arrays[e["npz"]] = arrays[e["npz"]][:2].copy()
    meta["crc32c"] = {k: crc32c(np.ascontiguousarray(v))
                      for k, v in arrays.items()}
    arrays[_SHARDMETA] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8)
    np.savez(path, **arrays)
    _, step, report = restore_with_fallback(
        d, {"w": np.zeros(4, np.float32)})
    assert step == 3 and report.fallback_depth == 1
    assert len(report.quarantined) == 1


def test_rotted_npy_member_header_quarantined_not_loud(tmp_path):
    """Bit rot in a member's ~100-byte .npy header makes numpy raise a
    bare ValueError ('magic string is not correct') before any CRC runs
    — decode-phase ValueErrors must take the quarantine rung, not crash
    the ladder (r8 review)."""
    d = str(tmp_path)
    save_checkpoint(d, _state(10, 1.0), 10)
    save_checkpoint(d, _state(20, 2.0), 20)
    p = os.path.join(d, "ckpt-20.npz")
    with zipfile.ZipFile(p) as z:
        info = next(i for i in z.infolist() if i.filename.endswith(".npy"))
    with open(p, "r+b") as f:
        f.seek(info.header_offset)
        hdr = f.read(30)
        name_len = int.from_bytes(hdr[26:28], "little")
        extra_len = int.from_bytes(hdr[28:30], "little")
        f.seek(info.header_offset + 30 + name_len + extra_len)
        f.write(b"\x00\x00\x00\x00")  # clobber the \x93NUMPY magic
    _, step, report = restore_with_fallback(d, _template())
    assert step == 10 and report.fallback_depth == 1


def test_losing_the_quarantine_race_falls_back_not_dies(tmp_path,
                                                        monkeypatch):
    """Shared-logdir race: a PEER quarantined (or GC'd) the corrupt set
    between our failed read and our rename — quarantine_step returns []
    but the set is gone, so the ladder must fall back like the race
    winner did, not re-raise (r8 review)."""
    import distributed_tensorflow_tpu.checkpoint.checkpoint as ckpt_mod

    d = str(tmp_path)
    save_checkpoint(d, _state(10, 1.0), 10)
    save_checkpoint(d, _state(20, 2.0), 20)
    p = os.path.join(d, "ckpt-20.npz")
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)

    def peer_wins(directory, step):
        # the peer's rename lands first; ours finds nothing to move
        if os.path.exists(p):
            os.replace(p, p + ".corrupt")
        return []

    monkeypatch.setattr(ckpt_mod, "quarantine_step", peer_wins)
    _, step, report = restore_with_fallback(d, _template())
    assert step == 10
    assert report.fallback_depth == 1 and report.quarantined == ()


def test_newer_format_version_stays_loud_not_quarantined(tmp_path):
    """A shard set from a NEWER build (format version ahead of ours) is
    an intact file this build can't read — the ladder must raise, not
    quarantine a perfectly good checkpoint (r8 review)."""
    import glob

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        _SHARDMETA,
        CheckpointFormatError,
    )

    d = str(tmp_path)
    save_checkpoint_sharded(d, {"w": np.arange(4.0, dtype=np.float32)},
                            step=5)
    p = glob.glob(os.path.join(d, "ckpt-5.shard0-of-1*.npz"))[0]
    with np.load(p) as z:
        meta = json.loads(bytes(z[_SHARDMETA]).decode())
        arrays = {k: z[k] for k in z.files if k != _SHARDMETA}
    meta["version"] = 99
    arrays[_SHARDMETA] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8)
    np.savez(p, **arrays)
    with pytest.raises(CheckpointFormatError):
        restore_with_fallback(d, {"w": np.zeros(4, np.float32)})
    assert os.path.exists(p)  # untouched


def test_ladder_exhausted_raises_never_fresh_init(tmp_path):
    d = str(tmp_path)
    for s in (10, 20):
        save_checkpoint(d, _state(s), s)
        p = os.path.join(d, f"ckpt-{s}.npz")
        with open(p, "r+b") as f:
            f.truncate(os.path.getsize(p) // 2)
    with pytest.raises(CheckpointCorruptError, match="no restorable"):
        restore_with_fallback(d, _template())


def test_empty_dir_is_fresh_init_not_an_error(tmp_path):
    assert restore_with_fallback(str(tmp_path / "none"), _template()) is None


def test_structural_mismatch_stays_loud_not_quarantined(tmp_path):
    """A checkpoint that is INTACT but doesn't fit the template (wrong
    layout) must raise immediately — falling back would resurrect an old
    trajectory under a changed config — and must NOT be quarantined."""
    d = str(tmp_path)
    save_checkpoint(d, _state(10), 10)
    bad_template = {"params": {"w": np.zeros(512, np.float32),
                               "b": np.zeros(16, np.float32),
                               "extra": np.zeros(3, np.float32)},
                    "step": np.int64(0)}
    with pytest.raises(KeyError, match="extra"):
        restore_with_fallback(d, bad_template)
    assert os.path.exists(os.path.join(d, "ckpt-10.npz"))  # untouched


def test_manifestless_legacy_checkpoint_still_restores(tmp_path):
    """Pre-manifest files (older saves) restore unverified — the format
    change is backward compatible."""
    d = str(tmp_path)
    np.savez(os.path.join(d, "ckpt-5.npz"),
             **{"params/w": np.full(512, 3.0, np.float32),
                "params/b": np.full(16, 3.0, np.float32),
                "step": np.int64(5)})
    state, step = restore_latest(d, _template())
    assert step == 5
    np.testing.assert_array_equal(state["params"]["w"],
                                  np.full(512, 3.0, np.float32))
    out = restore_with_fallback(d, _template())
    assert out is not None and out[1] == 5 and out[2].fallback_depth == 0


def test_restore_injection_one_liner_drives_the_ladder(tmp_path):
    """The tentpole's point: `--fault_spec restore:mode=torn_file` is the
    whole reproduction of a torn newest checkpoint."""
    d = str(tmp_path)
    save_checkpoint(d, _state(10, 1.0), 10)
    save_checkpoint(d, _state(20, 2.0), 20)
    faults.configure("restore:mode=torn_file:times=1")
    _, step, report = restore_with_fallback(d, _template())
    assert step == 10 and report.fallback_depth == 1


def test_gc_accounting_ignores_quarantined_files(tmp_path):
    """Quarantined sets neither count toward max_to_keep nor get
    deleted."""
    from distributed_tensorflow_tpu.checkpoint.checkpoint import _gc

    d = str(tmp_path)
    for s in (1, 2, 3):
        save_checkpoint(d, _state(s), s, max_to_keep=10)
    p = os.path.join(d, "ckpt-3.npz")
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    _, step, _ = restore_with_fallback(d, _template())
    assert step == 2
    _gc(d, max_to_keep=2)
    names = set(os.listdir(d))
    assert "ckpt-3.npz.corrupt" in names  # survives GC forever
    assert "ckpt-1.npz" in names and "ckpt-2.npz" in names  # 2 kept


# ----------------------------------------------------- supervisor wiring


def test_supervisor_restores_through_the_ladder(tmp_path):
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.training import create_train_state, sgd
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    d = str(tmp_path)
    state = create_train_state(DeepCNN(), sgd(0.01), seed=0)
    save_checkpoint(d, state, 10)
    save_checkpoint(d, state, 20)
    p = os.path.join(d, "ckpt-20.npz")
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    sv = Supervisor(is_chief=True, logdir=d, save_model_secs=10_000)
    restored, step = sv.init_or_restore(state)
    assert step == 10
    rep = sv.restore_report
    assert rep is not None and rep.step == 10
    assert rep.fallback_depth == 1 and len(rep.quarantined) == 1


def test_supervisor_fresh_init_has_no_report(tmp_path):
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.training import create_train_state, sgd
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    sv = Supervisor(is_chief=True, logdir=str(tmp_path),
                    save_model_secs=10_000)
    state = create_train_state(DeepCNN(), sgd(0.01), seed=0)
    _, step = sv.init_or_restore(state)
    assert step == 0 and sv.restore_report is None


def test_exit_agreement_injection_fails_the_agreement():
    """exit_agreement:mode=error — the agreement's bounded gather fails,
    the verdict comes back None (managed() then skips the final save and
    raises the abandoned error on a clean exit): the r3 exit protocol
    exercised deterministically, single-process."""
    from distributed_tensorflow_tpu.utils.pytree import agree_clean_exit

    faults.configure("exit_agreement:mode=error")
    verdict, token = agree_clean_exit(True, timeout_s=30.0,
                                      return_token=True)
    assert verdict is None and token is None


def test_collective_fetch_injection_reports_failed_final_save(tmp_path,
                                                              capsys):
    """collective_fetch:mode=error — the exit save fails LOUDLY but the
    managed() exit still completes (best-effort final save contract)."""
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.training import create_train_state, sgd
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    state = create_train_state(DeepCNN(), sgd(0.01), seed=0)
    faults.configure("collective_fetch:mode=error")
    sv = Supervisor(is_chief=True, logdir=str(tmp_path),
                    save_model_secs=10_000)
    with sv.managed(state) as box:
        box.update(state, 3)
    assert "final checkpoint failed" in capsys.readouterr().out
    assert latest_checkpoint(str(tmp_path)) is None


def test_ckpt_write_crash_mode_hard_exits_subprocess(tmp_path):
    """ckpt_write:mode=crash is a hard os._exit(17): no final save, no
    atexit — but the file ALREADY landed (the point fires after the
    atomic rename), so a restart restores it through the index-fallback
    scan even though the index write never happened."""
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from distributed_tensorflow_tpu.utils import faults\n"
        "from distributed_tensorflow_tpu.checkpoint.checkpoint import "
        "save_checkpoint\n"
        "faults.configure('ckpt_write:at_step=7:mode=crash')\n"
        f"d = {str(tmp_path)!r}\n"
        "save_checkpoint(d, {'w': np.arange(4.0, dtype=np.float32)}, 3)\n"
        "save_checkpoint(d, {'w': np.arange(4.0, dtype=np.float32)}, 7)\n"
        "print('NOT REACHED')\n"
    )
    r = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ, "PYTHONPATH": REPO,
                            "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == faults.FAULT_EXIT_CODE, r.stdout + r.stderr
    assert "NOT REACHED" not in r.stdout
    assert os.path.exists(tmp_path / "ckpt-7.npz")
    # the index still names step 3 (the crash beat the index write) but
    # selection is scan-based, so the newer complete file wins
    found = latest_checkpoint(str(tmp_path))
    assert found is not None and found[1] == 7
    out = restore_with_fallback(str(tmp_path),
                                {"w": np.zeros(4, np.float32)})
    assert out is not None and out[1] == 7


# ------------------------------------------------------- init retry path


def test_init_retry_rides_through_injected_refusals():
    from distributed_tensorflow_tpu.cluster import _initialize_with_retry

    faults.configure("init:mode=refuse:times=2")
    calls = {"n": 0}
    sleeps = []

    def init_fn():
        calls["n"] += 1

    _initialize_with_retry(init_fn, retries=3, backoff_s=0.5,
                           what="test init", sleep=sleeps.append)
    assert calls["n"] == 1  # two injected refusals, then the real join
    assert sleeps == [0.5, 1.0]  # linear backoff


def test_init_retry_exhausts_loudly():
    from distributed_tensorflow_tpu.cluster import _initialize_with_retry

    faults.configure("init:mode=refuse:times=0")  # unlimited refusals
    with pytest.raises(faults.InjectedFault):
        _initialize_with_retry(lambda: None, retries=2, backoff_s=0.1,
                               what="test init", sleep=lambda s: None)


def test_init_retry_runs_cleanup_between_attempts():
    from distributed_tensorflow_tpu.cluster import _initialize_with_retry

    faults.configure("init:mode=refuse:times=1")
    cleaned = {"n": 0}
    _initialize_with_retry(lambda: None, retries=2, backoff_s=0.0,
                           what="test init", sleep=lambda s: None,
                           cleanup_fn=lambda: cleaned.update(
                               n=cleaned["n"] + 1))
    assert cleaned["n"] == 1


def test_maybe_initialize_skips_single_host():
    from distributed_tensorflow_tpu.cluster import (
        ClusterSpec,
        maybe_initialize_distributed,
    )

    spec = ClusterSpec({"ps": [], "worker": ["localhost:1"]})
    assert maybe_initialize_distributed(spec, 0, init_retries=5) is False


# -------------------------------------------------- bench recovery fields


def test_bench_recovery_phase_nonnull():
    import bench

    out = bench.recovery_phase()
    assert out["recovery_restore_step"] == 10
    assert out["recovery_fallback_depth"] == 1
    assert out["recovery_quarantined"] == 1
    assert out["recovery_time_s"] is not None


def test_bench_degraded_record_keeps_recovery_fields():
    import bench

    rec = bench.degraded_record("forced outage")
    assert rec["recovery_restore_step"] == 10
    assert rec["recovery_fallback_depth"] == 1
    assert rec["recovery_time_s"] is not None


# --------------------------------------------------------- inspect --verify


def test_inspect_verify_reports_and_exit_code(tmp_path):
    from distributed_tensorflow_tpu.checkpoint.inspect import (
        main as inspect_main,
        verify_logdir,
    )

    d = str(tmp_path)
    save_checkpoint(d, _state(10), 10)
    save_checkpoint_sharded(d, _state(20), 20)
    buf = io.StringIO()
    assert verify_logdir(d, out=buf) == 0
    text = buf.getvalue()
    assert "step 10 [monolithic]: ok" in text
    assert "step 20 [sharded x1]: ok" in text
    # tear the newest -> nonzero + CORRUPT line
    import glob

    p = glob.glob(os.path.join(d, "ckpt-20.shard0-of-1*.npz"))[0]
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    buf = io.StringIO()
    assert verify_logdir(d, out=buf) == 1
    text = buf.getvalue()
    assert "CORRUPT" in text and "newest restorable set" in text
    # older-set corruption alone does not fail the exit code
    os.replace(p, p + ".gone")  # leave only an orphaned... restore it
    os.replace(p + ".gone", p)
    save_checkpoint(d, _state(30), 30)
    buf = io.StringIO()
    assert verify_logdir(d, out=buf) == 0, buf.getvalue()
    # CLI surface
    assert inspect_main(["--verify", f"--logdir={d}"]) == 0


def test_inspect_verify_flags_incomplete_sets(tmp_path):
    import glob
    import shutil

    from distributed_tensorflow_tpu.checkpoint.inspect import verify_logdir

    d = str(tmp_path)
    save_checkpoint_sharded(d, _state(5), 5)
    src = glob.glob(os.path.join(d, "ckpt-5.shard0-of-1*.npz"))[0]
    shutil.copy(src, os.path.join(d, "ckpt-9.shard0-of-2.npz"))
    buf = io.StringIO()
    verify_rc = verify_logdir(d, out=buf)
    assert "step 9 [sharded]: incomplete" in buf.getvalue()
    assert verify_rc == 0  # newest RESTORABLE (step 5) is fine


def test_inspect_verify_notes_manifestless_sets(tmp_path):
    from distributed_tensorflow_tpu.checkpoint.inspect import verify_logdir

    d = str(tmp_path)
    np.savez(os.path.join(d, "ckpt-5.npz"),
             **{"w": np.arange(4.0), "step": np.int64(5)})
    buf = io.StringIO()
    assert verify_logdir(d, out=buf) == 0
    assert "ok (no manifest)" in buf.getvalue()
