"""Efficiency accounting (utils/efficiency.py): FLOPs budgets pinned
against hand arithmetic and the XLA cost-analysis cross-check, peak
resolution, goodput/MFU meters, the trace_ops --flops CLI, and the
bench efficiency phase."""

import math

import pytest

from distributed_tensorflow_tpu.models import get_model
from distributed_tensorflow_tpu.utils import efficiency
from distributed_tensorflow_tpu.utils.efficiency import (
    EfficiencyMeter,
    GoodputMeter,
    flops_budget,
    peak_flops_per_sec,
)

# ------------------------------------------------------------- budgets


def test_cnn_budget_matches_hand_arithmetic():
    """The flagship CNN's per-layer forward FLOPs, computed by hand from
    the architecture (conv 2*K*K*Cin*Cout*H*W, dense 2*M*N)."""
    m = get_model("deep_cnn", image_size=28, channels=1, num_classes=10)
    b = flops_budget(m, 128)
    expect = {
        "conv1 5x5": 2 * 5 * 5 * 1 * 32 * 28 * 28,
        "conv2 5x5": 2 * 5 * 5 * 32 * 64 * 14 * 14,
        "dense1": 2 * 3136 * 1024,
        "logits": 2 * 1024 * 10,
    }
    got = {r["layer"]: r["flops"] for r in b["rows"]}
    assert got == expect
    fwd = sum(expect.values())
    assert b["fwd_flops_per_example"] == fwd
    assert b["train_flops_per_example"] == 3 * fwd
    assert b["flops_per_step"] == 3 * fwd * 128
    assert b["source"] == "analytic"


def test_mlp_budget_exact_and_batch_scaling():
    m = get_model("mlp", image_size=28, channels=1, num_classes=10,
                  hidden_units=100)
    b1 = flops_budget(m, 1)
    assert b1["fwd_flops_per_example"] == 2 * 784 * 100 + 2 * 100 * 10
    b64 = flops_budget(m, 64)
    assert b64["flops_per_step"] == 64 * b1["flops_per_step"]


def test_lm_budget_scales_with_blocks_and_counts_head():
    mk = lambda nb: get_model("lm", vocab_size=64, seq_len=32, d_model=32,
                              num_heads=2, num_blocks=nb)
    b1, b2 = flops_budget(mk(1)), flops_budget(mk(2))
    per_block = b2["fwd_flops_per_example"] - b1["fwd_flops_per_example"]
    s, d, mlp = 32, 32, 4 * 32
    # the causal half of the score matrix, as benchmark/harness/flops.py
    # counts it: a token attends to S/2 keys on average
    assert per_block == (4 * s * 2 * d * d + 2 * (2 * s * s * d) // 2
                         + 2 * s * 2 * d * mlp)
    from benchmark.harness import flops as bench_flops

    sizes = dict(d_model=d, num_blocks=1, ffn_dim=mlp, vocab_size=64)
    assert b1["train_flops_per_example"] == pytest.approx(
        s * bench_flops.train_flops_per_token(sizes, s))
    head = [r for r in b1["rows"] if r["layer"] == "lm_head"]
    assert head and head[0]["flops"] == s * 2 * d * 64


def test_resnet_and_transformer_budgets_positive():
    for name, kw in (("resnet20", dict(image_size=32, channels=3,
                                       num_classes=10)),
                     ("transformer", dict(image_size=28, channels=1,
                                          num_classes=10, d_model=32,
                                          num_heads=2, num_blocks=2))):
        b = flops_budget(get_model(name, **kw))
        assert b["fwd_flops_per_example"] > 0
        assert all(r["flops"] > 0 for r in b["rows"])


def test_unknown_model_raises():
    class Exotic:
        pass

    with pytest.raises(ValueError, match="no analytic FLOPs rule"):
        flops_budget(Exotic())
    with pytest.raises(ValueError, match="batch_size"):
        flops_budget(get_model("mlp", image_size=28, channels=1,
                               num_classes=10), 0)


def test_xla_cost_analysis_cross_check_in_band():
    """The dual pattern's measured half: where the backend reports
    FLOPs, the cost-analysis total must land in the same decade as the
    analytic budget (XLA fuses/simplifies, so equality is not expected
    — a 2x band catches unit errors like fwd-only vs fwd+bwd)."""
    m = get_model("deep_cnn", image_size=28, channels=1, num_classes=10)
    b = flops_budget(m, 8, xla=True)
    if b["xla_flops_per_step"] is None:
        pytest.skip("backend reports no cost-analysis FLOPs")
    ratio = b["xla_flops_per_step"] / b["flops_per_step"]
    assert 0.5 <= ratio <= 2.0, ratio
    assert b["source"] == "analytic+xla_cost_analysis"


# ---------------------------------------------------------------- peak


def test_peak_resolution_and_cache():
    efficiency._reset_peak_cache()
    peak, src = peak_flops_per_sec()
    assert peak > 0
    assert src == "matmul_calibration" or src.startswith("device_table")
    peak2, src2 = peak_flops_per_sec()  # cached: same answer
    assert (peak2, src2) == (peak, src)
    po, so = peak_flops_per_sec(override=123.0)
    assert po == 123.0 and so == "flag_override"


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", 197e12),  # the string a v5e chip reports
    ("TPU v4", 275e12),
])
def test_peak_resolves_the_exact_device_kind(monkeypatch, kind, want):
    import jax

    efficiency._reset_peak_cache()
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", kind)])
    assert peak_flops_per_sec() == (want, f"device_table:{kind}")
    efficiency._reset_peak_cache()


def test_unknown_tpu_kind_raises_instead_of_guessing(monkeypatch):
    """A TPU the table does not know is an error: no calibration, no
    floor — an MFU over a guessed peak would read as a measurement."""
    import jax

    efficiency._reset_peak_cache()
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", "TPU v9 ultra")])
    with pytest.raises(ValueError, match="TPU v9 ultra"):
        peak_flops_per_sec()
    efficiency._reset_peak_cache()


# -------------------------------------------------------------- meters


def test_goodput_meter_arithmetic():
    g = GoodputMeter()
    g.charge(0.5, "ckpt")
    g.charge(0.25, "eval")
    g.charge(-1.0, "eval")  # negative clamps to 0, never credits back
    assert g.lost_s == pytest.approx(0.75)
    assert g.by_kind() == {"ckpt": 0.5, "eval": 0.25}
    s = g.scalars()
    assert 0.0 <= s["goodput"] <= 1.0
    assert s["goodput_lost_s"] == pytest.approx(0.75)


def test_efficiency_meter_scalars():
    m = get_model("deep_cnn", image_size=28, channels=1, num_classes=10)
    eff = EfficiencyMeter(m, 128, 2, peak_override=1e12)
    assert eff.peak_flops_total == 2e12  # per-chip peak x chips
    s = eff.scalars(1000.0)  # 1000 examples/sec
    assert s["model_flops_per_sec"] == pytest.approx(
        1000.0 * eff.train_flops_per_example)
    assert s["mfu"] == pytest.approx(
        1000.0 * eff.train_flops_per_example / 2e12, rel=1e-4)
    assert 0.0 <= s["goodput"] <= 1.0
    assert math.isfinite(s["goodput_lost_s"])


def test_meter_from_flags_gates():
    class F:
        mfu = False
        mfu_peak_flops = 0.0

    m = get_model("mlp", image_size=28, channels=1, num_classes=10)
    assert efficiency.meter_from_flags(F(), m, 32, 1) is None

    class F2:
        mfu = True
        mfu_peak_flops = 1e12

    class Exotic:
        pass

    # unknown model: accounting declines quietly, training must proceed
    assert efficiency.meter_from_flags(F2(), Exotic(), 32, 1) is None
    eff = efficiency.meter_from_flags(F2(), m, 32, 4)
    assert eff is not None and eff.peak_flops_total == 4e12


# ------------------------------------------------------ CLI and bench


def test_trace_ops_flops_printer(capsys):
    from tools import trace_ops

    trace_ops.print_flops("deep_cnn", 64)
    out = capsys.readouterr().out
    assert "conv2 5x5" in out and "dense1" in out
    assert "train FLOPs/step at batch 64" in out
    assert f"{3 * 27767808 * 64:,}" in out  # the hand-pinned total
    with pytest.raises(SystemExit, match="unknown model"):
        trace_ops.print_flops("nope", 1)


def test_bench_efficiency_phase_fields():
    import bench

    out = bench.efficiency_phase()
    assert out.get("efficiency_error") is None, out
    # the CPU mesh's peak is a matmul's achieved rate, not a bound: the
    # conv step beats it, so only a unit error is ruled out here
    assert out["mfu_peak_source"] == "matmul_calibration"
    assert 0.0 < out["mfu"] <= bench.EFFICIENCY_CALIBRATED_MFU_LIMIT
    assert 0.0 < out["goodput"] <= 1.0
    assert out["flops_per_step"] == 3 * 27767808 * bench.EFFICIENCY_BATCH
    assert out["model_flops_per_sec"] > 0
    assert out["mfu_peak_flops_per_sec"] > 0
    assert out["mfu_peak_source"]
