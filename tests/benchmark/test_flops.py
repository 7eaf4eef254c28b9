"""The FLOP count against figures worked by hand for both configurations."""

import pytest

from benchmark.harness import flops, manifest

OPT_1_3B_8 = dict(d_model=2048, ffn_dim=8192, num_blocks=8, vocab_size=50272, seq_len=2048)
OPT_125M = dict(d_model=768, ffn_dim=3072, num_blocks=12, vocab_size=50272, seq_len=2048)


def test_opt_1_3b_at_eight_blocks():
    # a block: 4 d^2 + 2 d ffn = 16.78 M + 33.55 M = 50.33 M; head 102.96 M
    assert flops.matmul_params(OPT_1_3B_8) == 8 * 50_331_648 + 102_957_056 == 505_610_240
    # 6 x 505.6 M + 6 x 8 x 2048 x 2048 = 3.0337 G + 0.2013 G
    assert flops.train_flops_per_token(OPT_1_3B_8, 2048) == pytest.approx(3.235e9, rel=1e-3)
    # token table 102.96 M, positions 4.19 M, head 102.96 M + 50,272, blocks 8 x 50.36 M
    assert flops.total_params(OPT_1_3B_8) == 612_963_424
    assert flops.state_bytes(OPT_1_3B_8) == 12 * 612_963_424


def test_opt_125m_whole():
    # a block: 4 x 768^2 + 2 x 768 x 3072 = 7.078 M; head 38.61 M
    assert flops.matmul_params(OPT_125M) == 12 * 7_077_888 + 38_608_896 == 123_543_552
    # 6 x 123.5 M + 6 x 12 x 768 x 2048 = 0.7413 G + 0.1132 G
    assert flops.train_flops_per_token(OPT_125M, 2048) == pytest.approx(0.8545e9, rel=1e-3)
    assert flops.total_params(OPT_125M) == 163_860_064


def test_attention_is_the_causal_half_and_nothing_is_counted_twice():
    full_square = 12.0 * OPT_125M["num_blocks"] * OPT_125M["d_model"] * 2048
    assert flops.attention_flops_per_token(OPT_125M, 2048) == full_square / 2
    assert flops.matmul_flops_per_token(OPT_125M) + flops.attention_flops_per_token(OPT_125M, 2048) \
        == flops.train_flops_per_token(OPT_125M, 2048)


@pytest.mark.parametrize("cell_name", [w["name"] for w in manifest.load_manifest()["workloads"]])
def test_configuration_files_carry_the_reckoned_bytes(cell_name):
    cell = manifest.load_cell(cell_name)
    assert cell.config["bytes"]["parameters"] == flops.total_params(cell.sizes)
    assert cell.config["bytes"]["state_f32_master_m_v"] == flops.state_bytes(cell.sizes)
