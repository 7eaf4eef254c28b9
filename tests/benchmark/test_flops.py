"""Each family's count against figures worked by hand: both configurations
of the benchmark through ``opt_lm``, and the tests' second family at d 32."""

import pytest

from benchmark.harness import manifest
from tests.benchmark import tiny

OPT_1_3B_8 = dict(d_model=2048, ffn_dim=8192, num_blocks=8, vocab_size=50272, seq_len=2048)
OPT_125M = dict(d_model=768, ffn_dim=3072, num_blocks=12, vocab_size=50272, seq_len=2048)


@pytest.fixture(scope="module")
def opt():
    return manifest.load_cell("opt-125m.train-s2048").family()


def matmul_params(family, sizes):
    """Parameters that multiply every token: 6 operations each."""
    parts = family.scope_flops_per_token(sizes)
    return (parts["attn_proj"] + parts["mlp"] + parts["lm_head"]) / 6


def test_opt_1_3b_at_eight_blocks(opt):
    # a block: 4 d^2 + 2 d ffn = 16.78 M + 33.55 M = 50.33 M; head 102.96 M
    assert matmul_params(opt, OPT_1_3B_8) == 8 * 50_331_648 + 102_957_056 == 505_610_240
    # 6 x 505.6 M + 6 x 8 x 2048 x 2048 = 3.0337 G + 0.2013 G
    assert opt.train_flops_per_token(OPT_1_3B_8) == 3_234_988_032
    assert opt.train_flops_per_token(OPT_1_3B_8) == pytest.approx(3.235e9, rel=1e-3)
    # token table 102.96 M, positions 4.19 M, head 102.96 M + 50,272, blocks 8 x 50.36 M
    assert opt.total_params(OPT_1_3B_8) == 612_963_424
    assert opt.state_bytes(OPT_1_3B_8) == 12 * 612_963_424
    assert opt.adam_bytes_per_step(OPT_1_3B_8) == 28 * 612_963_424
    assert opt.allreduce_bytes_per_step(OPT_1_3B_8) == 4 * 612_963_424


def test_opt_125m_whole(opt):
    # a block: 4 x 768^2 + 2 x 768 x 3072 = 7.078 M; head 38.61 M
    assert matmul_params(opt, OPT_125M) == 12 * 7_077_888 + 38_608_896 == 123_543_552
    # 6 x 123.5 M + 6 x 12 x 768 x 2048 = 0.7413 G + 0.1132 G
    assert opt.train_flops_per_token(OPT_125M) == 854_507_520
    assert opt.train_flops_per_token(OPT_125M) == pytest.approx(0.8545e9, rel=1e-3)
    assert opt.total_params(OPT_125M) == 163_860_064


def test_attention_is_the_causal_half_and_nothing_is_counted_twice(opt):
    full_square = 12.0 * OPT_125M["num_blocks"] * OPT_125M["d_model"] * 2048
    parts = opt.scope_flops_per_token(OPT_125M)
    assert parts["attention"] == full_square / 2
    # the parts are the program's scopes and add up to the whole
    assert set(parts) == {"attn_proj", "attention", "mlp", "lm_head"}
    assert sum(parts.values()) == opt.train_flops_per_token(OPT_125M)
    assert 6 * matmul_params(opt, OPT_125M) + parts["attention"] \
        == opt.train_flops_per_token(OPT_125M)


@pytest.mark.parametrize("cell_name", [w["name"] for w in manifest.load_manifest()["workloads"]])
def test_configuration_files_carry_the_reckoned_bytes(cell_name):
    cell = manifest.load_cell(cell_name)
    family = cell.family()
    assert cell.config["bytes"]["parameters"] == family.total_params(cell.sizes)
    assert cell.config["bytes"]["state_f32_master_m_v"] == family.state_bytes(cell.sizes)


def test_the_count_kept_for_the_programs_own_test_is_the_familys(opt):
    """``tests/test_efficiency.py`` still asks ``harness/flops.py``."""
    from benchmark.harness import flops

    sizes = {k: v for k, v in OPT_125M.items() if k != "seq_len"}
    assert flops.train_flops_per_token(sizes, 2048) == opt.train_flops_per_token(OPT_125M)


def test_the_second_familys_count_against_a_hand_count_at_d_32(tmp_path):
    root = tiny.add_switch_family(tiny.make_root(str(tmp_path / "root")))
    cell = manifest.load_cell(tiny.SWITCH_CELL, root)
    switch, sizes = cell.family(), cell.sizes
    # d 32, 2 blocks, 4 experts of 128, 300 tokens, 64 positions. A token
    # multiplies, in a block: q, k, v, o 4 x 32^2 = 4,096; the router 32 x 4
    # = 128; ONE expert's two matrices 2 x 32 x 128 = 8,192; and the head
    # 32 x 300 = 9,600; attention over the causal half 6 x 2 x 32 x 64
    assert switch.scope_flops_per_token(sizes) == {
        "attn_proj": 6 * 2 * 4_096, "attention": 24_576,
        "mlp": 6 * 2 * (8_192 + 128), "lm_head": 6 * 9_600}
    assert switch.train_flops_per_token(sizes) == 231_168
    # a block holds ALL experts: 4 x (2 x 4,096 + 128 + 32) + router 128 +
    # attention 4,096 + LayerNorms 128 = 37,760; tables 9,600 + 2,048; final
    # LayerNorm 64; head 9,600 + 300
    assert switch.total_params(sizes) == 2 * 37_760 + 11_648 + 64 + 9_900 == 97_132
    assert switch.state_bytes(sizes) == 12 * 97_132
    # the dense family at the same widths counts the same but for the router
    dense = manifest.load_cell(tiny.CELL, root)
    assert switch.train_flops_per_token(sizes) - \
        dense.family().train_flops_per_token(dense.sizes) == 6 * 2 * 128
