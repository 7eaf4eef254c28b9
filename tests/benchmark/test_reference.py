"""The plain reference against the program at a tiny size in float32, and
the control and the comparison on top of it.

The program's pieces are driven as the trainer drives them (one compiled
device-resident step, one state, batches sampled on the device); the
reference shares no code with them and meets them only in the numbers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import compare, probe
from benchmark.reference import opt_lm

SIZES = dict(d_model=32, num_heads=2, num_blocks=2, ffn_dim=128,
             vocab_size=300, seq_len=64)
BATCH, LR = 4, 1e-3


def program_first_steps(seed, compute_dtype=None, shards=1):
    from distributed_tensorflow_tpu.data.device_data import put_device_data
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.models.transformer import TransformerLM
    from distributed_tensorflow_tpu.training import adam, create_train_state
    from distributed_tensorflow_tpu.training.device_step import (
        make_device_dp_train_step,
        make_device_train_step,
    )

    model = TransformerLM(vocab_size=300, seq_len=64, d_model=32, num_heads=2,
                          num_blocks=2, compute_dtype=compute_dtype,
                          attn_block=16, remat=True, ce_block=16)
    opt = adam(LR)
    state = create_train_state(model, opt, seed=seed)
    split = LMDataSet(opt_lm.LM_TRAIN_SEQUENCES, 64, 300, seed=seed)
    if shards == 1:
        data = put_device_data(split)
        fn = make_device_train_step(model, opt, BATCH, keep_prob=1.0, chunk=1)
    else:
        from distributed_tensorflow_tpu.parallel import make_mesh
        from distributed_tensorflow_tpu.parallel.data_parallel import replicate_state

        mesh = make_mesh(devices=jax.devices()[:shards])
        state = replicate_state(mesh, state)
        data = put_device_data(split, mesh)
        fn = make_device_dp_train_step(model, opt, mesh, BATCH * shards,
                                       keep_prob=1.0, chunk=1)
    p = probe.FirstStepsProbe(opt_lm.leaf_names)
    call = p.wrap(fn)
    for _ in range(4):
        state, _ = call(state, data)
    assert p.done.is_set() and p.calls == 3
    return p.result, p.first_gradient()


def reference_first_steps(seed, shards=1, **kw):
    batches = opt_lm.first_batches(seed, 3, SIZES, BATCH, shards)
    return opt_lm.first_steps(seed, SIZES, batches, LR, **kw)


def numbers(program, reference):
    return {k: v for k, (v, _) in compare.training_numbers(program, reference).items()}


def against(reference, other):
    """``reference`` with the differences that ``other`` measured from it."""
    return dict(reference, grad_differences=other["grad_differences"])


@pytest.fixture(scope="module")
def sound():
    seed = 3000000019  # more than 32 signed bits hold
    program, gradient = program_first_steps(seed)
    reference = reference_first_steps(seed, first_gradient_of_other=gradient,
                                      keep_first_gradient=True)
    own = reference.pop("first_gradient")
    return (program, reference,
            reference_first_steps(seed, precision="fp8", first_gradient_of_other=own),
            reference_first_steps(seed, keep_rows=[0, 1], first_gradient_of_other=own),
            own)


def test_reference_meets_the_program_in_float32(sound):
    program, reference, _, _, _ = sound
    n = numbers(program, reference)
    assert max(n[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 1e-6
    assert n["grad_norm_gap"] < 1e-5
    assert n["grad_difference_median"] < 1e-5
    assert n["change_norm_gap"] < 1e-4
    assert len(program["grad_norms"]) == 4 + 2 + 10 * SIZES["num_blocks"]


def test_the_token_rows_are_the_programs(sound):
    from distributed_tensorflow_tpu.data.lm import LMDataSet

    split = LMDataSet(opt_lm.LM_TRAIN_SEQUENCES, 64, 300, seed=11)
    rows = opt_lm.token_rows(11, [0, 255, 256, 4095], 64, 300)
    for r, walk in rows.items():
        assert np.array_equal(walk, split._tokens[r].astype(np.int64))


def test_bfloat16_program_is_sound_and_the_control_is_not(sound):
    _, reference, control, _, own = sound
    bf16, gradient = program_first_steps(3000000019, compute_dtype=jnp.bfloat16)
    bf16_reference = dict(reference, grad_differences=opt_lm.leaf_differences(
        jax.tree.unflatten(jax.tree.structure(opt_lm.init_params(0, SIZES)),
                           [jnp.asarray(g) for g in own]), gradient))
    sound_numbers = numbers(bf16, bf16_reference)
    control_numbers = numbers(control, against(reference, control))
    # the step below bfloat16 reads several times what bfloat16 reads
    assert control_numbers["grad_difference_median"] > 3 * sound_numbers["grad_difference_median"]
    limits = {"loss_gap_step1": 5e-3, "loss_gap_step2": 5e-3, "loss_gap_step3": 5e-3,
              "grad_norm_gap": 0.1, "change_norm_gap": 0.2,
              "grad_difference_median": 1.7 * sound_numbers["grad_difference_median"]}
    assert compare.judge(compare.training_numbers(bf16, bf16_reference), limits)[0]
    ok, checks = compare.judge(compare.training_numbers(control, against(reference, control)), limits)
    assert not ok and not checks["grad_difference_median"]["ok"]


def test_half_of_the_batch_left_out_is_not_correct(sound):
    _, reference, _, half, _ = sound
    n = numbers(half, against(reference, half))
    assert n["grad_norm_gap"] > 0.05 and n["grad_difference_median"] > 0.2


def test_a_state_left_unchanged_reads_one(sound):
    program, reference, _, _, _ = sound
    stuck = dict(program, change_norms={k: 0.0 for k in program["change_norms"]})
    numbers = compare.training_numbers(stuck, reference)
    assert numbers["change_norm_gap"][0] == pytest.approx(1.0)


def test_a_number_without_a_limit_or_not_finite_is_not_correct(sound):
    program, reference, _, _, _ = sound
    found = compare.training_numbers(program, reference)
    assert not compare.judge(found, {"loss_gap_step1": 1.0})[0]
    broken = dict(program, losses=[float("nan")] + program["losses"][1:])
    limits = {k: 10.0 for k in found}
    assert compare.judge(found, limits)[0]
    # null: named as not compared in this cell, printed all the same
    ok, checks = compare.judge(found, dict(limits, loss_gap_step2=None))
    assert ok and checks["loss_gap_step2"]["limit"] is None
    assert checks["loss_gap_step2"]["detail"].startswith("not compared")
    assert not compare.judge(compare.training_numbers(broken, reference), limits)[0]


def test_across_shards_the_rows_of_every_shard_are_followed():
    seed = 5
    program, gradient = program_first_steps(seed, shards=4)
    reference = reference_first_steps(seed, shards=4, first_gradient_of_other=gradient)
    n = numbers(program, reference)
    assert max(n[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 1e-6
    assert n["grad_norm_gap"] < 1e-5 and n["grad_difference_median"] < 1e-5
    one_shard = reference_first_steps(seed, shards=4, keep_rows=list(range(BATCH)))
    assert numbers(one_shard, reference)["grad_norm_gap"] > 0.05
