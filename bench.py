#!/usr/bin/env python
"""Benchmark: MNIST images/sec/chip + time-to-accuracy on the flagship CNN.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Phase 1 — throughput (the headline `value`): DEVICE-RESIDENT training.
The train split (60k x 784 uint8 ≈ 47 MB) is staged into HBM once; every
step samples its batch on device from the step PRNG and `lax.scan` runs
CHUNK steps per dispatch (training/device_step.py). Per-step host↔device
traffic is zero, so the number measures the compiled step itself, not the
host's input path. bf16 compute, f32 master params, adam.

Phase 2 — thin-wire throughput (reported as
"wire_images_per_sec_per_chip"): the host-fed fast path users get without
--device_data — uint8+int32 batches through the prefetch-to-device queue,
normalized on device. This is the bandwidth-bound figure.

Phase 3 — convergence (the BASELINE north star's accuracy half): fresh
params, device-resident stepping, eval on the device-resident test split
until test accuracy >= 99% (budget-capped); reports accuracy, wall-clock
seconds and steps to target. Real MNIST IDX files when present in
/tmp/mnist-data, else the procedural set ("data_source" says which).

Phase 3b — Fashion-MNIST convergence (BASELINE config 3): the same
drop-in loader pointed at /tmp/fashion-mnist-data (dataset swap parity,
MNISTDist.py:167), trained to 85% test accuracy with the same
device-resident recipe; "fashion_*" fields, "fashion_data_source" labels
real-IDX vs procedural.

Phase 5 — ResNet-20 on CIFAR-10 (BASELINE config 4): device-resident
throughput of the batch-norm model, reported as
"resnet20_cifar10_images_per_sec_per_chip" (real CIFAR pickles from
/tmp/cifar10-data when present, else the procedural set —
"resnet_data_source" says which).

Phase 6 (runs last) — async PS emulation (BASELINE config 5): one ps task
+ one worker on localhost (in-process server thread, TCP loopback), the
reference's pull/compute/push cycle at batch 128, reported as
"ps_emulation_images_per_sec". This measures the stale-gradient topology's
end-to-end cycle including the full parameter transfer each step — the
cost structure the sync/device modes exist to eliminate (SURVEY.md §3.4).

Phase 4 — measured same-machine baseline
("feeddict_images_per_sec_per_chip"): a direct transplant of the
reference's training configuration onto this chip — per-step synchronous
upload of an f32-pixel + one-hot-f32 batch of 128 (the feed_dict pattern,
MNISTDist.py:179,188), no prefetch, f32 compute, same compiled XLA step
otherwise. "vs_feeddict" = value / that number: the measured END-TO-END
speedup of this build's fast path over that transplant on identical
hardware. It bundles every deliberate design delta — device-resident
input AND the larger per-chip batch (1536 vs 128) AND bf16 compute — not
the input path alone (PERF.md separates the contributions).

vs_baseline: the reference publishes no numbers (BASELINE.md), so the
denominator is the throughput its own defaults *imply* for the north-star
target — 10,000 iterations x batch 128 in <60 s on a v4-8 (8 chips) =>
128*10000/60/8 ~= 2,667 images/sec/chip. value/2667 > 1 means this build
clears the reference's implied per-chip rate.
"""

import contextlib
import json
import time

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def _prng(impl: str):
    """Scope the default PRNG impl (keys created inside keep it)."""
    prev = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", impl)
    try:
        yield
    finally:
        jax.config.update("jax_default_prng_impl", prev)

IMPLIED_BASELINE_IMAGES_PER_SEC_PER_CHIP = 128 * 10_000 / 60.0 / 8

PER_CHIP_BATCH = 2048  # measured sweet spot (PERF.md sweep: beats 1536 by ~5-9%)
CHUNK = 50          # scan length per dispatch in the device-resident phases
TIMED_CHUNKS = 8    # 8 x 50 = 400 timed steps

# thin-wire phase: one staged batch is 1536 x 788 B ~= 1.2 MB (sized on
# an earlier installation; the benchmark PR re-sizes it)
WIRE_BATCH = 1536
WIRE_TIMED_STEPS = 150

TARGET_ACC = 0.99
FASHION_TARGET_ACC = 0.85  # the classic achievable bar for this CNN
FASHION_MAX_STEPS = 3000
CONVERGE_BATCH = 128
CONVERGE_LR = 1e-3
CONVERGE_MAX_STEPS = 5000
CONVERGE_EVAL_EVERY = 50

FEEDDICT_BATCH = 128  # the reference's default batch (MNISTDist.py:28)
FEEDDICT_STEPS = 30

# long-context LM phase: the blockwise-flash production step at 4k
# tokens (the config the round-4 sweep measured at ~290-310k tok/s and
# 1.2 GB compiler temp; dense compile-fails at 2x this length)
LM_SEQ_LEN = 4096
LM_BATCH = 8
LM_D_MODEL = 256
LM_ATTN_BLOCK = 512
LM_TIMED_STEPS = 20

# large-vocab long-context phase (r5): V=32k x S=8k through the
# STREAMED loss head (--ce_block custom VJP). The unstreamed head's
# logits+grad alone would be 2 x B*S*V*4 = 8 GB f32 at this config —
# past the chip; streamed, the loss peaks at O(ce_block * V).
LM_BIGV_VOCAB = 32768
LM_BIGV_SEQ_LEN = 8192
LM_BIGV_BATCH = 4
LM_BIGV_CE_BLOCK = 512
LM_BIGV_TIMED_STEPS = 10

# PP/EP device-resident phases (r6): the two newest parallel modes
# composed with the headline input path — split resident in HBM, batch
# sampled on device inside shard_map, lax.scan chunking. Needs a model
# axis: skipped (null fields) on a 1-chip machine; the 2-way split is
# the fallback so a v4-8's 4-way axis and a 2-chip donor both measure.
PP_EP_SEQ_LEN = 128
PP_EP_VOCAB = 64
PP_EP_D_MODEL = 128
PP_EP_NUM_BLOCKS = 4
PP_EP_SPLIT = 2048           # resident sequences staged per phase
PP_EP_BATCH_PER_DATA_WAY = 16
PP_EP_CHUNK = 10
PP_EP_TIMED_CHUNKS = 3
PP_EP_EXPERTS = 8

# r7: the PP phase A/Bs the GPipe schedule against the interleaved
# virtual-stage schedule (--virtual_stages, parallel/pp_schedule.py) in
# the same session — 8 blocks so V=2 groups exist for both a 2- and a
# 4-way stage axis (V*K must divide the block count). The schedule
# facts (pp_schedule / pp_virtual_stages / pp_useful_tick_fraction) are
# ANALYTIC: they need no chip and ride every record.
PP_NUM_BLOCKS = 8
PP_VIRTUAL_STAGES = 2


def _sync_every(n_chips: int) -> int:
    """In-flight collective-program cap (see utils.collective_sync_cadence
    / PERF.md); only multi-device programs rendezvous."""
    from distributed_tensorflow_tpu.utils import collective_sync_cadence

    return collective_sync_cadence(n_chips > 1)


def _mesh_or_none(n_chips):
    if n_chips <= 1:
        return None
    from distributed_tensorflow_tpu.parallel import make_mesh

    return make_mesh()


def _build(model, opt, n_chips, fresh_only: bool = False):
    """(state, step_fn, sharding-or-None) for 1 chip or the local mesh.

    ``fresh_only=True`` returns a fresh state (and None fns) without
    building new jitted functions — used to reset params while keeping
    already-compiled executables warm."""
    from distributed_tensorflow_tpu.parallel import (
        make_dp_train_step,
        make_mesh,
        shard_batch,
    )
    from distributed_tensorflow_tpu.parallel.data_parallel import replicate_state
    from distributed_tensorflow_tpu.training import create_train_state, make_train_step

    if n_chips > 1:
        mesh = make_mesh()
        state = replicate_state(mesh, create_train_state(model, opt, seed=0))
        if fresh_only:
            return state, None, None
        step_fn = make_dp_train_step(model, opt, mesh, keep_prob=0.75)
        stage = lambda b: shard_batch(mesh, b)  # per-array data-axis layout
    else:
        state = create_train_state(model, opt, seed=0)
        if fresh_only:
            return state, None, None
        step_fn = make_train_step(model, opt, keep_prob=0.75)
        stage = None
    return state, step_fn, stage


def _device_chunk_fn(model, opt, mesh, batch_size, chunk):
    from distributed_tensorflow_tpu.training.device_step import (
        make_device_dp_train_step,
        make_device_train_step,
    )

    # donate: rebinding state every call lets XLA reuse the buffers
    # (measured ~9% on the headline phase, PERF.md)
    if mesh is not None:
        return make_device_dp_train_step(
            model, opt, mesh, batch_size, keep_prob=0.75, chunk=chunk)
    return make_device_train_step(
        model, opt, batch_size, keep_prob=0.75, chunk=chunk)


def _timed_device_phase(ds, n_chips, model, opt, per_chip_batch: int,
                        timed_chunks: int, chunk: int) -> float:
    """Shared recipe for the device-resident timed phases: stage the split,
    compile + hard-readback warmup, then time ``timed_chunks`` scan chunks
    with the CPU collective-depth cap. Returns images/sec/chip."""
    from distributed_tensorflow_tpu.data.device_data import put_device_data
    from distributed_tensorflow_tpu.parallel.data_parallel import replicate_state
    from distributed_tensorflow_tpu.training import create_train_state

    batch_size = per_chip_batch * n_chips
    mesh = _mesh_or_none(n_chips)
    data = put_device_data(ds.train, mesh)
    state = create_train_state(model, opt, seed=0)
    if mesh is not None:
        state = replicate_state(mesh, state)
    chunk_fn = _device_chunk_fn(model, opt, mesh, batch_size, chunk)

    state, m = chunk_fn(state, data)  # compile + program/weights upload
    float(m["loss"])  # hard readback so the clock starts clean

    sync_every = _sync_every(n_chips)
    t0 = time.perf_counter()
    for c in range(1, timed_chunks + 1):
        state, m = chunk_fn(state, data)
        if sync_every and (c * chunk) % sync_every < chunk:
            jax.block_until_ready(state.params)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    return timed_chunks * chunk * batch_size / dt / n_chips


def device_resident_phase(ds, n_chips) -> float:
    """Headline: images/sec/chip with the split resident in HBM and zero
    per-step host traffic."""
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.training import adam

    return _timed_device_phase(ds, n_chips, DeepCNN(compute_dtype=jnp.bfloat16),
                               adam(1e-3), PER_CHIP_BATCH, TIMED_CHUNKS, CHUNK)


def throughput_phase(ds, n_chips) -> float:
    """Thin-wire host-fed path: uint8+int32 through the prefetch queue."""
    from distributed_tensorflow_tpu.data.pipeline import batch_iterator, prefetch_to_device
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.training import adam

    batch_size = WIRE_BATCH * n_chips
    model = DeepCNN(compute_dtype=jnp.bfloat16)
    state, step_fn, stage = _build(model, adam(1e-3), n_chips)

    it = prefetch_to_device(
        batch_iterator(ds.train, batch_size, raw=True), size=4, stage=stage
    )
    state, _ = step_fn(state, next(it))  # warmup (compile)
    jax.block_until_ready(state.params)

    sync_every = _sync_every(n_chips)
    t0 = time.perf_counter()
    for s in range(1, WIRE_TIMED_STEPS + 1):
        state, _ = step_fn(state, next(it))
        if sync_every and s % sync_every == 0:
            jax.block_until_ready(state.params)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    it.close()
    return WIRE_TIMED_STEPS * batch_size / dt / n_chips


RESNET_PER_CHIP_BATCH = 512  # measured sweet spot: ~2.5x the 256 rate,
                             # ~tied with 1024 at half the step latency
RESNET_TIMED_CHUNKS = 4
RESNET_CHUNK = 50  # r4 trace discipline: chunk=10 left ~1.1 ms/step of
                   # dispatch amortization on the table (107.7k -> 140.5k
                   # img/s same-session at chunk=50; PERF.md ResNet section)


def resnet_phase(n_chips, data_dir: str = "/tmp/cifar10-data") -> tuple[float, str]:
    """BASELINE config 4: ResNet-20 on CIFAR-10 images/sec/chip (stresses
    XLA conv fusion + batch-norm state threading). Device-resident input,
    same recipe as the headline phase; real CIFAR pickles when present in
    ``data_dir``, the procedural fallback otherwise. Returns
    (rate, data_source)."""
    from distributed_tensorflow_tpu.data import read_data_sets
    from distributed_tensorflow_tpu.models import ResNet20
    from distributed_tensorflow_tpu.training import get_optimizer

    ds = read_data_sets(data_dir, one_hot=True, dataset="cifar10")
    rate = _timed_device_phase(
        ds, n_chips, ResNet20(compute_dtype=jnp.bfloat16),
        get_optimizer("momentum", 0.1), RESNET_PER_CHIP_BATCH,
        RESNET_TIMED_CHUNKS, RESNET_CHUNK)
    return rate, ds.source


PS_BATCH = 128
PS_STEPS = 30


def ps_emulation_phase(ds, wire: str = "f32") -> float:
    """BASELINE config 5: the async parameter-server topology's cycle rate
    (images/sec for ONE worker), running the product's DEFAULT sgd cycle
    (--ps_mirror): params device-resident, grads pushed to the ps (which
    applies ApplyGradientDescent parity), the identical sgd update applied
    to the on-chip mirror, and the grad download+push software-pipelined
    one step behind the chip (parallel/ps_emulation._mirror_train_loop —
    trajectory-exact vs the serial pull cycle, tested). ``wire='bf16'``
    additionally moves every tensor at half width over BOTH the TCP wire
    and the host<->chip link (--ps_wire=bf16). Same-session A/B and the
    cycle-segment profile live in PERF.md."""
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.parallel.ps_emulation import (
        MirrorCycle,
        PSClient,
        PSServer,
        assign_shards,
        bf16_template,
        flatten_params,
        make_grad_fn,
    )

    server = PSServer(0, "127.0.0.1:0")
    server.start_background()
    client = PSClient([server.address], wire=wire)
    try:
        model = DeepCNN()
        template = model.init(jax.random.PRNGKey(0))
        flat = flatten_params(template)
        assignment = assign_shards(list(flat), 1)
        client.init_params(flat, assignment, optimizer="sgd",
                           learning_rate=0.01)
        grad_fn = make_grad_fn(model, keep_prob=0.75,
                               devices=jax.devices()[:1], wire=wire)
        compute_template = (bf16_template(template) if wire == "bf16"
                            else template)

        # the PRODUCT's cycle object (run_worker drives the same class);
        # resync cadence set beyond the phase so the steady-state
        # zero-param-transfer cycle is what the clock sees
        cyc = MirrorCycle(client, grad_fn, compute_template, assignment,
                          learning_rate=0.01, resync_steps=10**9)
        cyc.maybe_sync()  # initial pull + upload
        rng = jax.random.PRNGKey(1)

        def cycle(i):
            cyc.run_cycle(ds.train.next_batch(PS_BATCH),
                          jax.random.fold_in(rng, i))

        cycle(10**6)  # warmup: compile + first program upload
        t0 = time.perf_counter()
        for i in range(PS_STEPS):
            cycle(i)
        dt = time.perf_counter() - t0
        return PS_STEPS * PS_BATCH / dt
    finally:
        client.close()
        server.close()


def _lm_phase(vocab: int, seq_len: int, batch: int, steps: int, *,
              ce_block: int | None, prefix: str) -> dict:
    """Shared LM bench recipe (both LM phases): build the production
    train step (bf16, adam, blockwise flash attention; streamed-CE head
    when ``ce_block``), AOT-compile for the compiler's exact peak-temp
    figure, warm up with a hard readback, then time ``steps`` steps. One
    implementation so the timing/readback discipline cannot drift
    between phases."""
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.models.transformer import TransformerLM
    from distributed_tensorflow_tpu.training import (
        adam,
        create_train_state,
        make_train_step,
    )

    model = TransformerLM(vocab_size=vocab, seq_len=seq_len,
                          d_model=LM_D_MODEL, num_heads=4, num_blocks=4,
                          attn_block=LM_ATTN_BLOCK, ce_block=ce_block,
                          compute_dtype=jnp.bfloat16)
    opt = adam(1e-3)
    state = create_train_state(model, opt, seed=0)
    step = make_train_step(model, opt, keep_prob=1.0)
    ds = LMDataSet(max(batch, 4), seq_len=seq_len, vocab_size=vocab,
                   seed=0)
    b = ds.next_batch(batch)
    runner = step.lower(state, b).compile()
    temp_bytes = int(runner.memory_analysis().temp_size_in_bytes)
    state, m = runner(state, b)
    float(m["loss"])  # hard readback: clean clock
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = runner(state, ds.next_batch(batch))
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    return {f"{prefix}_tokens_per_sec_per_chip":
                round(steps * batch * seq_len / dt),
            f"{prefix}_step_temp_bytes": temp_bytes}


def lm_longctx_phase() -> dict:
    """Long-context causal LM: tokens/sec/chip for the production train
    step at 4096-token context — blockwise flash attention
    (--attn_block 512, custom-VJP backward: O(S*block) memory both
    passes), bf16, adam, batch 8. Also reports the XLA compiler's peak
    temp allocation for the step (memory_analysis — the evidence that
    the long-context path's memory claim holds on this hardware; the
    dense form compile-fails at 2x this length, PERF.md round-4
    sweep). The reference has no attention at all (images only,
    MNISTDist.py:68) — this phase records the build's beyond-parity
    flagship."""
    out = _lm_phase(64, LM_SEQ_LEN, LM_BATCH, LM_TIMED_STEPS,
                    ce_block=None, prefix="lm_4k")
    out["lm_seq_len"] = LM_SEQ_LEN
    return out


def lm_largevocab_phase() -> dict:
    """Large-vocab long context: tokens/sec/chip for the production
    train step at LM_BIGV_VOCAB x LM_BIGV_SEQ_LEN with BOTH streams on
    — blockwise flash attention (O(S*block)) and the streamed
    softmax-CE head (O(ce_block*V), custom VJP; ops/nn.py). At this
    config the UNSTREAMED head's logits+grad alone exceed the chip
    (the r5 vocab sweep records the naive wall); this phase is the
    driver-captured evidence that large-vocab long context trains on
    one chip. Reports the compiler's exact peak temp allocation."""
    out = _lm_phase(LM_BIGV_VOCAB, LM_BIGV_SEQ_LEN, LM_BIGV_BATCH,
                    LM_BIGV_TIMED_STEPS, ce_block=LM_BIGV_CE_BLOCK,
                    prefix="lm_bigvocab")
    out["lm_bigvocab_vocab"] = LM_BIGV_VOCAB
    out["lm_bigvocab_seq_len"] = LM_BIGV_SEQ_LEN
    return out


def _ppep_model_ways(n_chips: int, num_blocks: int | None = None) -> int:
    """Model-axis width for the PP/EP device phases: the largest of
    {4, 2} that divides the chip count and the block/expert layout
    (``num_blocks`` defaults to the shared PP/EP constant; the PP phase
    passes its own PP_NUM_BLOCKS so its divisibility guard tracks its
    model); 0 = no model axis on this machine (phase skipped)."""
    nb = PP_EP_NUM_BLOCKS if num_blocks is None else num_blocks
    for ways in (4, 2):
        if n_chips >= ways and n_chips % ways == 0 \
                and nb % ways == 0 \
                and PP_EP_EXPERTS % ways == 0:
            return ways
    return 0


def _pp_virtual_stages(ways: int) -> int:
    """Virtual-stage count for the PP phase's interleaved run: the
    largest of {PP_VIRTUAL_STAGES, 1} whose K*V block groups divide the
    phase model (microbatches = ways, so the V>1 round constraint
    M % K == 0 holds by construction)."""
    for v in (PP_VIRTUAL_STAGES, 1):
        if PP_NUM_BLOCKS % (ways * v) == 0:
            return v
    return 1


def _pp_schedule_facts(ways: int) -> dict:
    """Analytic schedule facts for the PP phase config at ``ways``
    stages (microbatches = ways): computable with NO chip, so host-only
    records still carry schedule-level evidence."""
    from distributed_tensorflow_tpu.parallel.pp_schedule import (
        build_pp_schedule,
    )

    v = _pp_virtual_stages(ways)
    sched = build_pp_schedule(ways, ways, v)
    return {
        "pp_schedule": "interleaved" if v > 1 else "gpipe",
        "pp_virtual_stages": v,
        "pp_useful_tick_fraction": round(sched.useful_tick_fraction, 4),
    }


def _time_resident_chunks(chunk_fn, state, data, chunk: int,
                          timed_chunks: int, n_chips: int) -> float:
    """Warm up (compile + hard readback), then time ``timed_chunks``
    dispatches of a device-resident chunked step; returns seconds."""
    state, m = chunk_fn(state, data)
    float(m["loss"])  # hard readback so the clock starts clean
    sync_every = _sync_every(n_chips)
    t0 = time.perf_counter()
    for c in range(1, timed_chunks + 1):
        state, m = chunk_fn(state, data)
        if sync_every and (c * chunk) % sync_every < chunk:
            jax.block_until_ready(state.params)
    jax.block_until_ready(state.params)
    return time.perf_counter() - t0


def pp_device_phase(n_chips) -> dict:
    """Pipeline parallelism over a DEVICE-RESIDENT split: the stage
    ring (blocks staged over the model axis, schedule-table tick scan +
    ppermute) fed by on-device batch sampling with lax.scan chunking —
    zero host->device bytes per step, one dispatch per chunk
    (training/device_step.make_pp_device_train_step). Runs a
    same-session A/B of the two schedules: GPipe (V=1, reported as
    ``pp_gpipe_images_per_sec_per_chip``) vs interleaved virtual
    stages (--virtual_stages, the headline
    ``pp_images_per_sec_per_chip``), with the analytic schedule facts
    (``pp_schedule`` / ``pp_virtual_stages`` /
    ``pp_useful_tick_fraction``) alongside. Rates are sequences/sec/
    chip (the bench's examples-rate convention); null rate fields on a
    1-chip machine — the schedule facts stay non-null (analytic).
    NOTE: the phase model grew 4 -> 8 blocks in r7 (interleaving needs
    V*K to divide the block count on both the 2- and 4-way axes), so
    the pp_images_per_sec_per_chip series breaks at r7 — compare
    within-record against the GPipe A/B number, not across rounds;
    ``pp_device_num_blocks`` records the config."""
    ways = _ppep_model_ways(n_chips, PP_NUM_BLOCKS)
    if not ways:
        out = {"pp_images_per_sec_per_chip": None,
               "pp_gpipe_images_per_sec_per_chip": None,
               "pp_interleave_speedup": None,
               "pp_device_skipped": f"no 2/4-way model axis over "
                                    f"{n_chips} chip(s)"}
        out.update(_pp_schedule_facts(2))  # 2-way fallback config
        return out
    from distributed_tensorflow_tpu.data.device_data import put_device_data
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.models.transformer import TransformerLM
    from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh
    from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS
    from distributed_tensorflow_tpu.parallel.pipeline_parallel import (
        shard_state_pp,
    )
    from distributed_tensorflow_tpu.training import adam, create_train_state
    from distributed_tensorflow_tpu.training.device_step import (
        make_pp_device_train_step,
    )

    mesh = make_mesh(MeshSpec(data=-1, model=ways))
    data_ways = mesh.shape[DATA_AXIS]
    batch = PP_EP_BATCH_PER_DATA_WAY * data_ways
    model = TransformerLM(
        vocab_size=PP_EP_VOCAB, seq_len=PP_EP_SEQ_LEN,
        d_model=PP_EP_D_MODEL, num_heads=4, num_blocks=PP_NUM_BLOCKS,
        compute_dtype=jnp.bfloat16)
    opt = adam(1e-3)
    ds = LMDataSet(PP_EP_SPLIT, seq_len=PP_EP_SEQ_LEN,
                   vocab_size=PP_EP_VOCAB, seed=0)
    data = put_device_data(ds, mesh, data_sharded=True)
    v_best = _pp_virtual_stages(ways)
    rates = {}
    for v in sorted({1, v_best}):
        # fresh base per arm: device_put can ALIAS a committed host
        # leaf into the placed state, and the step's donation then
        # deletes it — re-stacking a shared base on the next arm would
        # read deleted buffers (the CPU-backend aliasing path)
        base = create_train_state(model, opt, seed=0)
        state = shard_state_pp(base, mesh, virtual_stages=v)
        fn = make_pp_device_train_step(model, opt, mesh, batch, ways,
                                       keep_prob=1.0, chunk=PP_EP_CHUNK,
                                       virtual_stages=v)
        dt = _time_resident_chunks(fn, state, data, PP_EP_CHUNK,
                                   PP_EP_TIMED_CHUNKS, n_chips)
        rates[v] = PP_EP_TIMED_CHUNKS * PP_EP_CHUNK * batch / dt / n_chips
    out = {"pp_images_per_sec_per_chip": round(rates[v_best], 1),
           "pp_gpipe_images_per_sec_per_chip": round(rates[1], 1),
           "pp_interleave_speedup": (round(rates[v_best] / rates[1], 3)
                                     if v_best > 1 else None),
           "pp_device_stages": ways, "pp_device_chunk": PP_EP_CHUNK,
           "pp_device_global_batch": batch,
           "pp_device_num_blocks": PP_NUM_BLOCKS}
    out.update(_pp_schedule_facts(ways))
    return out


def ep_device_phase(n_chips) -> dict:
    """Switch-MoE expert parallelism over a DEVICE-RESIDENT split:
    experts sharded over the model axis, on-device batch sampling,
    lax.scan chunking (make_ep_device_train_step). Reports
    ``ep_tokens_per_sec_per_chip``; null fields on a 1-chip machine."""
    ways = _ppep_model_ways(n_chips)
    if not ways:
        return {"ep_tokens_per_sec_per_chip": None,
                "ep_device_skipped": f"no 2/4-way model axis over "
                                     f"{n_chips} chip(s)"}
    from distributed_tensorflow_tpu.data.device_data import put_device_data
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.models.transformer import TransformerLM
    from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh
    from distributed_tensorflow_tpu.parallel.expert_parallel import (
        shard_state_ep,
    )
    from distributed_tensorflow_tpu.parallel.mesh import (
        DATA_AXIS,
        MODEL_AXIS,
    )
    from distributed_tensorflow_tpu.training import adam, create_train_state
    from distributed_tensorflow_tpu.training.device_step import (
        make_ep_device_train_step,
    )

    mesh = make_mesh(MeshSpec(data=-1, model=ways))
    data_ways = mesh.shape[DATA_AXIS]
    batch = PP_EP_BATCH_PER_DATA_WAY * data_ways
    kw = dict(vocab_size=PP_EP_VOCAB, seq_len=PP_EP_SEQ_LEN,
              d_model=PP_EP_D_MODEL, num_heads=4, num_blocks=2,
              moe_experts=PP_EP_EXPERTS, compute_dtype=jnp.bfloat16)
    ep_model = TransformerLM(**kw, moe_axis=MODEL_AXIS)
    opt = adam(1e-3)
    ds = LMDataSet(PP_EP_SPLIT, seq_len=PP_EP_SEQ_LEN,
                   vocab_size=PP_EP_VOCAB, seed=0)
    data = put_device_data(ds, mesh, data_sharded=True)
    state = shard_state_ep(
        create_train_state(TransformerLM(**kw), opt, seed=0), mesh)
    fn = make_ep_device_train_step(ep_model, opt, mesh, batch,
                                   keep_prob=1.0, chunk=PP_EP_CHUNK)
    dt = _time_resident_chunks(fn, state, data, PP_EP_CHUNK,
                               PP_EP_TIMED_CHUNKS, n_chips)
    rate = (PP_EP_TIMED_CHUNKS * PP_EP_CHUNK * batch * PP_EP_SEQ_LEN
            / dt / n_chips)
    return {"ep_tokens_per_sec_per_chip": round(rate, 1),
            "ep_device_experts": PP_EP_EXPERTS,
            "ep_device_chunk": PP_EP_CHUNK,
            "ep_device_global_batch": batch}


def feeddict_baseline_phase(ds, n_chips) -> float:
    """Measured same-machine baseline: the reference's per-step host feed
    (f32 pixels + one-hot f32 labels uploaded synchronously each step,
    batch 128, f32 compute, plain SGD at the reference's default lr —
    GradientDescentOptimizer(0.001), MNISTDist.py:30,149) driving the same
    compiled step. Everything this build's input path improves on is
    deliberately absent here."""
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.training import sgd

    model = DeepCNN()  # f32 compute
    state, step_fn, stage = _build(model, sgd(1e-3), n_chips)

    batch_size = -(-FEEDDICT_BATCH // n_chips) * n_chips
    state, _ = step_fn(state, _stage_feed(ds, batch_size, stage))  # compile
    jax.block_until_ready(state.params)

    t0 = time.perf_counter()
    for _ in range(FEEDDICT_STEPS):
        # synchronous host-side batch assembly + upload on the critical path
        state, _ = step_fn(state, _stage_feed(ds, batch_size, stage))
        jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    return FEEDDICT_STEPS * batch_size / dt / n_chips


def _stage_feed(ds, batch_size, stage):
    batch = ds.train.next_batch(batch_size)  # f32 + one-hot, 3176 B/image
    return stage(batch) if stage is not None else jax.device_put(batch)


def convergence_phase(ds, n_chips, target_acc: float | None = None,
                      max_steps: int | None = None) -> dict:
    """Train to ``target_acc`` test accuracy; wall-clock measured after the
    step/eval executables are compiled (binaries warm, params fresh).
    Device-resident stepping (CONVERGE_EVAL_EVERY steps per dispatch) and a
    device-resident test split: the clock measures training, not the link.
    ``target_acc``/``max_steps`` default to the module globals AT CALL
    TIME (not import time) so tests can monkeypatch the budgets."""
    target_acc = TARGET_ACC if target_acc is None else target_acc
    max_steps = CONVERGE_MAX_STEPS if max_steps is None else max_steps
    from distributed_tensorflow_tpu.data.device_data import put_device_data
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.parallel.data_parallel import replicate_state
    from distributed_tensorflow_tpu.training import adam, create_train_state
    from distributed_tensorflow_tpu.training.train_state import evaluate, make_eval_step

    mesh = _mesh_or_none(n_chips)
    model = DeepCNN(compute_dtype=jnp.bfloat16)
    opt = adam(CONVERGE_LR)
    # round the batch up to a multiple of the data-axis size
    batch_size = -(-CONVERGE_BATCH // n_chips) * n_chips
    data = put_device_data(ds.train, mesh)

    def fresh_state():
        s = create_train_state(model, opt, seed=0)
        return replicate_state(mesh, s) if mesh is not None else s

    chunk_fn = _device_chunk_fn(model, opt, mesh, batch_size,
                                CONVERGE_EVAL_EVERY)

    # device-resident raw test set: periodic evals re-upload nothing
    test_dev = None
    eval_fn = None
    test_raw = (ds.test._raw_u8(), ds.test.labels_int.astype("int32"))
    if n_chips == 1:
        eval_fn = make_eval_step(model)
        test_dev = tuple(jax.device_put(a) for a in test_raw)
    elif ds.test.num_examples % n_chips == 0:
        from distributed_tensorflow_tpu.parallel import shard_batch
        from distributed_tensorflow_tpu.parallel.data_parallel import make_dp_eval_step

        eval_fn = make_dp_eval_step(model, mesh)
        test_dev = shard_batch(mesh, test_raw)
    # else: evaluate() fallback (uneven test split over the mesh)

    # compile AND first-run the step + eval executables (a float()
    # readback ends the first execution for certain), then restart from
    # fresh params REUSING the warm functions
    warm, m = chunk_fn(fresh_state(), data)
    float(m["loss"])
    for _ in range(2):
        if test_dev is not None:
            m = eval_fn(warm.params, test_dev, warm.model_state)
        else:
            m = evaluate(model, warm.params, ds.test, model_state=warm.model_state)
        float(m["loss"])
    del warm
    state = fresh_state()

    acc = 0.0
    steps = 0
    seconds_to_target = None
    t0 = time.perf_counter()
    while steps < max_steps:
        state, _ = chunk_fn(state, data)
        steps += CONVERGE_EVAL_EVERY
        if test_dev is not None:
            m = eval_fn(state.params, test_dev, state.model_state)
        else:
            m = evaluate(model, state.params, ds.test,
                         model_state=state.model_state)
        acc = float(m["accuracy"])
        if acc >= target_acc:
            seconds_to_target = time.perf_counter() - t0
            break
    return {
        "test_accuracy": round(float(acc), 5),
        "seconds_to_target": (
            round(seconds_to_target, 2) if seconds_to_target is not None else None
        ),
        "steps_to_target": steps if seconds_to_target is not None else None,
        "target_accuracy": target_acc,
    }


# Serving drill (r9): the checkpoint-to-traffic path measured HOST-ONLY
# — a numpy model through the REAL engine/batcher/reload machinery
# (serving/), so the serving fields stay non-null in the host-only
# record exactly like the recovery drill. The chip-bound serving numbers
# (jitted buckets, KV decode) live in tests; this phase evidences the
# traffic machinery: offered-load latency quantiles, throughput, and the
# hot-reload blip with a corrupt-newest fallback.
SERVE_BENCH_REQUESTS = 240
SERVE_BENCH_CONCURRENCY = 4
SERVE_BENCH_SWEEP_RPS = (200.0, 800.0)


class _ServeBenchModel:
    """Minimal host model for the serving drill: logits = x @ w + b."""

    @staticmethod
    def apply(params, x):
        import numpy as np

        return np.asarray(x) @ params["w"] + params["b"]


def serving_phase() -> dict:
    import os
    import shutil
    import sys
    import tempfile

    import numpy as np

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        save_checkpoint,
    )
    from distributed_tensorflow_tpu.serving.batcher import DynamicBatcher
    from distributed_tensorflow_tpu.serving.engine import InferenceEngine
    from distributed_tensorflow_tpu.serving.server import (
        make_predict_runner,
    )
    from distributed_tensorflow_tpu.utils.metrics import StreamingHistogram
    from tools.serve_loadgen import run_closed_loop, run_open_loop

    d = tempfile.mkdtemp(prefix="bench-serving-")
    batcher = None
    try:
        rng = np.random.default_rng(0)
        params = {"w": rng.standard_normal((64, 16)).astype(np.float32),
                  "b": np.zeros(16, np.float32)}
        save_checkpoint(d, {"params": params}, 10)
        save_checkpoint(
            d, {"params": {**params, "b": params["b"] + 1.0}}, 20)

        engine = InferenceEngine(_ServeBenchModel(), d, jit=False,
                                 params_template=params, max_batch=8)
        hist = StreamingHistogram()
        batcher = DynamicBatcher(make_predict_runner(engine),
                                 max_batch=8, max_delay_ms=1.0,
                                 queue_depth=64, latency=hist,
                                 name="bench-serve")
        x = rng.standard_normal(64).astype(np.float32)
        request = lambda: batcher.submit(x).result(10)
        rep = run_closed_loop(request,
                              n_requests=SERVE_BENCH_REQUESTS,
                              concurrency=SERVE_BENCH_CONCURRENCY)

        # offered-load sweep (open loop: arrivals don't slow down with
        # the server, so the p99 under each offered rate is honest)
        sweep = []
        for rate in SERVE_BENCH_SWEEP_RPS:
            pt = run_open_loop(request, rate_rps=rate, duration_s=1.5)
            sweep.append({
                "offered_rps": rate,
                "achieved_rps": pt["achieved_rps"],
                "p99_ms": round(pt["latency_ms_p99"], 3),
                "rejected": pt["rejected"],
            })

        # hot-reload blip under traffic: a GOOD newer checkpoint swaps
        # mid-stream; then a TORN newest rides the fallback ladder. The
        # blip is the swap's wall time; drops must stay zero throughout.
        import threading

        stop = threading.Event()
        errors = []

        def traffic():
            while not stop.is_set():
                try:
                    batcher.submit(x).result(10)
                except Exception as e:  # noqa: BLE001 — counted, not raised
                    errors.append(e)

        threads = [threading.Thread(target=traffic, daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()
        try:
            save_checkpoint(
                d, {"params": {**params, "b": params["b"] + 2.0}}, 30)
            # the engine/ladder narrate reloads on stdout; bench's
            # stdout contract is ONE JSON line — route to stderr
            with contextlib.redirect_stdout(sys.stderr):
                good = engine.reload_if_newer()
                save_checkpoint(
                    d, {"params": {**params, "b": params["b"] + 3.0}},
                    40)
                newest = os.path.join(d, "ckpt-40.npz")
                with open(newest, "r+b") as f:
                    f.truncate(os.path.getsize(newest) // 2)
                corrupt = engine.reload_if_newer()
        finally:
            # a failure above must not leave the traffic threads
            # spinning against the closed batcher for the rest of bench
            stop.set()
        for t in threads:
            t.join(timeout=10)

        assert good and good.get("swapped"), f"good reload failed: {good}"
        assert corrupt and not corrupt.get("swapped"), (
            f"corrupt newest must not swap: {corrupt}")
        # headline latency/throughput come from the SAME population
        # (the nominal closed-loop drill); the batcher-level histogram
        # also saw the deliberately-saturating sweep + reload traffic
        return {
            "serving_p50_ms": round(rep["latency_ms_p50"], 3),
            "serving_p99_ms": round(rep["latency_ms_p99"], 3),
            "serving_throughput_rps": rep["achieved_rps"],
            "serving_reload_blip_ms": round(good["reload_ms"], 3),
            "serving_reload_fallback_depth": corrupt.get(
                "fallback_depth"),
            "serving_dropped": len(errors) + rep["errors"],
            "serving_offered_sweep": sweep,
        }
    finally:
        if batcher is not None:
            batcher.close(drain=False)
        shutil.rmtree(d, ignore_errors=True)


# r22: the fleet-router drill — 2 HOST-ONLY replicas (numpy engines
# through the real batcher/server machinery, LocalTransport, no
# sockets) under the real Router: dispatch spread, per-request routing
# overhead, a breaker trip-and-recover, a hedged dispatch, and the
# drain-on-503 flip. Serial dispatch from the bench thread (the one
# hedge timer is router.py's registered Timer), so every router_* fact
# stays non-null in the host-only record.
ROUTER_BENCH_REQUESTS = 40


def router_phase() -> dict:
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        save_checkpoint,
    )
    from distributed_tensorflow_tpu.serving.batcher import DynamicBatcher
    from distributed_tensorflow_tpu.serving.engine import InferenceEngine
    from distributed_tensorflow_tpu.serving.replica import (
        LocalTransport,
        Replica,
        TransportError,
    )
    from distributed_tensorflow_tpu.serving.router import Router
    from distributed_tensorflow_tpu.serving.server import (
        InferenceServer,
        InProcessClient,
        make_predict_runner,
    )

    class _Flaky:
        """Transport wrapper that refuses until told otherwise — the
        breaker drill's unreachable-replica stand-in."""

        def __init__(self, inner):
            self.inner = inner
            self.fail = False

        def get(self, path):
            if self.fail:
                raise TransportError("bench: injected connect-fail")
            return self.inner.get(path)

        def post(self, path, obj):
            if self.fail:
                raise TransportError("bench: injected connect-fail")
            return self.inner.post(path, obj)

    d = tempfile.mkdtemp(prefix="bench-router-")
    batchers = []
    try:
        rng = np.random.default_rng(0)
        params = {"w": rng.standard_normal((64, 16)).astype(np.float32),
                  "b": np.zeros(16, np.float32)}
        save_checkpoint(d, {"params": params}, 10)
        replicas, clients = [], []
        for i in range(2):
            engine = InferenceEngine(_ServeBenchModel(), d, jit=False,
                                     params_template=params, max_batch=8)
            batcher = DynamicBatcher(make_predict_runner(engine),
                                     max_batch=8, max_delay_ms=1.0,
                                     queue_depth=64,
                                     name=f"bench-router-{i}")
            batchers.append(batcher)
            client = InProcessClient(predict_batcher=batcher)
            srv = InferenceServer(engine, client, port=0)  # never started
            clients.append(client)
            replicas.append(
                Replica(f"bench-r{i}",
                        _Flaky(LocalTransport(srv)),
                        breaker_fails=2, eject_s=0.05))
        router = Router(replicas, retries=2, backoff_ms=2.0,
                        min_healthy=1, seed=0)
        x = rng.standard_normal(64).astype(np.float32).tolist()
        payload = {"inputs": x}

        # dispatch spread + routing overhead: routed (hedge off — the
        # honest single-dispatch path) vs direct on the same population
        t0 = _time.perf_counter()
        for _ in range(ROUTER_BENCH_REQUESTS):
            status, _body, _name = router.dispatch("/v1/predict",
                                                   dict(payload))
            assert status == 200, f"routed dispatch failed: {status}"
        routed_s = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        for _ in range(ROUTER_BENCH_REQUESTS):
            clients[0].predict_ex(x)
        direct_s = _time.perf_counter() - t0
        spread = [r.snapshot()["dispatches"] for r in replicas]
        assert min(spread) > 0, f"one replica starved: {spread}"

        # hedge drill: a second router over the SAME fleet with a
        # hair-trigger budget — the timer fires mid-dispatch and the
        # duplicate rides the other replica (serial from this thread;
        # the timer is router.py's registered hedge Timer)
        hedger = Router(replicas, retries=2, backoff_ms=2.0,
                        hedge_ms=0.5, hedge_budget_pct=100.0,
                        min_healthy=1, seed=0)
        for _ in range(8):
            status, _body, _name = hedger.dispatch("/v1/predict",
                                                   dict(payload))
            assert status == 200, f"hedged dispatch failed: {status}"

        # breaker drill: replica 1 goes unreachable — retries absorb
        # onto replica 0, consecutive failures eject, then the
        # half-open probe heals it after the cooldown
        replicas[1].transport.fail = True
        for _ in range(6):
            status, _body, _name = router.dispatch("/v1/predict",
                                                   dict(payload))
            assert status == 200, "retry must absorb the outage"
        ejections = replicas[1].snapshot()["ejections"]
        assert ejections >= 1, "breaker never tripped"
        replicas[1].transport.fail = False
        _time.sleep(0.08)  # past eject_s: the probe window opens
        healed = False
        for _ in range(20):
            router.dispatch("/v1/predict", dict(payload))
            if replicas[1].is_healthy():
                healed = True
                break
        assert healed, "half-open probe never closed the breaker"

        # drain-on-503 LAST (it closes a batcher): replica 1's healthz
        # flips 503, the fold drains it, traffic keeps flowing on 0
        batchers[1].close(drain=False)
        st, body = replicas[1].transport.get("/healthz")
        replicas[1].observe_health(st, body, _time.monotonic())
        assert replicas[1].state_name() == "draining", \
            replicas[1].state_name()
        status, _body, name = router.dispatch("/v1/predict",
                                              dict(payload))
        assert status == 200 and name == "bench-r0", (status, name)

        fleet = router.fleet_report()
        n = ROUTER_BENCH_REQUESTS
        return {
            "router_replicas": len(replicas),
            "router_healthy": fleet["healthy"],
            "router_ejections": sum(r["ejections"]
                                    for r in fleet["replicas"]),
            "router_retries": fleet["retries_total"],
            "router_hedges": hedger.fleet_report()["hedges_total"],
            "router_overhead_ms": round(
                max(routed_s - direct_s, 0.0) / n * 1e3, 4),
        }
    finally:
        for b in batchers:
            if not b.closed:
                b.close(drain=False)
        shutil.rmtree(d, ignore_errors=True)


# r21: continuous batching — the long-generation-adversary A/B. Both
# arms are HOST-ONLY (HostSlotBackend charges a fixed sleep per decode
# iteration; no jax, no chip), so every continuous_*/kv_* field stays
# non-null in the host-only record like the serving drill. The
# arms pay the SAME per-iteration price; what differs is the schedule:
# whole-batch commits a worker for a request's entire generation
# (longs head-of-line-block shorts, batches fragment on the
# (len, n, temp) group key), continuous admits/retires between
# iterations over slots whose memory is paged — which is why the same
# KV token budget that gives whole-batch 4 dense rows
# (WB_BATCH x CAPACITY tokens = PAGES x PAGE) runs more continuous
# slots: commitments track actual footprints (prompt + n - 1), not
# capacity. The defaults are the SMOKE config (~1-2 s): the drill
# rides every host-only record and the record builder runs many
# times under test, so the default sweep must stay cheap. The
# adversary-scale config — longer generations, a wider rate sweep,
# 12 slots vs 4 dense rows — lives in CONTINUOUS_BENCH_FULL and is
# pinned by the slow-tier A/B test (>=2x knee, >=5x p99 queue_wait
# reduction, zero drops below the knee).
CONTINUOUS_BENCH_STEP_S = 0.0005
CONTINUOUS_BENCH_PROMPT_LEN = 2
CONTINUOUS_BENCH_SHORT_TOKENS = 3
CONTINUOUS_BENCH_LONG_TOKENS = 9
CONTINUOUS_BENCH_LONG_EVERY = 5
CONTINUOUS_BENCH_SLOTS = 4
CONTINUOUS_BENCH_WB_BATCH = 2
CONTINUOUS_BENCH_CAPACITY = 24
CONTINUOUS_BENCH_PAGE = 4
CONTINUOUS_BENCH_PAGES = 12  # == WB_BATCH * CAPACITY tokens / PAGE
CONTINUOUS_BENCH_RATES = (60.0, 120.0, 240.0, 480.0)
CONTINUOUS_BENCH_DURATION_S = 0.25

# the long-generation-adversary config (slow-tier A/B; see above)
CONTINUOUS_BENCH_FULL = {
    "CONTINUOUS_BENCH_STEP_S": 0.001,
    "CONTINUOUS_BENCH_PROMPT_LEN": 2,
    "CONTINUOUS_BENCH_SHORT_TOKENS": 4,
    "CONTINUOUS_BENCH_LONG_TOKENS": 32,
    "CONTINUOUS_BENCH_LONG_EVERY": 10,
    "CONTINUOUS_BENCH_SLOTS": 12,
    "CONTINUOUS_BENCH_WB_BATCH": 4,
    "CONTINUOUS_BENCH_CAPACITY": 72,
    "CONTINUOUS_BENCH_PAGE": 4,
    "CONTINUOUS_BENCH_PAGES": 72,
    "CONTINUOUS_BENCH_RATES": (
        40.0, 80.0, 160.0, 320.0, 640.0, 960.0, 1280.0),
    "CONTINUOUS_BENCH_DURATION_S": 1.2,
}

_CONTINUOUS_NULLS = {
    "continuous_knee_rps": None,
    "whole_batch_knee_rps": None,
    "continuous_knee_ratio": None,
    "continuous_queue_wait_p99_ms": None,
    "whole_batch_queue_wait_p99_ms": None,
    "continuous_queue_wait_reduction": None,
    "continuous_drops_below_knee": None,
    "continuous_mix": None,
    "kv_pages_allocated": None,
    "kv_pages_high_water": None,
    "kv_page_ledger_ok": None,
    "slot_occupancy": None,
    "tokens_per_iteration": None,
}


def continuous_batching_phase(measured: bool = True) -> dict:
    """Two halves, separately guarded. The ANALYTIC half drives a short
    mixed workload through the continuous scheduler with zero step cost
    and reports the page-ledger facts (kv_pages_allocated,
    slot_occupancy, tokens_per_iteration — asserting the paged-cache
    claim: KV high water tracks live tokens, not slots x capacity).
    The MEASURED half is the knee-throughput A/B on the long-tail mix —
    whole-batch vs continuous at equal per-iteration cost — reporting
    each arm's knee and the p99 queue_wait at the highest rate both
    sustain. ``measured=False`` (the host-only record) keeps the
    analytic ledger facts and leaves the knee keys null — the same
    convention the chip-gated A/Bs use, here because a wall-clock rate
    sweep has no place in the host-only record."""
    import numpy as np

    from distributed_tensorflow_tpu.serving import reqtrace
    from distributed_tensorflow_tpu.serving.batcher import DynamicBatcher
    from distributed_tensorflow_tpu.serving.continuous import (
        ContinuousBatcher,
        HostSlotBackend,
    )
    from distributed_tensorflow_tpu.serving.server import (
        generate_group_key,
    )
    from tools.serve_loadgen import knee_throughput, long_tail_fn

    out = dict(_CONTINUOUS_NULLS)
    short_n = CONTINUOUS_BENCH_SHORT_TOKENS
    long_n = CONTINUOUS_BENCH_LONG_TOKENS
    prompt = np.arange(1, CONTINUOUS_BENCH_PROMPT_LEN + 1, dtype=np.int32)
    out["continuous_mix"] = (
        f"1-in-{CONTINUOUS_BENCH_LONG_EVERY} long "
        f"({long_n} tokens), rest short ({short_n})")

    # ---- analytic half: the page ledger under a mixed residency
    cb = None
    try:
        backend = HostSlotBackend(
            n_slots=4, capacity=CONTINUOUS_BENCH_CAPACITY,
            page_size=CONTINUOUS_BENCH_PAGE)
        cb = ContinuousBatcher(backend, queue_depth=32,
                               default_timeout_ms=30000,
                               name="bench-cont-ledger")
        futs = [cb.submit(prompt, max_new_tokens=(
                    long_n if i % 3 == 2 else short_n),
                    temperature=0.0)
                for i in range(12)]
        for f in futs:
            f.result(30)
        snap = cb.scheduler.snapshot()
        kv = snap["kv_pages"]
        # the paged-cache claim, analytically: pages never ran ahead of
        # live tokens by more than the per-slot partial-page slack
        page = CONTINUOUS_BENCH_PAGE
        assert snap["page_ledger_ok"], "page ledger diverged from residents"
        assert kv["pages_high_water"] * page < (
            snap["live_tokens_high_water"] + backend.n_slots * page), (
            f"KV high water {kv['pages_high_water']} pages exceeds the "
            f"live-token bound ({snap['live_tokens_high_water']} tokens)")
        out.update({
            "kv_pages_allocated": kv["allocs_total"],
            "kv_pages_high_water": kv["pages_high_water"],
            "kv_page_ledger_ok": snap["page_ledger_ok"],
            "slot_occupancy": snap["slot_occupancy"],
            "tokens_per_iteration": snap["tokens_per_iteration"],
        })
    finally:
        if cb is not None:
            cb.close(drain=False)

    # ---- measured half: knee-throughput A/B on the long-tail mix
    if not measured:
        return out
    prev_plane = reqtrace.get_plane()
    cont = wb = None
    try:
        step_cost = lambda: time.sleep(CONTINUOUS_BENCH_STEP_S)  # noqa: E731
        # the plane supplies per-request queue_wait to the loadgen rows
        reqtrace.configure(enabled=True, ring=256)

        backend = HostSlotBackend(
            n_slots=CONTINUOUS_BENCH_SLOTS,
            capacity=CONTINUOUS_BENCH_CAPACITY,
            page_size=CONTINUOUS_BENCH_PAGE,
            num_pages=CONTINUOUS_BENCH_PAGES, step_cost=step_cost)
        cont = ContinuousBatcher(backend, queue_depth=64,
                                 default_timeout_ms=10000,
                                 name="bench-cont")

        def wb_runner(payloads, opts_list):
            # whole-batch generation cost model: one prefill step plus
            # n decode steps, batch-wide — the batch runs as long as
            # its generation length whatever its width
            n = int(opts_list[0].get("max_new_tokens", 16))
            for _ in range(n + 1):
                step_cost()
            return [np.zeros(len(p) + n, np.int32) for p in payloads]

        wb = DynamicBatcher(wb_runner, group_key=generate_group_key,
                            max_batch=CONTINUOUS_BENCH_WB_BATCH,
                            max_delay_ms=2.0, queue_depth=64,
                            default_timeout_ms=10000, name="bench-wb")

        def mk(batcher, n):
            def call():
                f = batcher.submit(prompt, max_new_tokens=n,
                                   temperature=0.0)
                f.result(15)
                meta = f.meta or {}
                return {"request_id": meta.get("request_id"),
                        "phases_ms": meta.get("phases_ms")}
            return call

        reps = {}
        for arm, b in (("whole_batch", wb), ("continuous", cont)):
            fn = long_tail_fn(mk(b, short_n), mk(b, long_n),
                              long_every=CONTINUOUS_BENCH_LONG_EVERY)
            reps[arm] = knee_throughput(
                fn, CONTINUOUS_BENCH_RATES,
                duration_s=CONTINUOUS_BENCH_DURATION_S)

        wb_knee = reps["whole_batch"]["knee_rps"]
        cont_knee = reps["continuous"]["knee_rps"]
        # compare tails at the highest rate BOTH arms sustain — the
        # honest rate: neither arm is in collapse there
        wb_sust = {r["offered_rps"]
                   for r in reps["whole_batch"]["sweep"] if r["sustained"]}
        common = [r for r in reps["continuous"]["sweep"]
                  if r["sustained"] and r["offered_rps"] in wb_sust]
        qw_c = qw_w = None
        if common:
            rate = common[-1]["offered_rps"]
            qw_c = common[-1]["queue_wait_p99_ms"]
            qw_w = next(r for r in reps["whole_batch"]["sweep"]
                        if r["offered_rps"] == rate)["queue_wait_p99_ms"]
        out.update({
            "continuous_knee_rps": cont_knee,
            "whole_batch_knee_rps": wb_knee,
            "continuous_knee_ratio": (
                round(cont_knee / wb_knee, 3) if wb_knee else None),
            "continuous_queue_wait_p99_ms": qw_c,
            "whole_batch_queue_wait_p99_ms": qw_w,
            "continuous_queue_wait_reduction": (
                round(qw_w / max(qw_c, 1e-3), 2)
                if qw_w is not None and qw_c is not None else None),
            "continuous_drops_below_knee": sum(
                r["rejected"] + r["errors"]
                for r in reps["continuous"]["sweep"] if r["sustained"]),
        })
    finally:
        for b in (cont, wb):
            if b is not None:
                b.close(drain=False)
        reqtrace._PLANE = prev_plane
    return out


# r11: telemetry phases. The span overhead and the breakdown-machinery
# drill are HOST-ONLY (stdlib telemetry, no chip), like the recovery
# and serving drills; the A/B (telemetry on vs off around the flagship
# device-resident chunk loop) needs the chip and stays null without it.

#: The clock of the two host-cost budgets (ns/span here, ms/request-record
#: in reqtrace_phase): the measuring thread's CPU time. Both loops are pure
#: host work whose budget assertion ends the run, and on a shared host the
#: wall counts whoever else ran: with every core oversubscribed one span
#: read 19-41 us on the wall and 3.0-3.2 us of CPU time, against 2.3-2.7 us
#: on either clock with the host idle (PR 21, this sandbox's CPU).
_host_cost_clock = time.thread_time
TELEMETRY_SPAN_SAMPLES = 20000
TELEMETRY_SPAN_BUDGET_NS = 5000  # < 5 us/span, asserted
TELEMETRY_AB_CHUNKS = 4
TELEMETRY_SYNTH_STEPS = 32



def telemetry_phase() -> dict:
    """Host-only telemetry evidence: measured ns/span of the tracing
    context manager (asserted under the 5 us budget — the always-on
    claim is a number, not a promise), and the step-time-breakdown
    machinery driven end-to-end (the REAL StepTimer against a synthetic
    stepper with known host_wait/dispatch/device phases — the same
    accumulate-and-window path every training loop emits through).
    ``telemetry_overhead_pct`` stays null here; the chip A/B fills it."""
    import math

    from distributed_tensorflow_tpu.utils import telemetry

    tracer = telemetry.get_tracer()
    prev_enabled = tracer.enabled
    try:
        tracer.enabled = True
        best = math.inf
        for _ in range(3):  # best-of-3: absorb host scheduling noise
            t0 = _host_cost_clock()
            for _ in range(TELEMETRY_SPAN_SAMPLES):
                with telemetry.trace_span("bench_span"):
                    pass
            best = min(best, (_host_cost_clock() - t0)
                       / TELEMETRY_SPAN_SAMPLES * 1e9)
        assert best < TELEMETRY_SPAN_BUDGET_NS, (
            f"span overhead {best:.0f} ns/span blows the "
            f"{TELEMETRY_SPAN_BUDGET_NS} ns budget — the always-on "
            f"telemetry claim no longer holds")

        st = telemetry.StepTimer()
        for _ in range(TELEMETRY_SYNTH_STEPS):
            for key, dt in (("host_wait", 2e-4), ("dispatch", 5e-4),
                            ("device", 2e-4)):
                t0 = time.perf_counter()
                time.sleep(dt)
                st.add(key, time.perf_counter() - t0)
            st.steps()
        bd = st.scalars()
        assert set(bd) == {"step_host_wait_s", "step_dispatch_s",
                           "step_device_s"} and all(
            v > 0 for v in bd.values()), bd
        return {
            "telemetry_span_overhead_ns": round(best, 1),
            "telemetry_span_budget_ns": TELEMETRY_SPAN_BUDGET_NS,
            "telemetry_step_host_wait_s": bd["step_host_wait_s"],
            "telemetry_step_dispatch_s": bd["step_dispatch_s"],
            "telemetry_step_device_s": bd["step_device_s"],
            "telemetry_breakdown_source": "synthetic",
            "telemetry_overhead_pct": None,
        }
    finally:
        tracer.enabled = prev_enabled


def telemetry_ab_phase(ds, n_chips) -> dict:
    """Same-session A/B on the flagship device-resident chunk loop:
    telemetry ON (the loops' exact per-chunk instrumentation — span +
    watchdog-arm + StepTimer, PLUS the r12 accounting: EfficiencyMeter
    scalars and an armed warn-mode Sentinel observation per chunk, PLUS
    the r13 resource plane: a MemoryMeter display-cadence sample and a
    CompileSentry signature note per chunk) vs OFF (bare dispatch),
    same compiled executable.
    ``telemetry_overhead_pct`` is the acceptance number (< 2% required
    — now covering the full armed observability stack, PLUS the r19
    request plane: one request record begun/finished through an armed
    RequestPlane per chunk — audit ring, tail histograms, SLO ledger,
    and the req:* span emission); the ON arm's StepTimer also yields
    the MEASURED step-time breakdown for the flagship CNN, replacing
    the host-only phase's synthetic facts."""
    from distributed_tensorflow_tpu.data.device_data import (
        put_device_data,
    )
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.parallel.data_parallel import (
        replicate_state,
    )
    from distributed_tensorflow_tpu.serving import reqtrace
    from distributed_tensorflow_tpu.training import (
        adam,
        create_train_state,
    )
    from distributed_tensorflow_tpu.utils import resources, telemetry
    from distributed_tensorflow_tpu.utils.efficiency import (
        EfficiencyMeter,
    )
    from distributed_tensorflow_tpu.utils.sentinel import Sentinel

    model = DeepCNN(compute_dtype=jnp.bfloat16)
    opt = adam(1e-3)
    batch_size = PER_CHIP_BATCH * n_chips
    mesh = _mesh_or_none(n_chips)
    data = put_device_data(ds.train, mesh)
    chunk_fn = _device_chunk_fn(model, opt, mesh, batch_size, CHUNK)
    sync_every = _sync_every(n_chips)
    tracer = telemetry.get_tracer()
    prev_enabled = tracer.enabled
    # built OUTSIDE the timed window: the one-shot peak calibration
    # (cached) must not bill the ON arm
    eff = EfficiencyMeter(model, batch_size, n_chips)
    rates = {}
    breakdown = {}
    try:
        for arm in ("off", "on"):
            tracer.enabled = arm == "on"
            # the ON arm pays the REAL armed() path (cv + dict +
            # notify per dispatch), not the no-op shortcut — the
            # <2% number must cover a --watchdog_s production run
            telemetry.set_watchdog(
                telemetry.Watchdog(3600.0) if arm == "on" else None)
            snt = Sentinel(action="warn") if arm == "on" else None
            # the r13 resource plane pays its display-site cost in
            # the ON arm too: a memory sample (runtime stat query /
            # live-array walk — no device sync) and a signature
            # note per chunk
            mm = resources.MemoryMeter() if arm == "on" else None
            cs = resources.CompileSentry() if arm == "on" else None
            # the r19 request plane pays its per-request cost in
            # the ON arm too (built outside the timed window; the
            # per-chunk begin/finish below is the armed record)
            rplane = (reqtrace.RequestPlane(ring=256, exemplars=3,
                                            slo_p99_ms=1000.0)
                      if arm == "on" else None)
            state = create_train_state(model, opt, seed=0)
            if mesh is not None:
                state = replicate_state(mesh, state)
            state, m = chunk_fn(state, data)  # compile + upload
            float(m["loss"])  # hard readback: clock starts clean
            st = telemetry.StepTimer()
            t0 = time.perf_counter()
            for c in range(1, TELEMETRY_AB_CHUNKS + 1):
                if arm == "on":
                    t1 = time.perf_counter()
                    with telemetry.trace_span("device_chunk",
                                              step=c * CHUNK,
                                              length=CHUNK), \
                            telemetry.armed("device_chunk",
                                            step=c * CHUNK):
                        state, m = chunk_fn(state, data)
                    st.add("dispatch", time.perf_counter() - t1)
                    st.steps(CHUNK)
                    # the r12 accounting at the loops' display-site
                    # cost: mfu/goodput scalar math + a sentinel
                    # observation (host-side only — a device
                    # readback here would add a sync the OFF arm
                    # doesn't pay and poison the A/B)
                    eff.scalars(batch_size * CHUNK)
                    snt.observe(c * CHUNK, {"loss": 1.0 + 1e-3 * c})
                    mm.scalars()
                    cs.observe("device_chunk", (CHUNK,))
                    # one armed request-plane record: trace begin,
                    # lifecycle marks, finish (audit + tail hists
                    # + SLO observe + req:* span emission)
                    tr = rplane.begin(reqtrace.new_request_id(),
                                      "bench", CHUNK)
                    tr.admitted()
                    tr.taken()
                    tr.run_start()
                    tr.note("prefill", 0.0)
                    tr.run_end()
                    rplane.finish(tr, "ok")
                else:
                    state, m = chunk_fn(state, data)
                if sync_every and (c * CHUNK) % sync_every < CHUNK:
                    if arm == "on":
                        t1 = time.perf_counter()
                        with telemetry.trace_span("device_sync"):
                            jax.block_until_ready(state.params)
                        st.add("device", time.perf_counter() - t1)
                    else:
                        jax.block_until_ready(state.params)
            jax.block_until_ready(state.params)
            dt = time.perf_counter() - t0
            rates[arm] = (TELEMETRY_AB_CHUNKS * CHUNK * batch_size
                          / dt / n_chips)
            if arm == "on":
                breakdown = st.scalars()
            del state
    finally:
        tracer.enabled = prev_enabled
        telemetry.set_watchdog(None)
    overhead = (rates["off"] - rates["on"]) / rates["off"] * 100.0
    return {
        "telemetry_overhead_pct": round(overhead, 3),
        "telemetry_off_images_per_sec_per_chip": round(rates["off"], 1),
        "telemetry_on_images_per_sec_per_chip": round(rates["on"], 1),
        "telemetry_step_host_wait_s": breakdown["step_host_wait_s"],
        "telemetry_step_dispatch_s": breakdown["step_dispatch_s"],
        "telemetry_step_device_s": breakdown["step_device_s"],
        "telemetry_breakdown_source": "measured",
    }


# r19: the request-plane drill — host-only like the serving drill (the
# real engine/batcher/client with serving/reqtrace armed, no chip), so
# the per-request observability facts need no chip. The
# closed-loop loadgen drives REQTRACE_REQUESTS requests through the
# plane and the record asserts 100% of them reconstruct a complete
# phase timeline. Overhead is measured DETERMINISTICALLY: the plane's
# per-request cost (begin + lifecycle marks + finish with audit/tail/
# SLO/span emission, amortized over a tight loop) as a percent of the
# drill's measured mean request latency — a thread-scheduling-noisy
# on/off closed-loop A/B cannot resolve a cost this small. The <2%
# end-to-end acceptance number is telemetry_ab_phase's, whose ON arm
# pays the same per-record cost.
REQTRACE_REQUESTS = 200
REQTRACE_SLO_P99_MS = 250.0
REQTRACE_COST_SAMPLES = 2000



def reqtrace_phase() -> dict:
    import math
    import shutil
    import tempfile

    import numpy as np

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        save_checkpoint,
    )
    from distributed_tensorflow_tpu.serving import reqtrace
    from distributed_tensorflow_tpu.serving.batcher import DynamicBatcher
    from distributed_tensorflow_tpu.serving.engine import InferenceEngine
    from distributed_tensorflow_tpu.serving.server import (
        InProcessClient,
        make_predict_runner,
        predict_group_key,
    )
    from distributed_tensorflow_tpu.utils import telemetry
    from tools.serve_loadgen import run_closed_loop

    d = tempfile.mkdtemp(prefix="bench-reqtrace-")
    prev_plane = reqtrace.get_plane()
    tracer = telemetry.get_tracer()
    prev_enabled = tracer.enabled
    batchers = []
    try:
        rng = np.random.default_rng(0)
        params = {"w": rng.standard_normal((64, 16)).astype(np.float32),
                  "b": np.zeros(16, np.float32)}
        save_checkpoint(d, {"params": params}, 10)
        engine = InferenceEngine(_ServeBenchModel(), d, jit=False,
                                 params_template=params, max_batch=8)
        x = rng.standard_normal(64).astype(np.float32)

        tracer.enabled = True
        plane = reqtrace.configure(enabled=True,
                                   ring=REQTRACE_REQUESTS + 64,
                                   slo_p99_ms=REQTRACE_SLO_P99_MS)
        # the serving DEFAULT batching delay (5 ms): the overhead
        # denominator must be a default-configured request's latency,
        # not an artificially tightened one
        batcher = DynamicBatcher(make_predict_runner(engine),
                                 max_batch=8, max_delay_ms=5.0,
                                 queue_depth=64,
                                 group_key=predict_group_key,
                                 name="predict")
        batchers.append(batcher)
        client = InProcessClient(predict_batcher=batcher)

        def request():
            _out, meta = client.predict_ex(x)
            return meta

        rep = run_closed_loop(request,
                              n_requests=REQTRACE_REQUESTS,
                              concurrency=SERVE_BENCH_CONCURRENCY,
                              slo_p99_ms=REQTRACE_SLO_P99_MS)
        batcher.close(drain=False)
        assert rep["ok"] == REQTRACE_REQUESTS and rep["errors"] == 0, rep
        audit = plane.audit_snapshot()
        need = {"admit", "queue_wait", "batch_assembly", "prefill",
                "respond"}
        complete = [s for s in audit if s["disposition"] == "ok"
                    and need <= set(s["phases_ms"])]
        complete_pct = 100.0 * len(complete) / max(len(audit), 1)
        assert len(audit) == REQTRACE_REQUESTS \
            and complete_pct == 100.0, (
            f"{len(complete)}/{len(audit)} of {REQTRACE_REQUESTS} "
            f"requests reconstruct a complete phase timeline — the "
            f"request plane dropped records")
        tail = plane.tail_report()
        slo = plane.slo_report()
        # per-request plane cost, amortized (a throwaway plane with the
        # drill's config so the synthetic records don't pollute the
        # audit facts above), over the drill's measured mean latency
        cost_plane = reqtrace.RequestPlane(
            ring=64, slo_p99_ms=REQTRACE_SLO_P99_MS)
        cost_ms = math.inf
        for _ in range(3):  # best-of-3, as telemetry_phase's span cost
            t0 = _host_cost_clock()
            for _ in range(REQTRACE_COST_SAMPLES):
                tr = cost_plane.begin(reqtrace.new_request_id(),
                                      "predict", x)
                tr.admitted()
                tr.taken()
                tr.run_start()
                tr.note("prefill", 0.0)
                tr.run_end()
                cost_plane.finish(tr, "ok")
            cost_ms = min(cost_ms, (_host_cost_clock() - t0)
                          / REQTRACE_COST_SAMPLES * 1e3)
        mean_ms = rep["latency_ms_mean"]
        overhead = (100.0 * cost_ms / mean_ms if mean_ms > 0 else None)
        assert overhead is not None and overhead < 2.0, (
            f"armed request plane costs {cost_ms:.4f} ms/request = "
            f"{overhead:.2f}% of the {mean_ms:.2f} ms mean request — "
            f"blows the 2% observability budget")
        return {
            "reqtrace_requests_total": len(audit),
            "reqtrace_complete_pct": round(complete_pct, 2),
            "reqtrace_p99_phase":
                tail["exemplars"][0]["dominant_phase"],
            "reqtrace_slo_compliant_pct": slo["compliant_pct"],
            "reqtrace_record_cost_ms": round(cost_ms, 5),
            "reqtrace_overhead_pct": (None if overhead is None
                                      else round(overhead, 3)),
        }
    finally:
        for b in batchers:
            b.close(drain=False)
        # restore whatever plane the process had (the serving replica's
        # configured one in production; None in the test suite)
        reqtrace._PLANE = prev_plane
        tracer.enabled = prev_enabled
        shutil.rmtree(d, ignore_errors=True)


# r12: the efficiency phase — MFU / model-FLOPs / goodput accounting
# (utils/efficiency.py) measured on whatever backend is alive. The
# FLOPs budget is ANALYTIC (per-layer, no chip); the rate measurement
# is a short real train loop on the default backend — the chip in a
# record, the CPU test mesh under degraded_record. MFU is asserted in
# (0, 1] against the spec table's peak: the number must be a real
# utilization, not a unit-error artifact. The CPU mesh's peak is one f32
# matmul's ACHIEVED rate (matmul_calibration), which is no bound: the
# conv step beats it (MFU 1.07-1.17 on XLA:CPU, PR 21), so there the
# assertion only rules out a unit error.
EFFICIENCY_BATCH = 128
EFFICIENCY_STEPS = 6
EFFICIENCY_CALIBRATED_MFU_LIMIT = 10.0


_EFFICIENCY_CACHE: dict = {}


def efficiency_phase() -> dict:
    """Measured MFU/goodput evidence on the flagship CNN: analytic FLOPs
    budget x a short measured step rate over the peak (spec table on
    TPU, cached matmul calibration elsewhere), goodput from the run's
    own compile charge — the same EfficiencyMeter arithmetic every
    training loop emits through.

    Cached per process: one bench run measures at most once (a mid-run
    flap's degraded record would otherwise pay the compile twice, and
    the test suite drives degraded_record many times)."""
    if "out" in _EFFICIENCY_CACHE:
        return dict(_EFFICIENCY_CACHE["out"])
    from distributed_tensorflow_tpu.data import read_data_sets
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.training import (
        adam,
        create_train_state,
        make_train_step,
    )
    from distributed_tensorflow_tpu.utils.efficiency import (
        EfficiencyMeter,
    )

    # f32 end-to-end: the calibration matmul is f32, so the ratio
    # compares like with like on backends without a spec-table peak
    model = DeepCNN()
    opt = adam(1e-3)
    eff = EfficiencyMeter(model, EFFICIENCY_BATCH, 1)
    ds = read_data_sets("/tmp/mnist-data", one_hot=True)
    state = create_train_state(model, opt, seed=0)
    step_fn = make_train_step(model, opt, keep_prob=1.0)
    batch = ds.train.next_batch(EFFICIENCY_BATCH)
    t0 = time.perf_counter()
    state, m = step_fn(state, batch)  # compile
    float(m["loss"])  # hard readback: clock starts clean
    eff.charge(time.perf_counter() - t0, "init")
    t0 = time.perf_counter()
    for _ in range(EFFICIENCY_STEPS):
        state, m = step_fn(state, batch)
    float(m["loss"])
    dt = time.perf_counter() - t0
    rate = EFFICIENCY_STEPS * EFFICIENCY_BATCH / dt
    s = eff.scalars(rate)
    mfu_limit = (EFFICIENCY_CALIBRATED_MFU_LIMIT
                 if eff.peak_source == "matmul_calibration" else 1.0)
    assert 0.0 < s["mfu"] <= mfu_limit, (
        f"flagship-CNN MFU {s['mfu']} outside (0, {mfu_limit}] against "
        f"the {eff.peak_source} peak — the accounting (flops budget x "
        f"rate / peak) is broken")
    assert 0.0 < s["goodput"] <= 1.0, s
    _EFFICIENCY_CACHE["out"] = {
        "mfu": s["mfu"],
        "flops_per_step": eff.flops_per_step,
        "goodput": s["goodput"],
        "model_flops_per_sec": s["model_flops_per_sec"],
        "mfu_peak_flops_per_sec": round(eff.peak_flops_total, 1),
        "mfu_peak_source": eff.peak_source,
        "efficiency_images_per_sec": round(rate, 1),
    }
    return dict(_EFFICIENCY_CACHE["out"])


# r13: the resources phase — the resource plane's evidence
# (utils/resources.py) on whatever backend is alive. The budget and
# comm-ledger facts are ANALYTIC (jax.eval_shape, no chip); the live
# HBM sample and the compile drill run on the default backend — the chip
# in a record, the CPU test mesh under degraded_record (where the sample
# is live-array bytes).
# The compile assertion is the bench contract's recompile pin: exactly
# ONE compile per distinct chunk shape, ZERO on repeats.
RESOURCES_BATCH = 128


_RESOURCES_CACHE: dict = {}


def resources_phase() -> dict:
    """Resource-plane evidence on the flagship CNN: a live memory
    sample cross-checked against the analytic per-chip budget
    (``resource_budget`` — the live/analytic ratio is the artifact's
    sanity number), the compile sentry driven end-to-end (==1 compile
    per distinct chunk shape asserted, 0 on repeats — the no-churn
    claim as a number), and the analytic DP/ZeRO comm-ledger bytes.

    Cached per process (the efficiency_phase pattern): degraded
    records and the test suite drive this repeatedly and must not
    re-pay the jit compiles."""
    if "out" in _RESOURCES_CACHE:
        return dict(_RESOURCES_CACHE["out"])
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.training import (
        adam,
        create_train_state,
    )
    from distributed_tensorflow_tpu.utils import resources

    model = DeepCNN()
    opt = adam(1e-3)
    budget = resources.resource_budget(model, opt, RESOURCES_BATCH)
    led_dp = resources.comm_ledger(model, opt, RESOURCES_BATCH,
                                   mode="dp", data_ways=8)
    led_z1 = resources.comm_ledger(model, opt, RESOURCES_BATCH,
                                   mode="zero1", data_ways=8,
                                   zero_level=1)
    # live sample with the state actually materialized
    state = create_train_state(model, opt, seed=0)
    jax.block_until_ready(state.params)
    meter = resources.MemoryMeter(
        analytic_bytes=budget["per_chip_state_bytes"])
    s = meter.sample(tag="bench")
    assert s is not None and s["in_use"] > 0, s
    ratio = s["in_use"] / max(budget["per_chip_state_bytes"], 1)

    # compile drill: the sentry must count exactly one compile per
    # distinct chunk shape and none on repeats (signature ledger +
    # the jax.monitoring backend-compile listener)
    sentry = resources.CompileSentry()
    prev_meter = resources.active_meter()
    prev_sentry = resources.active_sentry()
    resources.activate(meter=meter, sentry=sentry, budget=budget)
    resources._install_compile_listener()
    try:
        fn = jax.jit(lambda a: (a * 2.0).sum())
        for n in (4, 4, 8, 8, 4):
            x = jnp.ones((n, 16), jnp.float32)
            sentry.observe("bench_chunk", ((n, 16), "float32"))
            jax.block_until_ready(fn(x))
        warm = sentry.compiles_total
        jax.block_until_ready(fn(jnp.ones((8, 16), jnp.float32)))
        repeat_delta = sentry.compiles_total - warm
    finally:
        resources.activate(meter=prev_meter, sentry=prev_sentry,
                           budget=None)
    distinct = sentry.site_signatures("bench_chunk")
    assert distinct == 2, (
        f"{distinct} distinct chunk signatures, expected 2")
    assert sentry.recompiles_total == 1, (
        f"{sentry.recompiles_total} recompiles, expected exactly 1 "
        f"(the second distinct shape) — repeats must not compile")
    assert repeat_delta == 0, (
        f"a repeated shape triggered {repeat_delta} backend "
        f"compile(s) — the executable cache regressed")
    _RESOURCES_CACHE["out"] = {
        "resources_hbm_live_bytes": int(s["in_use"]),
        "resources_hbm_source": s["source"],
        "resources_hbm_analytic_state_bytes":
            int(budget["per_chip_state_bytes"]),
        "resources_live_vs_analytic": round(ratio, 4),
        "resources_compiles_distinct_shapes": distinct,
        "resources_recompiles": int(sentry.recompiles_total),
        "resources_compile_time_s":
            round(sentry.compile_time_s, 4),
        "resources_comm_bytes_dp": led_dp["comm_bytes_per_step"],
        "resources_comm_bytes_zero1": led_z1["comm_bytes_per_step"],
    }
    return dict(_RESOURCES_CACHE["out"])


# r10: the dp_zero phase A/Bs replicated sync DP against --zero 1
# (ZeRO optimizer-state sharding, parallel/zero.py) on the flagship CNN
# in the same session — identical math (bit-identical trajectories,
# tests/test_zero.py), D-fold less optimizer HBM per chip. The memory
# facts are ANALYTIC (jax.eval_shape, host-only) so they stay non-null
# in EVERY record including the host-only one; the A/B rates and
# the measured live-buffer bytes need the chip.
ZERO_TIMED_CHUNKS = 4


def _zero_mem_facts(d: int) -> dict:
    """Analytic per-chip ZeRO memory/comm facts for the flagship CNN
    (zero_memory_budget — no chip, no compute). ``d`` clamps to 2 so
    the 1-chip or host-only record still shows the 2-way fallback config the
    other analytic facts use."""
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.parallel.zero import zero_memory_budget
    from distributed_tensorflow_tpu.training import adam

    d = max(2, int(d))
    b = zero_memory_budget(DeepCNN(compute_dtype=jnp.bfloat16),
                           adam(1e-3), d)
    per = b["per_chip"]
    total = lambda k: sum(per[k].values())
    g = b["param_bytes"]
    return {
        "zero_data_ways": d,
        "zero_opt_bytes_per_chip": per["zero1"]["opt"],
        "zero_opt_bytes_per_chip_replicated": per["replicated"]["opt"],
        "zero_opt_reduction": round(b["opt_reduction"], 3),
        "zero3_param_bytes_per_chip": per["zero3"]["params"],
        "zero_param_reduction": round(b["param_reduction"], 3),
        "zero_total_bytes_per_chip_analytic": total("zero1"),
        "dp_total_bytes_per_chip_analytic": total("replicated"),
        "zero_comm_bytes_allreduce": 2 * g,
        "zero_comm_bytes_reduce_scatter_gather": g + b["param_bytes"],
    }


def _live_bytes_per_chip():
    """Mean live-buffer bytes per local device via device.memory_stats()
    — None where the backend doesn't report (CPU)."""
    try:
        stats = [dev.memory_stats() for dev in jax.local_devices()]
        vals = [s["bytes_in_use"] for s in stats
                if s and "bytes_in_use" in s]
        return int(sum(vals) / len(vals)) if vals else None
    except Exception:  # noqa: BLE001 — absence of the stat, not an error
        return None


def dp_zero_phase(ds, n_chips) -> dict:
    """Same-session A/B: replicated sync DP vs --zero 1 on the flagship
    CNN over the device-resident input path (identical sampling — the
    trajectories are bit-identical, so the A/B isolates the collective
    pattern + memory layout). Records the measured rates and live-buffer
    bytes where the backend reports them (``device.memory_stats()``;
    analytic totals stand in where it doesn't, ``zero_live_bytes_source``
    says which), on top of the always-recorded analytic facts."""
    out = _zero_mem_facts(n_chips)
    out.update({
        "dp_ab_images_per_sec_per_chip": None,
        "zero_images_per_sec_per_chip": None,
        "zero_live_bytes_per_chip": out["zero_total_bytes_per_chip_analytic"],
        "dp_live_bytes_per_chip": out["dp_total_bytes_per_chip_analytic"],
        "zero_live_bytes_source": "analytic",
    })
    if n_chips < 2:
        out["zero_skipped"] = "needs a >1-chip data axis"
        return out

    from distributed_tensorflow_tpu.data.device_data import put_device_data
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.parallel import make_mesh
    from distributed_tensorflow_tpu.parallel.data_parallel import (
        replicate_state,
    )
    from distributed_tensorflow_tpu.parallel.zero import shard_state_zero
    from distributed_tensorflow_tpu.training import adam, create_train_state
    from distributed_tensorflow_tpu.training.device_step import (
        make_device_dp_train_step,
        make_zero_device_train_step,
    )

    model = DeepCNN(compute_dtype=jnp.bfloat16)
    opt = adam(1e-3)
    mesh = make_mesh()
    batch_size = PER_CHIP_BATCH * n_chips
    data = put_device_data(ds.train, mesh)
    sync_every = _sync_every(n_chips)
    rates = {}
    live = {}
    for name in ("replicated", "zero1"):
        state = create_train_state(model, opt, seed=0)
        if name == "replicated":
            state = replicate_state(mesh, state)
            chunk_fn = make_device_dp_train_step(
                model, opt, mesh, batch_size, keep_prob=0.75, chunk=CHUNK)
        else:
            state = shard_state_zero(state, mesh, 1)
            chunk_fn = make_zero_device_train_step(
                model, opt, mesh, 1, batch_size, keep_prob=0.75,
                chunk=CHUNK)
        state, m = chunk_fn(state, data)  # compile + upload
        float(m["loss"])  # hard readback so the clock starts clean
        live[name] = _live_bytes_per_chip()
        t0 = time.perf_counter()
        for c in range(1, ZERO_TIMED_CHUNKS + 1):
            state, m = chunk_fn(state, data)
            if sync_every and (c * CHUNK) % sync_every < CHUNK:
                jax.block_until_ready(state.params)
        jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0
        rates[name] = ZERO_TIMED_CHUNKS * CHUNK * batch_size / dt / n_chips
        del state
    out["dp_ab_images_per_sec_per_chip"] = round(rates["replicated"], 1)
    out["zero_images_per_sec_per_chip"] = round(rates["zero1"], 1)
    if live["zero1"] is not None and live["replicated"] is not None:
        out.update({"zero_live_bytes_per_chip": live["zero1"],
                    "dp_live_bytes_per_chip": live["replicated"],
                    "zero_live_bytes_source": "memory_stats"})
    return out


# r14: the overlap phase A/Bs the remaining on-device stalls' fixes in
# one session — (a) the three pipeline schedules (gpipe / interleaved /
# zero-bubble, --pp_schedule) on the 8-block model: zb splits backward
# into B/W ticks and fills the cooldown with deferred weight grads, so
# its analytic useful-tick fraction strictly exceeds interleaved at the
# same (K, M, V); (b) ZeRO comm/compute overlap (--zero_overlap) on vs
# off at levels 1 and 3 on the flagship CNN. The schedule fractions and
# exposed-comm bytes are ANALYTIC (no chip) and recorded in EVERY
# record including the host-only one; the A/B rates need chips.
OVERLAP_TIMED_CHUNKS = 3
OVERLAP_BUCKET_MB = 4.0

_OVERLAP_RATE_KEYS = (
    "overlap_pp_gpipe_images_per_sec_per_chip",
    "overlap_pp_interleaved_images_per_sec_per_chip",
    "overlap_pp_zb_images_per_sec_per_chip",
    "pp_zb_speedup_vs_interleaved",
    "zero1_serial_images_per_sec_per_chip",
    "zero1_overlap_images_per_sec_per_chip",
    "zero3_serial_images_per_sec_per_chip",
    "zero3_overlap_images_per_sec_per_chip",
)


def _pp_zb_virtual_stages(ways: int) -> int:
    """Virtual-stage count for the zb arm: the largest candidate whose
    groups still hold >= 2 blocks (the zb bit-identity constraint) —
    V=1 always qualifies on the 8-block model at 2/4 ways."""
    for v in (PP_VIRTUAL_STAGES, 1):
        if PP_NUM_BLOCKS % (ways * v) == 0 \
                and PP_NUM_BLOCKS // (ways * v) >= 2:
            return v
    return 1


_overlap_facts_cache: dict = {}


def _overlap_analytic_facts(ways: int, d: int) -> dict:
    """The overlap phase's chip-free facts: per-schedule useful-tick
    fractions at ONE shared (K, M, V) config (so the zb-vs-interleaved
    comparison is apples-to-apples), and the ZeRO exposed-comm bytes
    serial vs overlapped for the flagship CNN. Cached per process (the
    efficiency_phase pattern): the degraded record and the test suite
    both drive this repeatedly."""
    key = (max(2, int(ways)), max(2, int(d)), PP_NUM_BLOCKS)
    hit = _overlap_facts_cache.get(key)
    if hit is not None:
        return dict(hit)
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.parallel.pp_schedule import (
        build_zb_schedule,
        schedule_useful_fraction,
    )
    from distributed_tensorflow_tpu.parallel.zero import (
        n_buckets,
        zero_exposed_comm_bytes,
        zero_memory_budget,
    )

    ways = max(2, int(ways))
    d = max(2, int(d))
    v = _pp_zb_virtual_stages(ways)
    zb = build_zb_schedule(ways, ways, v)
    out = {
        "pp_overlap_stages": ways,
        "pp_overlap_microbatches": ways,
        "pp_zb_virtual_stages": v,
        "pp_gpipe_useful_tick_fraction": round(
            schedule_useful_fraction("gpipe", ways, ways, 1), 4),
        "pp_interleaved_useful_tick_fraction": round(
            schedule_useful_fraction("interleaved", ways, ways, v), 4),
        "pp_zb_useful_tick_fraction": round(
            zb.useful_tick_fraction, 4),
        "pp_zb_ticks": zb.num_ticks,
    }
    model = DeepCNN(compute_dtype=jnp.bfloat16)
    from distributed_tensorflow_tpu.training import adam

    g = zero_memory_budget(model, adam(1e-3), d)["param_bytes"]
    out.update({
        "zero_overlap_bucket_mb": OVERLAP_BUCKET_MB,
        "zero_overlap_buckets": n_buckets(model, d,
                                          OVERLAP_BUCKET_MB),
    })
    for lv in (1, 3):
        out[f"zero{lv}_exposed_comm_bytes_serial"] = \
            zero_exposed_comm_bytes(g, g, lv, d, False,
                                    OVERLAP_BUCKET_MB)
        out[f"zero{lv}_exposed_comm_bytes_overlap"] = \
            zero_exposed_comm_bytes(g, g, lv, d, True,
                                    OVERLAP_BUCKET_MB)
    _overlap_facts_cache[key] = dict(out)
    return out


def overlap_phase(ds, n_chips) -> dict:
    """Same-session A/B of the r14 stall killers. PP half: gpipe vs
    interleaved vs zero-bubble (--pp_schedule) on the 8-block model
    over the device-resident sampler — identical math (bit-identical
    trajectories, tests/test_pp_zb.py), only the tick schedule changes.
    ZeRO half: --zero_overlap on vs off at levels 1 and 3 on the
    flagship CNN — identical math again (bucketed collectives + the
    level-3 prefetched gather). Analytic facts (per-schedule
    useful-tick fractions, exposed-comm bytes) always recorded; the
    measured rates need a multi-chip mesh and stay null without one."""
    ways = _ppep_model_ways(n_chips, PP_NUM_BLOCKS)
    out = _overlap_analytic_facts(ways or 2, n_chips)
    out.update({k: None for k in _OVERLAP_RATE_KEYS})
    if n_chips < 2:
        out["overlap_skipped"] = "needs a >1-chip mesh"
        return out

    from distributed_tensorflow_tpu.data.device_data import put_device_data
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.models.transformer import TransformerLM
    from distributed_tensorflow_tpu.parallel import MeshSpec, make_mesh
    from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS
    from distributed_tensorflow_tpu.parallel.pipeline_parallel import (
        shard_state_pp,
    )
    from distributed_tensorflow_tpu.parallel.zero import shard_state_zero
    from distributed_tensorflow_tpu.training import adam, create_train_state
    from distributed_tensorflow_tpu.training.device_step import (
        make_pp_device_train_step,
        make_zero_device_train_step,
    )

    if ways:
        mesh = make_mesh(MeshSpec(data=-1, model=ways))
        data_ways = mesh.shape[DATA_AXIS]
        batch = PP_EP_BATCH_PER_DATA_WAY * data_ways
        model = TransformerLM(
            vocab_size=PP_EP_VOCAB, seq_len=PP_EP_SEQ_LEN,
            d_model=PP_EP_D_MODEL, num_heads=4,
            num_blocks=PP_NUM_BLOCKS, compute_dtype=jnp.bfloat16)
        opt = adam(1e-3)
        lm = LMDataSet(PP_EP_SPLIT, seq_len=PP_EP_SEQ_LEN,
                       vocab_size=PP_EP_VOCAB, seed=0)
        data = put_device_data(lm, mesh, data_sharded=True)
        v_zb = _pp_zb_virtual_stages(ways)
        arms = [("gpipe", 1), ("interleaved", v_zb), ("zb", v_zb)]
        rates = {}
        for sched, v in arms:
            # fresh base per arm (see pp_device_phase: device_put can
            # alias host leaves the donated step then deletes)
            base = create_train_state(model, opt, seed=0)
            state = shard_state_pp(base, mesh, virtual_stages=v)
            fn = make_pp_device_train_step(
                model, opt, mesh, batch, ways, keep_prob=1.0,
                chunk=PP_EP_CHUNK, virtual_stages=v, schedule=sched)
            dt = _time_resident_chunks(fn, state, data, PP_EP_CHUNK,
                                       OVERLAP_TIMED_CHUNKS, n_chips)
            rates[sched] = (OVERLAP_TIMED_CHUNKS * PP_EP_CHUNK * batch
                            / dt / n_chips)
        for sched in ("gpipe", "interleaved", "zb"):
            out[f"overlap_pp_{sched}_images_per_sec_per_chip"] = round(
                rates[sched], 1)
        out["pp_zb_speedup_vs_interleaved"] = round(
            rates["zb"] / rates["interleaved"], 3)
    else:
        out["overlap_pp_skipped"] = (f"no 2/4-way model axis over "
                                     f"{n_chips} chip(s)")

    cnn = DeepCNN(compute_dtype=jnp.bfloat16)
    opt = adam(1e-3)
    mesh = make_mesh()
    batch_size = PER_CHIP_BATCH * n_chips
    data = put_device_data(ds.train, mesh)
    for level in (1, 3):
        for overlap in (False, True):
            state = shard_state_zero(
                create_train_state(cnn, opt, seed=0), mesh, level)
            fn = make_zero_device_train_step(
                cnn, opt, mesh, level, batch_size, keep_prob=0.75,
                chunk=CHUNK, overlap=overlap,
                bucket_mb=OVERLAP_BUCKET_MB)
            dt = _time_resident_chunks(fn, state, data, CHUNK,
                                       OVERLAP_TIMED_CHUNKS, n_chips)
            rate = (OVERLAP_TIMED_CHUNKS * CHUNK * batch_size
                    / dt / n_chips)
            key = "overlap" if overlap else "serial"
            out[f"zero{level}_{key}_images_per_sec_per_chip"] = round(
                rate, 1)
            del state
    return out


def recovery_phase() -> dict:
    """Verified-restore drill (r8): save two checkpoints of a small host
    state, TEAR the newest mid-file (the machine-crash signature the
    fsync discipline now prevents, forged directly), and restore through
    the fallback ladder — measuring time-to-restore and recording the
    ladder's observability fields. HOST-ONLY (no chip, no mesh), so the
    ``recovery_*`` fields stay NON-NULL in the host-only record
    too."""
    import os
    import shutil
    import tempfile

    import numpy as np

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        restore_with_fallback,
        save_checkpoint,
    )

    d = tempfile.mkdtemp(prefix="bench-recovery-")
    try:
        state = {"params": {"w": np.arange(65536, dtype=np.float32)},
                 "step": np.int64(0)}
        save_checkpoint(d, dict(state, step=np.int64(10)), 10)
        save_checkpoint(d, dict(state, step=np.int64(20)), 20)
        newest = os.path.join(d, "ckpt-20.npz")
        with open(newest, "r+b") as f:
            f.truncate(os.path.getsize(newest) // 2)
        t0 = time.perf_counter()
        # the ladder narrates quarantines on stdout; bench's stdout
        # contract is ONE JSON line — route the narration to stderr
        import sys

        with contextlib.redirect_stdout(sys.stderr):
            out = restore_with_fallback(d, state)
        dt = time.perf_counter() - t0
        assert out is not None
        _, step, report = out
        return {
            "recovery_restore_step": int(step),
            "recovery_fallback_depth": int(report.fallback_depth),
            "recovery_quarantined": len(report.quarantined),
            "recovery_time_s": round(dt, 4),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def lint_phase() -> dict:
    """dttlint drill (r16): run the AST invariant linter over the whole
    walk set with the checked-in baseline. HOST-ONLY (pure ``ast``, no
    jax, no chip), so the ``lint_*`` facts stay NON-NULL in EVERY
    record including the host-only one, per the bench contract —
    PROGRESS tracks ``lint_baselined_total`` trending to zero (the
    baseline can only shrink: stale suppressions fail the run)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.dttlint import run_lint

    t0 = time.perf_counter()
    res = run_lint()
    return {
        "lint_findings_total": len(res.findings),
        "lint_baselined_total": len(res.baselined),
        "lint_stale_suppressions": len(res.stale),
        "lint_rules": len(res.rules),
        "lint_time_s": round(time.perf_counter() - t0, 3),
    }


def consan_phase() -> dict:
    """dttsan drill (r20): run the static concurrency analyzer over the
    whole walk set with the checked-in baseline + thread registry.
    HOST-ONLY (pure ``ast``, no jax, no chip), so the ``consan_*``
    facts stay NON-NULL in EVERY record including the host-only
    one, per the bench contract — PROGRESS tracks
    ``consan_findings_total`` staying at zero (the host plane's
    threads/locks/rings stay machine-proven race-free as the tree
    grows) with ``consan_threads_total`` counting the live concurrent
    roots the registry pins."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.dttsan import run_san

    t0 = time.perf_counter()
    res = run_san()
    return {
        "consan_findings_total": len(res.findings) + len(res.stale),
        "consan_baselined_total": len(res.baselined),
        "consan_threads_total": res.report["threads_total"],
        "consan_locks_total": res.report["locks_total"],
        "consan_shared_attrs": res.report["shared_attrs"],
        "consan_time_s": round(time.perf_counter() - t0, 3),
    }


_JAXPRCHECK_CACHE: dict = {}


def jaxprcheck_phase() -> dict:
    """dttcheck drill (r18): run the jaxpr-level ledger/SPMD verifier
    over the full (mode x model) scenario matrix in a SUBPROCESS with
    a forced 8-device virtual CPU mesh — host-only by construction
    (trace + tiny CPU HLO compiles, no chip), so the ``jaxprcheck_*``
    facts stay NON-NULL in EVERY record including the host-only
    one, per the bench contract. A subprocess because this process's
    jax may already be bound to real chips (or a 1-device CPU
    fallback), and the verifier's mesh must exist BEFORE jax
    initializes. PROGRESS tracks ``jaxprcheck_findings_total`` staying
    at zero with ``jaxprcheck_modes_proven`` covering the whole mode
    matrix — the analytic comm ledgers stay machine-proven against
    the lowered computation as the tree grows. Cached per process (the
    efficiency_phase pattern): the full record AND the degraded record
    both emit the facts, and the proof subprocess costs ~9s — the
    matrix cannot change mid-process."""
    import os
    import subprocess
    import sys

    if "out" in _JAXPRCHECK_CACHE:
        return dict(_JAXPRCHECK_CACHE["out"])
    t0 = time.perf_counter()
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    p = subprocess.run(
        [sys.executable, "-m", "tools.dttcheck", "--json"],
        capture_output=True, text=True, timeout=240,
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env)
    out = json.loads(p.stdout.strip().splitlines()[-1])
    report = out.get("report", {})
    _JAXPRCHECK_CACHE["out"] = {
        "jaxprcheck_findings_total": len(out.get("findings", ())),
        "jaxprcheck_modes_proven": len(
            report.get("modes_proven", ())),
        "jaxprcheck_collectives_total":
            report.get("collectives_total"),
        "jaxprcheck_time_s": round(time.perf_counter() - t0, 3),
    }
    return dict(_JAXPRCHECK_CACHE["out"])


_PERFCHECK_CACHE: dict = {}


def perfcheck_phase() -> dict:
    """dttperf drill (r23): run the performance-contract analyzer —
    predicted step time per canonical (mode x model) cell from the
    verified analytics, banded against the measured record rates, plus
    the fact-coverage and wall-time-budget closures. HOST-ONLY (pure
    Python + ``jax.eval_shape``, no chip), so the ``perfcheck_*`` facts
    stay NON-NULL in EVERY record including the host-only one,
    per the bench contract. PROGRESS tracks ``perfcheck_findings_total``
    staying at zero (findings + stale suppressions: an out-of-band rate
    means this tree made a step slower than the analytic band allows,
    a stale entry means a dead suppression lingers) with
    ``perfcheck_band_pct`` holding the in-band share of banded record
    rates. Cached per process (the jaxprcheck pattern): the full record
    AND the degraded record both emit the facts, and the full pass
    costs ~10s — the matrix cannot change mid-process."""
    if "out" in _PERFCHECK_CACHE:
        return dict(_PERFCHECK_CACHE["out"])
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.dttperf import run_perf

    t0 = time.perf_counter()
    res = run_perf()
    _PERFCHECK_CACHE["out"] = {
        "perfcheck_findings_total":
            len(res.findings) + len(res.stale),
        "perfcheck_scenarios_proven":
            res.report["scenarios_proven"],
        "perfcheck_band_pct": res.report["in_band_pct"],
        "perfcheck_time_s": round(time.perf_counter() - t0, 3),
    }
    return dict(_PERFCHECK_CACHE["out"])


def elastic_phase() -> dict:
    """Elastic-resize drill (r15): drive the detect -> drain -> adopt ->
    restore ladder end to end on a tiny host state — the REAL machinery
    (the ``preempt`` injection point, ``ElasticSupervisor.poll``/
    ``maybe_resize``, sentinel-snapshot adoption, the CRC-verified
    fallback restore, the membership epoch in cluster.py). HOST-ONLY
    (no mesh, no compiled step), so the ``elastic_*`` facts stay
    NON-NULL in the host-only record too, per the bench
    contract. The scenario is the lost-step worst case: an
    IMMEDIATE preemption (no drain save) whose sentinel emergency
    snapshot is newer than the last cadenced checkpoint but lands torn
    (the capacity died mid-write), so adoption AND the fallback ladder
    both engage."""
    import os
    import shutil
    import sys
    import tempfile
    import types

    import numpy as np

    from distributed_tensorflow_tpu import cluster
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        restore_with_fallback,
        save_checkpoint,
    )
    from distributed_tensorflow_tpu.training import elastic
    from distributed_tensorflow_tpu.utils import faults

    d = tempfile.mkdtemp(prefix="bench-elastic-")
    try:
        t0 = time.perf_counter()
        flags_ns = types.SimpleNamespace(logdir=d, worker_hosts="",
                                         task_index=0, world_size=2,
                                         elastic=True)
        with contextlib.redirect_stdout(sys.stderr):  # stdout stays JSON
            # a fresh elastic run at a 2-member world (resets the
            # handled-departure registry, so the drill is re-runnable)
            elastic.begin_run(flags_ns)
            faults.configure(
                "preempt:at_step=10:mode=immediate:host=1")
            es = elastic.ElasticSupervisor()
            assert not es.poll(8)   # unarmed boundary: no change
            assert es.poll(10)      # the preemption fires here
            state = {"params": {"w": np.arange(65536, dtype=np.float32)},
                     "step": np.int64(0)}
            # the last cadenced checkpoint (step 8) predates the loss
            save_checkpoint(d, dict(state, step=np.int64(8)), 8)
            # the sentinel's last-good emergency snapshot is newer...
            save_checkpoint(os.path.join(d, "sentinel"),
                            dict(state, step=np.int64(10)), 10)
            try:
                es.maybe_resize(12)
                raise AssertionError("maybe_resize did not resize")
            except elastic.ResizeRequired as rz:
                elastic.apply_resize(rz, flags_ns)  # adopts the snapshot
                drain_steps = rz.drain_steps
            # ...but landed torn (the capacity died mid-write): the
            # ladder must quarantine it and walk back to step 8
            adopted = os.path.join(d, "ckpt-10.npz")
            with open(adopted, "r+b") as f:
                f.truncate(os.path.getsize(adopted) // 2)
            out = restore_with_fallback(d, state)
            assert out is not None
            _, restore_step, report = out
            elastic.book_resize(None, None, restore_step)  # close+record
        return {
            "elastic_world": "2->1",
            "elastic_epoch": cluster.membership_epoch(),
            "elastic_drain_steps": int(drain_steps),
            "elastic_restore_step": int(restore_step),
            "elastic_restore_fallback_depth": int(report.fallback_depth),
            "elastic_resize_s": round(time.perf_counter() - t0, 4),
        }
    finally:
        faults.reset()
        cluster.reset_membership()
        shutil.rmtree(d, ignore_errors=True)


def degraded_record(error, partial: dict | None = None,
                    tpu_unavailable: bool = True) -> dict:
    """The host-only half of a record: the headline keys null (they
    need the chip), the error string, and every analytic or host-only
    fact non-null; ``partial`` overrides. Nothing prints this in place
    of a result — ``main()`` fails when the chip is not there or a
    phase raises. It stays because dttperf DTP002 and the tests hold
    every host-only phase to be wired here as well as in
    ``_run_phases``."""
    out = {
        "metric": "mnist_images_per_sec_per_chip",
        "value": None,
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "pp_images_per_sec_per_chip": None,
        "ep_tokens_per_sec_per_chip": None,
        "tpu_unavailable": bool(tpu_unavailable),
        "phase_error": not tpu_unavailable,
        "error": str(error)[:300],
    }
    # schedule-level facts are ANALYTIC (no chip required; 2-way
    # config — `partial` overrides with a measured one)
    out.update(_pp_schedule_facts(2))
    # the ZeRO memory/comm facts are analytic too (jax.eval_shape):
    # the D-fold optimizer-state saving stays auditable without the chip
    # (2-way fallback config; the A/B rates need the chip and stay null)
    zmem = _zero_mem_facts(2)
    out.update(zmem)
    out.update({"dp_ab_images_per_sec_per_chip": None,
                "zero_images_per_sec_per_chip": None,
                "zero_live_bytes_per_chip":
                    zmem["zero_total_bytes_per_chip_analytic"],
                "dp_live_bytes_per_chip":
                    zmem["dp_total_bytes_per_chip_analytic"],
                "zero_live_bytes_source": "analytic"})
    # r14: the overlap phase's schedule fractions and exposed-comm
    # bytes are analytic too — non-null without the chip, per the bench
    # contract (the A/B rates need chips and stay null)
    out.update(_overlap_analytic_facts(2, 2))
    out.update({k: None for k in _OVERLAP_RATE_KEYS})
    # the restore-ladder, serving, and telemetry drills are host-only:
    # the recovery_*/serving_*/telemetry_* fields stay non-null in
    # EVERY record, chip or not (the telemetry A/B needs the chip
    # and its overhead_pct stays null here)
    out.update(recovery_phase())
    out.update(serving_phase())
    # r22: the fleet-router drill is host-only too — router_* facts
    # stay non-null in EVERY record incl. host-only
    out.update(router_phase())
    # r21: the continuous-batching page-ledger facts are analytic
    # (zero-step-cost drill) and stay non-null without the chip; the knee
    # A/B is a wall-clock rate sweep and stays null here, like the
    # chip-gated A/Bs
    out.update(continuous_batching_phase(measured=False))
    # r19: the request-plane drill rides the same host-only contract —
    # reqtrace_* facts stay non-null in EVERY record
    out.update(reqtrace_phase())
    out.update(telemetry_phase())
    # r12: MFU/goodput facts — analytic FLOPs budget x a measured CPU
    # step rate over the calibrated peak; non-null in the host-only record
    out.update(efficiency_phase())
    # r13: resource-plane facts — the budget/ledger halves are analytic
    # and the live sample/compile drill run on the CPU test mesh, so
    # every resources_* field stays non-null in the host-only record too
    out.update(resources_phase())
    # r15: the elastic-resize drill is host-only like the recovery
    # drill — detect/adopt/restore facts stay non-null without the chip
    out.update(elastic_phase())
    # r16: the dttlint drill is pure ast — the static-invariant facts
    # (findings/baseline trend) stay non-null without the chip too
    out.update(lint_phase())
    # r20: the dttsan drill is pure ast too — the concurrency-proof
    # facts (thread/lock/ring census) stay non-null without the chip
    out.update(consan_phase())
    # r18: the dttcheck drill runs in its own CPU-mesh subprocess —
    # the jaxpr-proof facts stay non-null without the chip too
    out.update(jaxprcheck_phase())
    # r23: the dttperf drill is host-only (analytics + eval_shape) —
    # the performance-contract facts stay non-null without the chip too
    out.update(perfcheck_phase())
    if partial:
        out.update(partial)
    return out


def _require_tpu() -> dict:
    """The device this record is measured on, as JAX reports it — printed
    first, carried in the record. Anything but a TPU ends the run before
    a phase starts: a rate taken elsewhere is not this benchmark's."""
    import sys

    d = jax.devices()  # raises where the backend cannot start
    device = {"platform": d[0].platform, "device_kind": d[0].device_kind,
              "n_chips": len(d)}
    print(f"bench: platform={device['platform']} "
          f"device_kind={device['device_kind']!r} "
          f"count={device['n_chips']}", flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"bench.py measures the chip and the platform is "
                 f"{device['platform']!r}, not 'tpu': no phase ran, "
                 f"no record is printed")
    return device


def main():
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    out = _require_tpu()
    # the product's fast-PRNG mode (--prng rbg, mnist_dist.py): hardware
    # RNG for dropout masks and on-device batch sampling. Scoped, and set
    # here rather than at import time: this module is imported by tests,
    # and an unscoped config flip leaks into everything that runs after.
    # The baseline phases are scoped back to threefry inside. A phase
    # that raises ends the run with its traceback: no record is printed.
    with _prng("rbg"):
        _run_phases(out)


def _run_phases(out: dict):
    """Run every phase, accumulating fields into ``out`` as each completes
    (``out`` arrives carrying platform, device_kind and n_chips), then
    print the one-line JSON artifact."""
    from distributed_tensorflow_tpu.data import read_data_sets

    n_chips = out["n_chips"]
    ds = read_data_sets("/tmp/mnist-data", one_hot=True)
    out["data_source"] = ds.source

    per_chip = device_resident_phase(ds, n_chips)
    out.update({
        "metric": "mnist_images_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / IMPLIED_BASELINE_IMAGES_PER_SEC_PER_CHIP, 3),
        "global_batch": PER_CHIP_BATCH * n_chips,
        "input": "device_resident",
    })
    out["wire_images_per_sec_per_chip"] = round(throughput_phase(ds, n_chips), 1)
    out.update(convergence_phase(ds, n_chips))
    # BASELINE config 3: Fashion-MNIST through the same drop-in loader
    # (reference parity: swap the data_dir, MNISTDist.py:167). Real IDX
    # files when present in /tmp/fashion-mnist-data, else the procedural
    # fallback — "fashion_data_source" says which. The 0.85 target is the
    # classic achievable bar for this CNN on real Fashion-MNIST.
    ds_fashion = read_data_sets("/tmp/fashion-mnist-data", one_hot=True,
                                dataset="fashion_mnist")
    fashion = convergence_phase(ds_fashion, n_chips,
                                target_acc=FASHION_TARGET_ACC,
                                max_steps=FASHION_MAX_STEPS)
    out.update({
        "fashion_test_accuracy": fashion["test_accuracy"],
        "fashion_seconds_to_target": fashion["seconds_to_target"],
        "fashion_steps_to_target": fashion["steps_to_target"],
        "fashion_target_accuracy": fashion["target_accuracy"],
        "fashion_data_source": ds_fashion.source,
    })
    # baseline phases measure the REFERENCE's configuration: keep them on
    # threefry so the product's rbg speedup can't deflate the comparison
    with _prng("threefry2x32"):
        feeddict = feeddict_baseline_phase(ds, n_chips)
    out["feeddict_images_per_sec_per_chip"] = round(feeddict, 1)
    out["vs_feeddict"] = round(per_chip / feeddict, 3)
    resnet, resnet_source = resnet_phase(n_chips)
    out["resnet20_cifar10_images_per_sec_per_chip"] = round(resnet, 1)
    out["resnet_data_source"] = resnet_source
    with _prng("threefry2x32"):
        out["ps_emulation_images_per_sec"] = round(ps_emulation_phase(ds), 1)
        out["ps_emulation_bf16_images_per_sec"] = round(
            ps_emulation_phase(ds, wire="bf16"), 1)
    out.update(lm_longctx_phase())
    out.update(lm_largevocab_phase())
    # r6: the parallelism matrix's last structural gap closed — PP/EP
    # over the device-resident input path (skipped fields on 1 chip)
    out.update(pp_device_phase(n_chips))
    out.update(ep_device_phase(n_chips))
    # r10: ZeRO-sharded DP A/B — replicated vs --zero 1, flagship CNN,
    # device-resident input (analytic memory facts + measured rates)
    out.update(dp_zero_phase(ds, n_chips))
    # r14: the stall killers — pipeline-schedule A/B (gpipe vs
    # interleaved vs zero-bubble) + ZeRO comm overlap on-vs-off
    out.update(overlap_phase(ds, n_chips))
    # r8: the verified-restore drill (host-only; also runs in the
    # degraded record so the recovery fields are never null)
    out.update(recovery_phase())
    # r9: the serving drill (host-only for the same reason) — offered
    # load through the real engine/batcher/hot-reload machinery
    out.update(serving_phase())
    # r22: the fleet-router drill (host-only 2-replica fleet) —
    # dispatch spread, breaker trip/recover, hedge, drain-on-503
    out.update(router_phase())
    # r21: continuous batching vs whole-batch on the long-tail mix
    # (host-only A/B at equal per-iteration cost) + page-ledger facts
    out.update(continuous_batching_phase())
    # r19: the request-plane drill (host-only) — per-request phase
    # timelines, tail attribution, and SLO compliance through the
    # armed plane, with the on-vs-off serving A/B
    out.update(reqtrace_phase())
    # r11: telemetry — host-only span-overhead/breakdown drill, then
    # the chip A/B (telemetry on vs off on the flagship chunk loop)
    # overwriting the synthetic breakdown with the measured one
    out.update(telemetry_phase())
    out.update(telemetry_ab_phase(ds, n_chips))
    # r12: MFU / model-FLOPs / goodput accounting on the live backend
    out.update(efficiency_phase())
    # r13: the resource plane — live-vs-analytic HBM, the compile
    # drill, and the analytic comm-ledger bytes
    out.update(resources_phase())
    # r15: the elastic-resize drill (host-only; also runs in the
    # degraded record so the elastic facts are never null)
    out.update(elastic_phase())
    # r16: dttlint over the whole tree — the suppression count is a
    # tracked headline (trending to zero), and a nonzero finding count
    # in a bench record means the tree shipped a new invariant break
    out.update(lint_phase())
    # r20: dttsan over the whole tree — the host plane's threads, locks
    # and rings stay machine-proven race-free (a nonzero finding count
    # means the tree shipped a new concurrency hazard)
    out.update(consan_phase())
    # r18: dttcheck — the comm ledgers and SPMD safety machine-proven
    # against the lowered jaxpr for the full mode matrix (subprocess
    # with its own virtual CPU mesh; a nonzero finding count means an
    # analytic ledger drifted from what the compiler actually lowers)
    out.update(jaxprcheck_phase())
    # r23: dttperf — the step-time predictions banded against this very
    # record's measured rates (a nonzero finding count means a rate
    # left its analytic band: a named performance regression)
    out.update(perfcheck_phase())

    print(json.dumps(out))


if __name__ == "__main__":
    main()
