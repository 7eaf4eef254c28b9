"""Chunked train steps over device-resident data (see data/device_data.py).

Two builders mirroring the host-fed pair (``make_train_step`` /
``make_dp_train_step``) but with the input side moved INSIDE the compiled
program: each step draws its minibatch on device by PRNG gather from the
resident split, and ``lax.scan`` runs ``chunk`` steps per dispatch so the
host's per-step role shrinks to one function call per chunk. This is the
TPU-native answer to the reference's per-step feed_dict upload
(``MNISTDist.py:179,188``): nothing crosses the host boundary during
training at all.

Returned metrics are the LAST in-chunk step's training metrics (loss /
accuracy of the train pass, dropout on). The host-fed loop's display
semantics (dropout-off eval of the upcoming batch, ``MNISTDist.py:179-182``)
need the batch on the host, so this fast mode trades that for speed —
documented on the ``--device_data`` flag.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS
from distributed_tensorflow_tpu.training.train_state import (
    TrainState,
    apply_augment,
    apply_updates,
    loss_and_metrics,
)
from distributed_tensorflow_tpu.utils.profiling import scoped

_SAMPLE_SALT = 0x5EED  # folds the sampling stream away from the dropout stream
_NOISE_SALT = 0xD1FF  # folds the objective's noise away from the sampling


@scoped("sample_batch")
def _split_and_sample(state: TrainState, data, batch_size: int,
                      axis: str | None, augment_fn, noise_fn=None):
    """The ONE rng-evolution + on-device batch-draw rule every sampled
    step body shares (``_sampled_step_body`` and the ZeRO device step —
    their bit-identity contract is this function being common, not two
    copies kept in lockstep): returns ``(next_rng, dropout_sub, batch)``.
    ``state.rng`` advances every step, so the sampling key (a salted
    fold of it) yields a fresh batch each iteration of a scan.
    ``noise_fn(batch, key)`` (a model's ``noise_batch``: the masked-
    diffusion objective's mask and t) turns the sampled rows into what the
    loss takes, under a key folded from the sampling key."""
    rng, sub = jax.random.split(state.rng)
    samp = jax.random.fold_in(state.rng, _SAMPLE_SALT)
    if axis is not None:
        # distinct sample + dropout streams per data shard
        samp = jax.random.fold_in(samp, lax.axis_index(axis))
        sub = jax.random.fold_in(sub, lax.axis_index(axis))
    idx = jax.random.randint(samp, (batch_size,), 0, data.num_examples)
    batch = (data.images[idx], data.labels[idx])
    if augment_fn is not None:
        # samp is already per-shard (axis fold above), so the salted
        # augment stream decorrelates across shards too
        batch = apply_augment(augment_fn, batch, samp)
    if noise_fn is not None:
        batch = noise_fn(batch, jax.random.fold_in(samp, _NOISE_SALT))
    return rng, sub, batch


def _sampled_step_body(model, optimizer, batch_size: int, keep_prob: float,
                       axis: str | None, grad_transform=None,
                       batch_sharding=None, augment_fn=None):
    """(state, data) -> (state, metrics): one full train step — on-device
    batch sample (``_split_and_sample``), forward, backward, (pmean over
    ``axis`` if set), update. ``batch_sharding`` (global-view/GSPMD
    callers only) constrains the sampled batch's layout so the
    partitioner splits the compute over the data axis."""

    def body(state: TrainState, data):
        rng, sub, batch = _split_and_sample(
            state, data, batch_size, axis, augment_fn,
            getattr(model, "noise_batch", None))
        if batch_sharding is not None:
            batch = tuple(
                lax.with_sharding_constraint(b, s)
                for b, s in zip(batch, batch_sharding)
            )

        def loss_fn(params):
            return loss_and_metrics(model, params, batch, keep_prob=keep_prob,
                                    rng=sub, train=True,
                                    model_state=state.model_state)

        grads, aux = jax.grad(loss_fn, has_aux=True)(state.params)
        metrics, model_state = aux["metrics"], aux["model_state"]
        if axis is not None:
            grads = lax.pmean(grads, axis)
            metrics = lax.pmean(metrics, axis)
            if model_state:
                model_state = lax.pmean(model_state, axis)
        if grad_transform is not None:
            grads = grad_transform(grads)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params, state.step)
        params = apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1, rng, model_state), metrics

    return body


def _scan_chunk(body, chunk: int):
    def chunk_fn(state, data):
        state, metrics = lax.scan(
            lambda s, _: body(s, data), state, None, length=chunk
        )
        return state, jax.tree.map(lambda m: m[-1], metrics)

    return chunk_fn


def make_device_train_step(model, optimizer, batch_size: int, *,
                           keep_prob: float = 1.0, chunk: int = 1,
                           donate: bool = True, grad_transform=None,
                           augment_fn=None):
    """Single-device chunked step: (state, DeviceData) -> (state, metrics);
    advances ``state.step`` by ``chunk``."""
    body = _sampled_step_body(model, optimizer, batch_size, keep_prob, None,
                              grad_transform, augment_fn=augment_fn)
    fn = _scan_chunk(body, chunk)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def make_device_dp_train_step(model, optimizer, mesh, batch_size: int, *,
                              keep_prob: float = 1.0, chunk: int = 1,
                              donate: bool = True, grad_transform=None,
                              augment_fn=None):
    """Sync-DP chunked step over ``mesh``: state replicated, the resident
    split replicated, each shard samples ``batch_size // n_data`` examples
    locally and grads ``pmean`` over ICI — the input side costs no
    collective at all."""
    n_data = mesh.shape[DATA_AXIS]
    if batch_size % n_data:
        raise ValueError(
            f"batch_size={batch_size} not divisible by the {n_data}-way "
            f"data axis"
        )
    body = _sampled_step_body(model, optimizer, batch_size // n_data,
                              keep_prob, DATA_AXIS, grad_transform,
                              augment_fn=augment_fn)
    fn = jax.shard_map(
        _scan_chunk(body, chunk),
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def make_zero_device_train_step(model, optimizer, mesh, level: int,
                                batch_size: int, *,
                                keep_prob: float = 1.0, chunk: int = 1,
                                donate: bool = True, grad_transform=None,
                                augment_fn=None, overlap: bool = False,
                                bucket_mb: float | None = None):
    """ZeRO-sharded chunked step over device-resident data — the
    ``--zero`` composition of the headline input path. Sampling is the
    DP device step's verbatim (same salted PRNG folds, replicated
    split, ``batch_size // n_data`` rows per shard), so unclipped
    trajectories bit-match ``make_device_dp_train_step``; what changes
    is the update half (``parallel/zero._zero_step_core``): grads
    reduce-scatter over the data axis, the optimizer updates each
    rank's 1/D state shard, and — at level 1 — one all_gather rebuilds
    the replicated params. ``grad_transform`` arrives already
    axis-aware (``zero_clip_transform``).

    ``overlap=True`` (``--zero_overlap``) buckets the collectives and —
    at level 3 — DOUBLE-BUFFERS the param gather inside the scan body:
    each step ends by issuing the next step's all_gather, the scan
    carries the gathered full params, and the next iteration consumes
    them directly — XLA's async collectives hide the gather behind the
    step epilogue and the next step's on-device sampling. One warmup
    gather per dispatch primes the carry. Trajectories stay BITWISE
    identical to the serial ZeRO path (tests pin it)."""
    from distributed_tensorflow_tpu.parallel.zero import (
        DEFAULT_BUCKET_MB,
        _gather_bucketed,
        _zero_step_core,
        abstract_params,
        zero_state_specs,
    )

    n_data = mesh.shape[DATA_AXIS]
    if batch_size % n_data:
        raise ValueError(
            f"batch_size={batch_size} not divisible by the {n_data}-way "
            f"data axis")
    local_batch = batch_size // n_data
    bucket_mb = DEFAULT_BUCKET_MB if bucket_mb is None else float(bucket_mb)
    core = _zero_step_core(model, optimizer, mesh, level, keep_prob,
                           grad_transform, overlap=overlap,
                           bucket_bytes=int(bucket_mb * 2 ** 20))

    if overlap and level >= 3:
        meta = abstract_params(model)
        bucket_bytes = int(bucket_mb * 2 ** 20)

        def chunk_fn(state: TrainState, data):
            # warmup gather primes the double buffer once per dispatch
            full0 = _gather_bucketed(state.params, meta, n_data,
                                     bucket_bytes)

            def body(carry, _):
                st, full = carry
                rng, sub, batch = _split_and_sample(
                    st, data, local_batch, DATA_AXIS, augment_fn)
                st, metrics, nxt = core(st, batch, sub, rng,
                                        prefetched=full)
                return (st, nxt), metrics

            (state, _), metrics = lax.scan(body, (state, full0), None,
                                           length=chunk)
            return state, jax.tree.map(lambda mm: mm[-1], metrics)
    else:
        def body(state: TrainState, data):
            # _split_and_sample IS _sampled_step_body's sampler: every
            # shard draws the same rows a replicated-DP run would
            rng, sub, batch = _split_and_sample(state, data, local_batch,
                                                DATA_AXIS, augment_fn)
            st, metrics, _ = core(state, batch, sub, rng)
            return st, metrics

        chunk_fn = _scan_chunk(body, chunk)

    cache: dict = {}

    def call(state, data):
        fn = cache.get("fn")
        if fn is None:
            specs = zero_state_specs(state, level)
            sharded = jax.shard_map(
                chunk_fn, mesh=mesh,
                in_specs=(specs, P()),
                out_specs=(specs, P()),
                check_vma=False)
            fn = cache["fn"] = jax.jit(
                sharded, donate_argnums=(0,) if donate else ())
        return fn(state, data)

    return call


def make_device_sp_train_step(sp_model, optimizer, mesh, batch_size: int, *,
                              keep_prob: float = 1.0, chunk: int = 1,
                              donate: bool = True, grad_transform=None,
                              per_token_targets: bool = True):
    """Sequence-parallel chunked step over device-resident data — the
    composition of the two beyond-parity modes (--device_data +
    --seq_parallel). The split lives sharded over the token ("model")
    axis (data/device_data.put_device_data_sp); inside ``shard_map``
    each device samples example rows with a key folded on the DATA axis
    index ONLY — every token shard of a data row draws the SAME rows,
    so its local gather yields exactly its (B_local, S/P) tile of the
    batch, no collective on the input side. The rest is the SP train
    step verbatim: per-shard grads, ONE uniform pmean over the sequence
    axis then the data axis (both loss-family derivations in
    parallel/sequence_parallel.py), identical update everywhere.
    ``sp_model`` must carry ``seq_axis=MODEL_AXIS``."""
    from distributed_tensorflow_tpu.parallel.mesh import MODEL_AXIS
    from distributed_tensorflow_tpu.training.train_state import compute_grads

    if getattr(sp_model, "seq_axis", None) != MODEL_AXIS:
        raise ValueError(
            f"sp_model.seq_axis must be {MODEL_AXIS!r} (got "
            f"{getattr(sp_model, 'seq_axis', None)!r})")
    n_data = mesh.shape[DATA_AXIS]
    if batch_size % n_data:
        raise ValueError(
            f"batch_size={batch_size} not divisible by the {n_data}-way "
            f"data axis")
    local_batch = batch_size // n_data

    def body(state: TrainState, data):
        rng, sub = jax.random.split(state.rng)
        samp = jax.random.fold_in(state.rng, _SAMPLE_SALT)
        # DATA-axis fold only: token shards of one data row must draw
        # identical example rows (their tiles are slices of the same
        # sequences). The dropout key matches make_sp_train_step's: per
        # data shard here, and the LM folds the sequence index itself.
        samp = jax.random.fold_in(samp, lax.axis_index(DATA_AXIS))
        sub = jax.random.fold_in(sub, lax.axis_index(DATA_AXIS))
        idx = jax.random.randint(samp, (local_batch,), 0,
                                 data.num_examples)
        x = data.images[idx]
        y = data.labels[idx]
        if per_token_targets:
            # u8/u16 token storage -> int32 ids (image splits keep u8:
            # normalize_if_u8 in the model needs the original dtype)
            x = x.astype(jnp.int32)
            y = y.astype(jnp.int32)
        grads, metrics, model_state = compute_grads(
            sp_model, state.params, (x, y), keep_prob=keep_prob, rng=sub,
            model_state=state.model_state)
        grads = lax.pmean(grads, MODEL_AXIS)
        grads = lax.pmean(grads, DATA_AXIS)
        if grad_transform is not None:
            grads = grad_transform(grads)
        metrics = lax.pmean(metrics, MODEL_AXIS)
        metrics = lax.pmean(metrics, DATA_AXIS)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params, state.step)
        params = apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1, rng,
                          model_state), metrics

    from distributed_tensorflow_tpu.data.device_data import DeviceData

    y_spec = P(None, MODEL_AXIS) if per_token_targets else P(None)
    fn = jax.shard_map(
        _scan_chunk(body, chunk),
        mesh=mesh,
        # the data spec mirrors DeviceData's pytree type (shard_map's
        # spec matching is structural, a bare tuple prefix won't do)
        in_specs=(P(), DeviceData(P(None, MODEL_AXIS), y_spec)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def _make_resident_sharded_step(per_shard_step, state_specs_fn, mesh,
                                local_batch: int, chunk: int,
                                donate: bool):
    """Shared PP/EP resident-sampler wrapper: the DATA-axis-folded
    sample body lives HERE, once — every model-axis shard (stage or
    expert) of a data row folds the SAME (salt, data-index) key and so
    draws the SAME rows from its local 1/D of the split (the
    replicated-batch invariant both modes rest on); ``lax.scan`` runs
    ``chunk`` steps per dispatch, and the shard_map/jit pair is cached
    on first call (state specs need a concrete state)."""
    from distributed_tensorflow_tpu.data.device_data import DeviceData

    def body(state: TrainState, data):
        samp = jax.random.fold_in(state.rng, _SAMPLE_SALT)
        # DATA-axis fold only — the staged batch is replicated over the
        # model axis. The dropout stream is the wrapped step's own (it
        # folds DATA itself).
        samp = jax.random.fold_in(samp, lax.axis_index(DATA_AXIS))
        idx = jax.random.randint(samp, (local_batch,), 0,
                                 data.num_examples)
        batch = (data.images[idx].astype(jnp.int32),
                 data.labels[idx].astype(jnp.int32))
        return per_shard_step(state, batch)

    data_spec = DeviceData(P(DATA_AXIS, None), P(DATA_AXIS, None))
    cache: dict = {}

    def call(state, data):
        fn = cache.get("fn")
        if fn is None:
            specs = state_specs_fn(state)
            sharded = jax.shard_map(
                _scan_chunk(body, chunk), mesh=mesh,
                in_specs=(specs, data_spec),
                out_specs=(specs, P()),
                check_vma=False)
            fn = cache["fn"] = jax.jit(
                sharded, donate_argnums=(0,) if donate else ())
        return fn(state, data)

    return call


def make_pp_device_train_step(model, optimizer, mesh, batch_size: int,
                              microbatches: int, *, keep_prob: float = 1.0,
                              chunk: int = 1, donate: bool = True,
                              grad_transform=None,
                              virtual_stages: int = 1,
                              schedule: str = "auto"):
    """Pipeline-parallel chunked step over device-resident data — the
    GPipe schedule composed with the zero-host-bytes input path. The
    split lives DATA-SHARDED in HBM (``put_device_data(...,
    data_sharded=True)``: each data row of devices holds its 1/D of the
    examples, replicated over the stage axis); inside ``shard_map`` each
    device samples its local minibatch with a key folded on the DATA
    axis index ONLY — every stage of a data row draws the SAME rows, so
    its gather yields exactly its per-shard batch with no collective on
    the input side. The rest is the PP train step verbatim
    (parallel/pipeline_parallel._pp_step_fn: schedule-table tick scan +
    ppermute ring, psum'd replicated-leaf grads), and ``lax.scan`` runs
    ``chunk`` steps per dispatch. ``grad_transform`` composes inside the
    step — pass ``pp_clip_transform`` for an axis-correct --clip_norm.
    ``virtual_stages=V`` selects the interleaved schedule (state stacked
    by ``shard_state_pp(..., virtual_stages=V)``; bit-identical
    trajectories to V=1 with a ~V-fold smaller pipeline bubble).
    ``schedule="zb"`` runs the zero-bubble F/B/W table on the same
    layout — still bit-identical (parallel/pipeline_parallel)."""
    from distributed_tensorflow_tpu.parallel.pipeline_parallel import (
        _pp_step_fn,
        pp_state_specs,
    )

    n_data = mesh.shape[DATA_AXIS]
    if batch_size % n_data:
        raise ValueError(
            f"batch_size={batch_size} not divisible by the {n_data}-way "
            f"data axis")
    local_batch = batch_size // n_data
    if local_batch % int(microbatches):
        raise ValueError(
            f"per-shard batch {local_batch} must split into "
            f"{microbatches} microbatches")
    pp_step = _pp_step_fn(model, optimizer, mesh, microbatches, keep_prob,
                          grad_transform, virtual_stages, schedule)
    return _make_resident_sharded_step(pp_step, pp_state_specs, mesh,
                                       local_batch, chunk, donate)


def make_ep_device_train_step(model, optimizer, mesh, batch_size: int, *,
                              keep_prob: float = 1.0, chunk: int = 1,
                              donate: bool = True, grad_transform=None):
    """Expert-parallel chunked step over device-resident data — Switch
    MoE expert sharding composed with the zero-host-bytes input path.
    Same layout/sampling contract as the PP variant (data-sharded split,
    DATA-axis-folded sample key so every expert shard of a data row
    draws the SAME rows — the replicated-activation invariant the
    psum-combine rests on), with the EP gradient accounting verbatim
    (parallel/expert_parallel._ep_step_fn: 1/P loss seed, expert-shard
    grads as exact partials, psum'd replicated leaves). ``model`` must
    carry ``moe_axis=MODEL_AXIS``; pass ``ep_clip_transform`` as
    ``grad_transform`` for an axis-correct --clip_norm."""
    from distributed_tensorflow_tpu.parallel.expert_parallel import (
        _ep_step_fn,
        ep_state_specs,
    )

    n_data = mesh.shape[DATA_AXIS]
    if batch_size % n_data:
        raise ValueError(
            f"batch_size={batch_size} not divisible by the {n_data}-way "
            f"data axis")
    local_batch = batch_size // n_data
    ep_step = _ep_step_fn(model, optimizer, mesh, keep_prob,
                          grad_transform)
    return _make_resident_sharded_step(ep_step, ep_state_specs, mesh,
                                       local_batch, chunk, donate)


def make_device_tp_train_step(model, optimizer, mesh, batch_size: int, *,
                              keep_prob: float = 1.0, chunk: int = 1,
                              donate: bool = True, grad_transform=None,
                              augment_fn=None):
    """TP(+DP) chunked step over device-resident data: global-view GSPMD
    program — the state carries its TP layout (parallel/tensor_parallel),
    the split is replicated, the in-program sampled batch is constrained to
    the data axis, and XLA derives every collective. Composes the two
    beyond-parity modes (--device_data + --model_axis)."""
    from jax.sharding import NamedSharding

    from distributed_tensorflow_tpu.parallel.tensor_parallel import (
        shard_attention,
    )

    batch_sharding = (
        NamedSharding(mesh, P(DATA_AXIS, None)),  # images [B, P]
        NamedSharding(mesh, P(DATA_AXIS)),        # int labels [B]
    )
    body = _sampled_step_body(shard_attention(model, mesh), optimizer,
                              batch_size, keep_prob,
                              None, grad_transform, batch_sharding,
                              augment_fn=augment_fn)
    fn = _scan_chunk(body, chunk)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())
