"""The training loop: reference hot-loop semantics on a TPU-native step.

Reference loop (``MNISTDist.py:172-188``): while not stopped and
``step < training_iter`` — draw a minibatch, every ``display_step`` print
job/task + step + minibatch loss/accuracy (evaluated *before* the update,
dropout off, ``:179-182``), then run one optimizer step. Termination is on
the shared global step. On exit: ``sv.stop()`` + "Optimization Finished!"
(``:192-193``).

This loop keeps those semantics; what changed is underneath: the step is
one compiled XLA executable with state resident in HBM, and display-step
evaluation reuses a cached compiled eval fn. Modes:

- "local": single device (CPU parity config / one TPU chip)
- "sync":  synchronous DP over all local devices (mesh + psum over ICI)
The async "ps" mode lives in parallel/ps_emulation.py and drives this
same loop through a PS-backed step function.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax

from distributed_tensorflow_tpu.checkpoint import (
    background_save_from_flags,
    max_to_keep_from_flags,
)
from distributed_tensorflow_tpu.flags import coord_steps_from_flags
from distributed_tensorflow_tpu.data import read_data_sets
from distributed_tensorflow_tpu.data.device_data import (
    put_device_data,
    put_device_data_sp,
)
from distributed_tensorflow_tpu.data.pipeline import batch_iterator, prefetch_to_device
from distributed_tensorflow_tpu.models import get_model
from distributed_tensorflow_tpu.parallel import (
    MeshSpec,
    make_dp_train_step,
    make_mesh,
    shard_batch,
)
from distributed_tensorflow_tpu.parallel.data_parallel import (
    local_batch_size,
    make_dp_eval_step,
    replicate_state,
)
from distributed_tensorflow_tpu.training import (
    create_train_state,
    get_optimizer,
    make_eval_step,
    make_train_step,
    schedule_from_flags,
)
# the module, not its builders: a layout looks a builder up when it builds
# a chunk, so that what replaces one on the module (the benchmark's probe)
# is what gets called
from distributed_tensorflow_tpu.training import device_step, elastic
from distributed_tensorflow_tpu.training.supervisor import Supervisor
from distributed_tensorflow_tpu.training.train_state import evaluate
from distributed_tensorflow_tpu.utils import (
    MetricsLogger,
    StepTimer,
    Throughput,
    collective_sync_cadence,
    trace_span,
)
from distributed_tensorflow_tpu.utils import efficiency, resources, telemetry


@dataclass
class TrainResult:
    final_step: int
    train_metrics: dict[str, float]
    test_metrics: dict[str, float] | None
    images_per_sec: float
    images_per_sec_per_chip: float
    n_chips: int


def build_model_for(FLAGS, meta: dict):
    import jax.numpy as jnp

    compute_dtype = jnp.bfloat16 if FLAGS.bf16 else None
    if meta.get("kind") == "lm":
        # token data feeds only the causal-LM family (a pixel classifier
        # cannot consume ids), and vice versa — pair them loudly
        if FLAGS.model != "lm":
            raise ValueError(
                f"--dataset lm produces token sequences; --model "
                f"{FLAGS.model!r} is an image model. Use --model lm.")
        attn_block = int(getattr(FLAGS, "attn_block", 0))
        ce_block = int(getattr(FLAGS, "ce_block", 0))
        return get_model(
            "lm",
            vocab_size=meta["vocab_size"],
            seq_len=meta["seq_len"],
            d_model=FLAGS.d_model,
            num_heads=FLAGS.num_heads,
            num_blocks=FLAGS.num_blocks,
            compute_dtype=compute_dtype,
            attn_block=attn_block if attn_block > 0 else None,
            remat=bool(getattr(FLAGS, "remat", False)),
            ce_block=ce_block if ce_block > 0 else None,
            moe_experts=int(getattr(FLAGS, "moe_experts", 0)),
            moe_capacity=float(getattr(FLAGS, "moe_capacity", 1.25)),
            moe_aux=float(getattr(FLAGS, "moe_aux", 0.01)),
            **_lm_arch_kwargs(FLAGS),
        )
    if FLAGS.model == "lm":
        raise ValueError("--model lm consumes token sequences; use "
                         "--dataset lm")
    kwargs = {}
    if FLAGS.model == "deep_cnn" and getattr(FLAGS, "pallas", False):
        kwargs["use_pallas"] = True
    if FLAGS.model == "mlp":
        # the one model where the reference's dead --hidden_units flag is
        # live (models/mlp.py); deep_cnn keeps the reference's fixed 1024
        # FC width (MNISTDist.py:83 — the flag was dead there too)
        kwargs["hidden_units"] = FLAGS.hidden_units
    if FLAGS.model == "transformer":
        kwargs.update(d_model=FLAGS.d_model, num_heads=FLAGS.num_heads,
                      num_blocks=FLAGS.num_blocks,
                      remat=bool(getattr(FLAGS, "remat", False)))
    return get_model(
        FLAGS.model,
        image_size=meta["image_size"],
        channels=meta["channels"],
        num_classes=meta["num_classes"],
        compute_dtype=compute_dtype,
        **kwargs,
    )


def _lm_arch_kwargs(FLAGS) -> dict:
    """The LM's further choices, each named by its mechanism; a flag left
    at its default is not passed, so a parse set without these flags
    builds the first form."""
    names = ("norm", "norm_eps", "rope_theta", "num_kv_heads", "head_dim",
             "qk_norm", "mlp_gated", "biases", "moe_top_k", "moe_ffn_dim",
             "moe_first_expert", "moe_held_experts", "objective",
             "diffusion_block", "diffusion_t_min", "layer_plan",
             "attn_window", "window_rope_theta", "rope_fraction",
             "rope_yarn", "attn_gate", "moe_shared_dim", "moe_scoring",
             "moe_scale", "mlp_dim", "sandwich_norm", "loop_passes",
             "loop_exit_beta", "attn_gate_elementwise", "moe_shared_gate",
             "linear_key_heads", "linear_key_dim", "linear_value_dim",
             "linear_conv")
    out = {n: getattr(FLAGS, n) for n in names if hasattr(FLAGS, n)}
    out["noise_seed"] = int(FLAGS.seed)
    return out


def _reserved_ids(FLAGS) -> int:
    """Ids the LM data leaves out: the masked-diffusion mask id."""
    return int(getattr(FLAGS, "objective", "") == "masked_diffusion")


def build_training_for(FLAGS, meta: dict):
    """(model, optimizer, fresh train state) as the flags describe them:
    what every loop variant trains, and what ``chip_smoke.py`` compiles
    again to look inside the trainer's program."""
    model = build_model_for(FLAGS, meta)
    opt = get_optimizer(FLAGS.optimizer, schedule_from_flags(FLAGS),
                        weight_decay=getattr(FLAGS, "weight_decay", 0.0))
    return model, opt, create_train_state(model, opt, seed=FLAGS.seed)


def _log_recovery(sv, logger, step: int, eff=None) -> None:
    """Recovery observability: where this run's state came from
    (restore source step, fallback depth, quarantine count, time-to-
    restore — sv.restore_report, written by the verified-restore ladder).
    Emitted once per run into metrics.jsonl + the event file; a fresh
    init logs restore_step=-1 so 'never restored' and 'restored step 0'
    stay distinguishable. The restore stall is the goodput accounting's
    first charge (``eff``)."""
    rep = getattr(sv, "restore_report", None)
    logger.scalars(step, {
        "recovery_restore_step": float(rep.step) if rep else -1.0,
        "recovery_fallback_depth": float(rep.fallback_depth) if rep else 0.0,
        "recovery_quarantined": float(len(rep.quarantined)) if rep else 0.0,
        "recovery_time_s": round(rep.time_s, 4) if rep else 0.0,
    })
    if eff is not None and rep is not None:
        eff.charge(rep.time_s, "restore")
    # a re-formed elastic world books its resize downtime here — right
    # after the restore that downtime paid for (no-op otherwise)
    elastic.book_resize(eff, logger, step)


class _charged:
    """Tiny timing context: book the body's wall time against the
    efficiency meter's goodput ledger (no-op when accounting is off)."""

    __slots__ = ("_eff", "_kind", "_t0")

    def __init__(self, eff, kind: str):
        self._eff = eff
        self._kind = kind

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._eff is not None:
            self._eff.charge(time.perf_counter() - self._t0, self._kind)
        return False


def _display_scalars(meter, stimer, eff, rmon=None) -> dict:
    """The display-cadence scalar family every loop emits: throughput,
    the step-time breakdown, — when accounting is on — mfu /
    model_flops_per_sec / goodput (utils/efficiency.py), and — when the
    resource plane is on — hbm_* / compiles_* / comm_bytes_per_step
    (utils/resources.py; the HBM sample rides THIS cadence, no new
    sync points)."""
    out = {"images_per_sec": meter.images_per_sec, **stimer.scalars()}
    if eff is not None:
        out.update(eff.scalars(meter.images_per_sec))
    if rmon is not None:
        out.update(rmon.scalars())
    return out


def _display_eval(step, eval_fn, params, model_state, eff, stimer, *,
                  batch=None, draw=None) -> dict:
    """One display's evaluation, as three or four host spans that change
    no device work: ``display_stage`` (only where the loop draws and
    stages a fresh batch for it: ``draw`` given; its duration is the
    display's share of ``step_host_wait_s``; the host-fed loops pass the
    upcoming training ``batch`` instead); ``display_wait``, in which
    the eval is enqueued (at the first display: traced and compiled) and
    the host waits for the steps enqueued before it (the eval is queued
    behind them by then, so the device sees no gap); and ``display_eval``,
    the readback: the eval's own device time. Returns the display
    metrics as floats."""
    if draw is not None:
        with trace_span("display_stage", step=step) as staged:
            batch = draw()
        stimer.add("host_wait", staged.dur_s)
    with telemetry.armed("display_eval", step=step), _charged(eff, "eval"):
        with trace_span("display_wait", step=step):
            m = eval_fn(params, batch, model_state)
            jax.block_until_ready(params)
        with trace_span("display_eval", step=step):
            return {k: float(v) for k, v in m.items()}


def _display_log(step, display, logger, scalars, eff, snt,
                 snt_state) -> None:
    """The rest of a display, all host work with the device's queue as
    the loop left it (``display_log``): the sentinel's look, the synced
    row (written first: its wall time is the (step, time) pair every
    rate is read from), the scalars row (``scalars()``: the loop's own
    ``_display_scalars`` call, made in here so that its HBM sample is
    inside the span), and both flushes."""
    with trace_span("display_log", step=step):
        if snt is not None:
            snt.observe(step, display, state=snt_state,
                        stall_s=_booked_stall(eff))
        logger.log_display(
            step, display["loss"], display["accuracy"],
            {k: v for k, v in display.items()
             if k.startswith(("moe_rows", "moe_overflow", "moe_unrouted",
                              "moe_buffer", "moe_tiles", "moe_dispatch",
                              "diffusion_", "loop_"))})
        logger.scalars(step, scalars())
        logger.flush()
        telemetry.get_tracer().flush()


def _booked_stall(eff) -> float:
    """The cumulative stall seconds the goodput ledger has booked —
    handed to Sentinel.observe so known stalls (ckpt/eval/restore/
    compile) never read as a throughput collapse."""
    return eff.goodput.lost_s if eff is not None else 0.0


def _sentinel_host_state(state):
    """Host snapshot of the live device state for the sentinel's
    last-good ledger. The DP/TP step functions DONATE their input
    buffers, so a device reference held across steps is dead by the
    time a trip wants it — the snapshot must be taken at the healthy
    boundary. Only called when --sentinel_action needs snapshots
    (Sentinel.wants_state), at the display cadence. Cross-host-sharded
    state returns None (its fetch is a collective every process would
    have to join; the cadenced checkpoints remain that case's recovery
    path)."""
    from distributed_tensorflow_tpu.utils.pytree import (
        fetch_pytree,
        needs_collective_fetch,
    )

    if needs_collective_fetch(state):
        return None
    return fetch_pytree(state)


def _sentinel_for(FLAGS, sv, logger):
    """Chief-side training-health sentinel (utils/sentinel.py), its
    emergency-save callback wired to the verified-save path (the same
    CRC-manifest writer every checkpoint uses) under
    ``<logdir>/sentinel/`` — outside the main directory's GC, so a sick
    run that keeps checkpointing garbage can never age the last-good
    state out. None when unarmed (--sentinel_action default) or on
    non-chief processes (the chief owns the display metrics)."""
    import os

    from distributed_tensorflow_tpu.utils import sentinel as _sentinel

    if not sv.is_chief:
        return None

    def save_fn(state, step):
        from distributed_tensorflow_tpu.checkpoint.checkpoint import (
            save_checkpoint,
        )
        from distributed_tensorflow_tpu.utils.pytree import (
            needs_collective_fetch,
        )

        if needs_collective_fetch(state):
            print("sentinel: state spans hosts — emergency snapshot "
                  "skipped (the collective fetch needs every process at "
                  "this boundary; the cadenced checkpoints remain the "
                  "recovery path)")
            return None
        return save_checkpoint(os.path.join(FLAGS.logdir, "sentinel"),
                               state, step, max_to_keep=2)

    # abort: single-process raises (loud nonzero exit); multi-host must
    # route through the supervisor's stop so the coordinated vote takes
    # every process out at the same step instead of stranding peers in
    # the next collective
    stop_fn = sv.request_stop if jax.process_count() > 1 else None
    return _sentinel.from_flags(FLAGS, save_fn=save_fn, logger=logger,
                                stop_fn=stop_fn)


class _Scaffold(NamedTuple):
    """What every loop stands in: made once a run by ``_scaffold``."""
    sv: Supervisor
    logger: MetricsLogger
    meter: Throughput
    stimer: StepTimer
    eff: Any  # efficiency meter, None when accounting is off
    rmon: Any  # resource monitor, None when the resource plane is off
    snt: Any  # sentinel, None when unarmed or not the chief
    els: Any  # elastic supervisor, None outside an elastic run
    periodic_eval: Callable
    coord: Any  # _HostCoordinator of a multi-process mesh run, else None
    sync_every: int  # collective_sync_cadence of this run, in steps

    def should_stop(self) -> bool:
        """The vote's cached verdict where processes vote, else sv's."""
        return (self.coord or self.sv).should_stop()

    def checkpoint(self, state, step: int) -> None:
        """The save that may be due at ``step``, or the vote on it, whose
        wait is peer-coordination stall (mostly skew), booked apart."""
        if self.coord is not None:
            with _charged(self.eff, "coord"):
                self.coord.tick(state, step)
        else:
            with _charged(self.eff, "ckpt"):
                self.sv.maybe_checkpoint(state, step)


def _scaffold(FLAGS, ds, model, opt, mesh, n_chips,
              full_eval=None) -> _Scaffold:
    """Supervisor, logger, meters, monitors, sentinel, elastic supervisor
    and periodic eval of one run. Processes that share a mesh (sync mode,
    more than one of them) agree on stops and saves through the
    coordinator's vote; any other run asks its own Supervisor."""
    sv = Supervisor(
        is_chief=(FLAGS.task_index == 0),
        logdir=FLAGS.logdir,
        save_model_secs=FLAGS.save_model_secs,
        max_to_keep=max_to_keep_from_flags(FLAGS),
        background_save=background_save_from_flags(FLAGS),
        sharded_spanning=bool(getattr(FLAGS, "sharded_checkpoint", True)),
    )
    logger = MetricsLogger(FLAGS.logdir if sv.is_chief else None,
                           job_name=FLAGS.job_name or "worker",
                           task_index=FLAGS.task_index)
    stimer = StepTimer()
    eff = efficiency.meter_from_flags(FLAGS, model, FLAGS.batch_size,
                                      n_chips)
    els = elastic.supervisor_from_flags(FLAGS)
    coord = (_HostCoordinator(sv, coord_steps_from_flags(FLAGS),
                              stimer=stimer, logger=logger, elastic_sv=els)
             if mesh is not None and jax.process_count() > 1 else None)
    return _Scaffold(
        sv=sv, logger=logger, meter=Throughput(FLAGS.batch_size, n_chips),
        stimer=stimer, eff=eff,
        rmon=resources.monitor_from_flags(FLAGS, model, opt,
                                          FLAGS.batch_size, n_chips),
        snt=_sentinel_for(FLAGS, sv, logger), els=els,
        periodic_eval=_periodic_test_eval(FLAGS, sv, model, ds, logger,
                                          full_eval=full_eval, eff=eff),
        coord=coord,
        sync_every=collective_sync_cadence(mesh is not None))


def _close_compile_window(sc: _Scaffold, params, step: int) -> None:
    """After a loop's first dispatch, which carried the XLA compile (jit
    traces and compiles synchronously inside the call): keep it out of the
    throughput window and of the step breakdown, and let goodput see the
    pre-compile window's work plus this wait as an init stall."""
    if sc.eff is not None:
        sc.eff.charge(sc.stimer.cumulative_work()[0], "init")
    with trace_span("device_sync", step=step), _charged(sc.eff, "init"):
        jax.block_until_ready(params)
    sc.meter.reset()
    sc.stimer.reset()


def _finish(FLAGS, sc: _Scaffold, model, state, ds, step: int,
            last_display: dict, full_eval=None) -> TrainResult:
    """A loop's tail, after its managed block has saved ``state`` (in the
    checkpoint's layout): the end-of-run test eval, the reference's last
    line, the result."""
    test_metrics = _final_test_eval(FLAGS, sc.sv, sc.periodic_eval, model,
                                    state, ds, sc.logger, step,
                                    full_eval=full_eval)
    print("Optimization Finished!")
    sc.logger.close()
    return TrainResult(
        final_step=step,
        train_metrics=last_display,
        test_metrics=test_metrics,
        images_per_sec=sc.meter.images_per_sec,
        images_per_sec_per_chip=sc.meter.images_per_sec_per_chip,
        n_chips=sc.meter.n_chips,
    )


@dataclass(frozen=True)
class _DeviceLayout:
    """What differs between the layouts ``_train_device`` drives, built by
    the caller that chose the mode. With ``to_host`` (the live state is not
    the checkpoint's layout) the StateBox is updated, and so evals and
    saves happen, only at boundaries; without it after every chunk."""
    span: str  # name of the dispatch's span
    put_data: Callable[[], Any]  # the train split, onto the device
    build_chunk: Callable[[int], Callable]  # steps -> fn(live, data)
    # host state -> live state (after a restore too); None: the state as is
    to_live: Callable[[Any], Any] | None = None
    # live state -> the checkpoint's layout; None: the live state is that
    to_host: Callable[[Any], Any] | None = None
    # (eval_fn, stage): a display is the reference's, the dropout-off eval
    # of a fresh staged host batch before the chunk; None: the chunk's last
    # training metrics at the boundary (no host batch to stall the ring on)
    display: tuple[Callable, Callable] | None = None


def train(FLAGS, mode: str = "local") -> TrainResult:
    """Run a full training job in "local" or "sync" mode.

    "sync" spans every device in the process's view: all local chips on one
    host, or the global multi-host mesh when ``jax.distributed`` was
    initialized first (the reference's one-process-per-machine topology,
    ``MNISTDist.py:101-107``). In the multi-host case each process feeds
    its own slice of the global batch (assembled in ``shard_batch``) and
    draws from an independently-seeded shuffle, matching the reference's
    per-worker input semantics (``MNISTDist.py:167,178``).

    This is the ELASTIC wrapper (r15): the actual run lives in
    ``_train_once``. When the elasticity supervisor detects a membership
    change (a ``preempt`` fault, or — multi-host — a departure bit on
    the coordinator vote), the loop drains to a checkpoint boundary and
    raises ``ResizeRequired``; this wrapper records the change, installs
    the new world/epoch (``training/elastic.apply_resize``), and
    re-enters the loop — which RESTORES the drain checkpoint through
    the cross-topology machinery and continues at the new world size,
    bitwise on the trajectory a fresh run restored at that shape would
    take. A preempted process in a multi-host world exits here with a
    stub result instead (``Departed``)."""
    elastic.begin_run(FLAGS)
    while True:
        try:
            return _train_once(FLAGS, mode)
        except elastic.ResizeRequired as rz:
            elastic.apply_resize(rz, FLAGS)
        except elastic.Departed as d:
            print("Optimization Finished!")
            return TrainResult(final_step=d.step, train_metrics={},
                               test_metrics=None, images_per_sec=0.0,
                               images_per_sec_per_chip=0.0, n_chips=0)


def _train_once(FLAGS, mode: str = "local") -> TrainResult:
    """One membership epoch of a training run (see ``train``)."""
    from distributed_tensorflow_tpu.utils import faults

    faults.configure_from_flags(FLAGS)
    # the telemetry spine registers this run: span sink + flight
    # recorder under --logdir, optional --watchdog_s hang watchdog.
    # Every loop variant below inherits it (the dispatched _train_*
    # helpers run in this process)
    telemetry.configure_from_flags(FLAGS)
    # one clock with the profiler: while a jax.profiler session is live
    # every span also lies on the host plane of its trace
    telemetry.set_annotator(jax.profiler.TraceAnnotation)
    # everything the process did before this (imports, flags, the
    # compile cache's placement, the backend's start) is launch
    telemetry.get_tracer().record_instant("train_start", mode=mode)
    if int(getattr(FLAGS, "zero", 0) or 0) and mode != "sync":
        # fail BEFORE dataset/model setup: the parse-time validator can
        # only catch an EXPLICIT --mode=local/ps (--mode=auto resolves
        # against the device count, unknowable at parse time — a 1-chip
        # host lands here as "local")
        raise ValueError(
            f"--zero={FLAGS.zero} requires sync mode (a device mesh "
            f"with a data axis to shard over); got mode={mode!r}. On a "
            f"single-chip host --mode=auto resolves to local — ZeRO "
            f"needs >1 local device to shard over (it is single-process "
            f"in this version, so a multi-host launch won't help)")
    n_procs = jax.process_count()
    span = bool(getattr(FLAGS, "sp_span_hosts", False))
    if span and not getattr(FLAGS, "seq_parallel", False):
        raise ValueError(
            "--sp_span_hosts only applies to --seq_parallel (it lets the "
            "ring's token axis cross hosts); without it the flag would "
            "silently change nothing — drop it or add --seq_parallel")
    # span mode: every process draws the SAME global batch (hosts in a
    # data row hold token-slices of the same sequences) — one read with
    # the shared seed, not a per-process seed discarded later
    data_seed = FLAGS.seed + (
        jax.process_index() if (n_procs > 1 and not span) else 0)
    with trace_span("data_build", dataset=FLAGS.dataset):
        ds = read_data_sets(FLAGS.data_dir, one_hot=True,
                            dataset=FLAGS.dataset, seed=data_seed,
                            validation_size=FLAGS.validation_size,
                            seq_len=getattr(FLAGS, "seq_len", 256),
                            vocab_size=getattr(FLAGS, "vocab_size", 64),
                            reserved_ids=_reserved_ids(FLAGS))
    with trace_span("state_init"):
        model, opt, state = build_training_for(FLAGS, ds.meta)
        # init is dispatched asynchronously: the span ends when the
        # parameters and the optimizer's state are on the device
        jax.block_until_ready(state)
    is_lm = ds.meta.get("kind") == "lm"

    n_chips = 1
    mesh = None
    restage = None  # re-place a host-restored state onto the mesh layout
    sp_full_eval = None  # SP: full-split evals through the sharded step
    feed_batch = FLAGS.batch_size  # examples this process loads per step
    model_axis = max(1, getattr(FLAGS, "model_axis", 1))
    if model_axis > 1 and mode != "sync":
        raise ValueError(
            f"--model_axis={model_axis} requires sync mode (a device mesh); "
            f"got mode={mode!r}. Use --mode=sync."
        )
    clip = None
    if getattr(FLAGS, "clip_norm", 0.0) > 0:
        from distributed_tensorflow_tpu.training.train_state import (
            clip_by_global_norm,
        )

        clip = clip_by_global_norm(FLAGS.clip_norm)
    augment = None
    if getattr(FLAGS, "augment", False):
        if is_lm:
            raise ValueError("--augment crops/flips images; token "
                             "sequences (--dataset lm) have no image "
                             "layout to augment")
        from distributed_tensorflow_tpu.ops.augment import make_augment

        # flip only natural images (CIFAR): mirroring digits corrupts the
        # label-signal ('3' has no valid mirror glyph)
        augment = make_augment(ds.meta,
                               pad=getattr(FLAGS, "augment_pad", 4),
                               flip=ds.meta["channels"] == 3)
    accum = max(1, getattr(FLAGS, "accum_steps", 1))
    if accum > 1:
        if getattr(FLAGS, "device_data", False):
            raise ValueError(
                "--accum_steps>1 is incompatible with --device_data: the "
                "device-resident step samples its batch on device each "
                "step, so there is no host batch to split; raise "
                "--batch_size instead"
            )
        if FLAGS.batch_size % accum:
            raise ValueError(
                f"--batch_size={FLAGS.batch_size} must be divisible by "
                f"--accum_steps={accum}"
            )
    if int(getattr(FLAGS, "zero", 0) or 0):
        # ZeRO-sharded sync DP (parallel/zero.py): optimizer state (and,
        # at level 3, the params) partitioned 1/D over the data axis —
        # same math as replicated DP, D-fold less redundant HBM, and
        # reduce-scatter+all-gather (|G|+|P|) on the wire instead of the
        # all-reduce's 2|G|. Dispatched BEFORE the pipeline branch so a
        # non-CLI caller combining the two hits _train_zero's loud
        # rejection instead of silently training plain GPipe
        return _train_zero(FLAGS, ds, model, opt, state, mode, accum,
                           augment, model_axis)
    if getattr(FLAGS, "pipeline", False):
        if getattr(FLAGS, "seq_parallel", False) or \
                getattr(FLAGS, "expert_parallel", False):
            raise ValueError("--pipeline, --seq_parallel and "
                             "--expert_parallel are mutually exclusive "
                             "model-axis strategies — pick one")
        return _train_pipeline(FLAGS, ds, model, opt, state, mode,
                               model_axis)
    # --device_data: each branch below names the builder of its chunked
    # step (``chunk_step``); SP and EP also stage the split their own way
    put_split = lambda: put_device_data(ds.train, mesh)
    if getattr(FLAGS, "expert_parallel", False):
        # expert parallelism: MoE experts sharded --model_axis ways
        # (parallel/expert_parallel.py); the EP twin carries moe_axis
        # and the step/eval builders slot into the common loop like
        # SP's do
        from distributed_tensorflow_tpu.models.transformer import (
            TransformerLM,
        )
        from distributed_tensorflow_tpu.parallel.expert_parallel import (
            ep_clip_transform,
            make_ep_eval_step,
            make_ep_train_step,
            shard_state_ep,
        )
        from distributed_tensorflow_tpu.parallel.mesh import (
            DATA_AXIS,
            MODEL_AXIS,
            put_global,
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        if not (is_lm and getattr(model, "moe_experts", 0)):
            raise ValueError("--expert_parallel shards MoE experts; use "
                             "--model lm --dataset lm --moe_experts E")
        if mode != "sync":
            raise ValueError("--expert_parallel requires sync mode")
        if model_axis < 2:
            raise ValueError(f"--expert_parallel shards experts "
                             f"--model_axis ways; --model_axis="
                             f"{model_axis} shards nothing")
        if jax.process_count() > 1:
            raise ValueError("--expert_parallel is single-process in "
                             "this version")
        if getattr(FLAGS, "seq_parallel", False):
            # (--pipeline already raised or returned in its own branch)
            raise ValueError("--expert_parallel, --seq_parallel and "
                             "--pipeline each claim the model axis — "
                             "pick one")
        if accum > 1:
            raise ValueError("--accum_steps is not wired for "
                             "--expert_parallel yet; raise --batch_size "
                             "instead")
        if clip is not None:
            # the plain clip inside shard_map would scale by a
            # shard-LOCAL norm and diverge the replicated leaves — use
            # the axis-aware transform (psum'd squared-norm partials
            # over the expert axis, one scale everywhere)
            clip = ep_clip_transform(FLAGS.clip_norm)
        ep_model = TransformerLM(
            vocab_size=model.vocab_size, seq_len=model.seq_len,
            d_model=model.d_model, num_heads=model.num_heads,
            num_blocks=model.num_blocks,
            mlp_ratio=model.mlp_dim // model.d_model,
            compute_dtype=model.compute_dtype,
            attn_block=model.attn_block, remat=model.remat,
            ce_block=model.ce_block, moe_experts=model.moe_experts,
            moe_capacity=model.moe_capacity, moe_aux=model.moe_aux,
            moe_axis=MODEL_AXIS)
        mesh = make_mesh(MeshSpec(data=-1, model=model_axis))
        n_chips = mesh.devices.size
        data_ways = mesh.shape[DATA_AXIS]
        if FLAGS.batch_size % data_ways:
            raise ValueError(
                f"--batch_size={FLAGS.batch_size} must be divisible by "
                f"the {data_ways}-way data axis")
        state = shard_state_ep(state, mesh)
        step_fn = make_ep_train_step(ep_model, opt, mesh,
                                     keep_prob=FLAGS.keep_prob,
                                     grad_transform=clip)
        eval_fn = make_ep_eval_step(ep_model, mesh)
        _ep_specs = (NamedSharding(mesh, P(DATA_AXIS, None)),
                     NamedSharding(mesh, P(DATA_AXIS, None)))
        stage = lambda b: put_global(_ep_specs, b)
        restage = lambda s: shard_state_ep(s, mesh)
        # the split 1/D a data row; the step samples inside shard_map
        put_split = lambda: put_device_data(ds.train, mesh,
                                            data_sharded=True)
        chunk_step = lambda n: device_step.make_ep_device_train_step(
            ep_model, opt, mesh, FLAGS.batch_size,
            keep_prob=FLAGS.keep_prob, chunk=n, grad_transform=clip)
    elif getattr(FLAGS, "seq_parallel", False):
        # sequence/context parallelism: tokens sharded --model_axis ways,
        # ring attention over the mesh's "model" axis
        # (parallel/sequence_parallel.py). The training step runs an
        # SP-aware twin of the model; the DENSE model built above keeps
        # serving every host-side eval path (identical params and math —
        # ring == dense is pinned by tests/test_attention.py), since an
        # SP model cannot apply outside shard_map (lax.axis_index).
        from distributed_tensorflow_tpu.models.transformer import (
            MiniTransformer,
            TransformerLM,
        )
        from distributed_tensorflow_tpu.parallel.mesh import (
            DATA_AXIS,
            MODEL_AXIS,
        )
        from distributed_tensorflow_tpu.parallel.sequence_parallel import (
            make_sp_eval_step,
            make_sp_span_stager,
            make_sp_train_step,
            reshape_for_sp,
            stage_batch_sp,
        )

        if not isinstance(model, (MiniTransformer, TransformerLM)):
            raise ValueError(
                f"--seq_parallel requires --model transformer or lm (an "
                f"attention model with a token axis to shard); got "
                f"--model {FLAGS.model!r}")
        if getattr(model, "moe_experts", 0):
            raise ValueError(
                "--moe_experts with --seq_parallel is not supported: "
                "token-sharded MoE routing (each shard routing its own "
                "tokens) is a different design than the expert-sharded "
                "--expert_parallel; pick one model-axis strategy")
        if mode != "sync":
            raise ValueError(
                "--seq_parallel requires sync mode (a device mesh); "
                "use --mode=sync")
        if model_axis < 2:
            raise ValueError(
                f"--seq_parallel shards the sequence --model_axis ways; "
                f"--model_axis={model_axis} shards nothing (use >= 2)")
        if model.seq_len % model_axis:
            raise ValueError(
                f"sequence length {model.seq_len} must divide into "
                f"--model_axis={model_axis} token blocks")
        if int(getattr(FLAGS, "attn_block", 0)) > 0:
            raise ValueError(
                "--attn_block (local blockwise attention) and "
                "--seq_parallel (ring attention) are mutually exclusive "
                "attention flavors — the SP step ring-attends; drop one")
        # the one flag SP genuinely cannot compose with (--device_data
        # composes as of r5: the resident split shards over the token
        # axis and every token shard of a data row draws the same
        # example rows — device_step.make_device_sp_train_step);
        # --accum_steps and --clip_norm compose as pre/post-reduction
        # gradient transforms with no SP interaction
        if getattr(FLAGS, "augment", False):
            raise ValueError(
                "--augment is not supported with --seq_parallel "
                "(augmentation crops/flips the image layout; token "
                "blocks have no spatial structure)")
        if getattr(FLAGS, "device_data", False) and span and n_procs > 1:
            raise ValueError(
                "--device_data with --sp_span_hosts is not supported: "
                "the resident split would need per-process token-axis "
                "tiles of every example; stage batches instead (the "
                "span-host stager uploads only each process's tile)")

        if is_lm:
            if model.seq_len >= 1024:
                # host-side evals (display, multi-host periodic/final)
                # run the TWIN, not the sharded step; at long context a
                # dense twin would reintroduce the O(S^2) score matrix
                # the SP/blockwise forms exist to avoid — rebuild it
                # blockwise (identical math, streamed memory)
                blk = next((b for b in (512, 256, 128, 64)
                            if model.seq_len % b == 0), None)
                if blk is not None:
                    model = TransformerLM(
                        vocab_size=model.vocab_size,
                        seq_len=model.seq_len, d_model=model.d_model,
                        num_heads=model.num_heads,
                        num_blocks=model.num_blocks,
                        mlp_ratio=model.mlp_dim // model.d_model,
                        compute_dtype=model.compute_dtype,
                        attn_block=blk, remat=model.remat,
                        ce_block=model.ce_block)
            # the SP twin ring-attends causally; identical params/math
            # to the dense model built above (blockwise/dense forms are
            # its host-side evaluators). ce_block carries over: inside
            # shard_map the streamed head runs on the LOCAL (B, S/P, d)
            # tile — its shard-local mean is exactly the per-token SP
            # derivation's loss seed, so the uniform pmean reduction is
            # unchanged (and the (B, S/P, V) logits never materialize,
            # which is the point at large vocab)
            sp_model = TransformerLM(
                vocab_size=model.vocab_size, seq_len=model.seq_len,
                d_model=model.d_model, num_heads=model.num_heads,
                num_blocks=model.num_blocks,
                mlp_ratio=model.mlp_dim // model.d_model,
                compute_dtype=model.compute_dtype, seq_axis=MODEL_AXIS,
                remat=model.remat, ce_block=model.ce_block)
        else:
            sp_model = MiniTransformer(
                image_size=model.image_size, channels=model.channels,
                num_classes=model.num_classes, d_model=model.d_model,
                num_heads=model.num_heads, num_blocks=model.num_blocks,
                mlp_ratio=model.mlp_dim // model.d_model,
                compute_dtype=model.compute_dtype, seq_axis=MODEL_AXIS,
                remat=model.remat)
        mesh = make_mesh(MeshSpec(data=-1, model=model_axis))
        if n_procs > 1 and not span:
            # the token ("model") axis must stay within a host: staging
            # feeds each process its batch slice with the FULL token
            # axis. Check the MESH rows directly — on real TPU slices
            # device ids follow physical topology, so a size comparison
            # against local_device_count can pass while a row still
            # mixes processes. --sp_span_hosts lifts this: the ring's
            # cross-host hops ride DCN and staging tiles both axes.
            for row in mesh.devices:
                if len({d.process_index for d in row}) != 1:
                    raise ValueError(
                        f"--seq_parallel with --model_axis={model_axis} "
                        f"puts devices from multiple hosts on one token-"
                        f"axis row of the mesh; each host must hold the "
                        f"full sequence — use a model_axis whose rows "
                        f"stay within one host's chips, or opt into "
                        f"cross-host ring hops with --sp_span_hosts")
        n_chips = mesh.devices.size
        data_ways = mesh.shape[DATA_AXIS]
        if FLAGS.batch_size % data_ways:
            raise ValueError(
                f"--batch_size={FLAGS.batch_size} must be divisible by "
                f"the {data_ways}-way data axis")
        if accum > 1 and (FLAGS.batch_size // data_ways) % accum:
            raise ValueError(
                f"each data shard's slice "
                f"({FLAGS.batch_size // data_ways} examples) must split "
                f"into {accum} equal microbatches")
        # span-host staging feeds the FULL global batch on every process
        # (drawn from the shared-seed dataset built at the top) and
        # uploads only its tile
        feed_batch = (FLAGS.batch_size if (span and n_procs > 1)
                      else local_batch_size(FLAGS.batch_size))
        state = replicate_state(mesh, state)
        step_fn = make_sp_train_step(sp_model, opt, mesh,
                                     keep_prob=FLAGS.keep_prob,
                                     per_token_targets=is_lm,
                                     grad_transform=clip,
                                     accum_steps=accum)
        eval_fn = make_sp_eval_step(sp_model, mesh,
                                    per_token_targets=is_lm)
        if span and n_procs > 1:
            stage_impl = make_sp_span_stager(mesh,
                                             per_token_targets=is_lm)
        else:
            stage_impl = lambda b: stage_batch_sp(
                mesh, b, per_token_targets=is_lm)
        if is_lm:
            # LM batches are already (B, S) tokens + (B, S) targets
            stage = stage_impl
        else:
            stage = lambda b: stage_impl(
                (reshape_for_sp(sp_model, b[0]), b[1]))
        restage = lambda s: replicate_state(mesh, s)
        # the split token-axis-sharded; the step samples inside shard_map
        put_split = lambda: put_device_data_sp(
            ds.train, mesh, is_lm, token_shape=(
                None if is_lm else (sp_model.seq_len, sp_model.token_dim)))
        chunk_step = lambda n: device_step.make_device_sp_train_step(
            sp_model, opt, mesh, FLAGS.batch_size,
            keep_prob=FLAGS.keep_prob, chunk=n, grad_transform=clip,
            per_token_targets=is_lm)
        if n_procs == 1:
            # periodic + final full-split evals run THROUGH the sharded
            # eval step on the live mesh state (the dense twin only
            # serves display evals and multi-host runs, where each
            # process holds its own split and the collective step has
            # no coherent global batch). Batch scaled by context length
            # times the data ways — per-DEVICE token budget, same
            # reasoning as _eval_batch_for's host-path budget.
            sp_full_eval = _make_sp_full_split_eval(
                eval_fn, stage, data_ways,
                batch_size=data_ways * _eval_batch_for(model, ds.meta))
    elif mode == "sync" and model_axis > 1:
        # tensor parallelism (+DP on the remaining devices): GSPMD layout,
        # XLA inserts the collectives — parallel/tensor_parallel.py
        from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS
        from distributed_tensorflow_tpu.parallel.tensor_parallel import (
            has_tp_specs,
            make_tp_eval_step,
            make_tp_train_step,
            shard_state_tp,
            stage_batch_tp,
        )

        if not has_tp_specs(state.params):
            raise ValueError(
                f"--model_axis={model_axis} but model {FLAGS.model!r} has no "
                f"tensor-parallel sharding rule — every parameter would "
                f"replicate and the extra devices would do redundant work. "
                f"Use --model_axis=1 (data parallelism) for this model."
            )
        # shape/axis divisibility is enforced at the library layer
        # (tensor_parallel._check_divisibility, raised from
        # shard_state_tp below) so non-CLI callers are protected too
        mesh = make_mesh(MeshSpec(data=-1, model=model_axis))
        n_chips = mesh.devices.size
        data_ways = mesh.shape[DATA_AXIS]
        if FLAGS.batch_size % data_ways:
            raise ValueError(
                f"--batch_size={FLAGS.batch_size} must be divisible by the "
                f"{data_ways}-way data axis"
            )
        if accum > 1 and (FLAGS.batch_size // accum) % data_ways:
            raise ValueError(
                f"each of the {accum} microbatches "
                f"({FLAGS.batch_size // accum} examples) must split over "
                f"the {data_ways}-way data axis"
            )
        feed_batch = local_batch_size(FLAGS.batch_size)
        state = shard_state_tp(state, mesh)
        step_fn = make_tp_train_step(model, opt, mesh, keep_prob=FLAGS.keep_prob,
                                     grad_transform=clip, accum_steps=accum,
                                     augment_fn=augment)
        eval_fn = make_tp_eval_step(model, mesh)
        stage = lambda b: stage_batch_tp(mesh, b)
        restage = lambda s: shard_state_tp(s, mesh)
        # GSPMD: the state's TP layout + the data-axis batch constraint
        # drive the partitioner
        chunk_step = lambda n: device_step.make_device_tp_train_step(
            model, opt, mesh, FLAGS.batch_size, keep_prob=FLAGS.keep_prob,
            chunk=n, grad_transform=clip, augment_fn=augment)
    elif mode == "sync":
        mesh = make_mesh()
        n_chips = mesh.devices.size
        if FLAGS.batch_size % n_chips:
            raise ValueError(
                f"--batch_size={FLAGS.batch_size} must be divisible by the "
                f"{n_chips} devices in the data mesh"
            )
        if accum > 1 and (FLAGS.batch_size // n_chips) % accum:
            raise ValueError(
                f"each device's batch slice "
                f"({FLAGS.batch_size // n_chips} examples) must split into "
                f"{accum} equal microbatches"
            )
        feed_batch = local_batch_size(FLAGS.batch_size)
        state = replicate_state(mesh, state)
        step_fn = make_dp_train_step(model, opt, mesh, keep_prob=FLAGS.keep_prob,
                                     grad_transform=clip, accum_steps=accum,
                                     augment_fn=augment)
        eval_fn = make_dp_eval_step(model, mesh)
        stage = lambda b: shard_batch(mesh, b)
        chunk_step = lambda n: device_step.make_device_dp_train_step(
            model, opt, mesh, FLAGS.batch_size, keep_prob=FLAGS.keep_prob,
            chunk=n, grad_transform=clip, augment_fn=augment)
    else:
        step_fn = make_train_step(model, opt, keep_prob=FLAGS.keep_prob,
                                  grad_transform=clip, accum_steps=accum,
                                  augment_fn=augment)
        eval_fn = make_eval_step(model)
        stage = None  # prefetch default: device_put to the default device
        chunk_step = lambda n: device_step.make_device_train_step(
            model, opt, FLAGS.batch_size, keep_prob=FLAGS.keep_prob,
            chunk=n, grad_transform=clip, augment_fn=augment)

    if getattr(FLAGS, "device_data", False):
        if jax.process_count() > 1 and mesh is None:
            raise ValueError(
                "--device_data under multi-process requires sync mode "
                "(a global mesh to replicate the split over)"
            )
        # the live state is the checkpoint's layout (no ``to_host``), a
        # restored one is placed on the mesh again; a display's batch is
        # this process's slice of the global one, which ``stage`` assembles
        return _train_device(
            FLAGS, ds, model, opt, state, mesh, n_chips, _DeviceLayout(
                span="device_chunk", put_data=put_split,
                build_chunk=chunk_step, to_live=restage,
                display=(eval_fn, stage or jax.device_put)))

    sc = _scaffold(FLAGS, ds, model, opt, mesh, n_chips,
                   full_eval=sp_full_eval)
    sv, logger, meter, stimer = sc.sv, sc.logger, sc.meter, sc.stimer
    eff, rmon, snt, els = sc.eff, sc.rmon, sc.snt, sc.els
    last_display = {}

    with sv.managed(state) as box:
        state, step = box.state, box.step
        _log_recovery(sv, logger, step, eff)
        sc.periodic_eval.prime(step)
        if restage is not None:
            # a restored checkpoint arrives as host arrays; re-place it on
            # the mesh layout (no-op when the state is already placed)
            state = restage(state)
        # background host->device staging; the accelerator never waits on
        # next_batch (the feed-dict bottleneck this build eliminates,
        # SURVEY.md §3.4)
        batches = prefetch_to_device(
            batch_iterator(ds.train, feed_batch, raw=FLAGS.raw_input),
            size=2,
            stage=stage,
        )
        profiling = False
        profile_done = not FLAGS.profile_dir
        compile_done = False
        try:
            meter.reset()
            while not sc.should_stop() and step < FLAGS.training_iter:
                t0 = time.perf_counter()
                batch = next(batches)
                stimer.add("host_wait", time.perf_counter() - t0)
                if step % FLAGS.display_step == 0:
                    # the upcoming training batch, before the update
                    last_display = _display_eval(
                        step, eval_fn, state.params, state.model_state,
                        eff, stimer, batch=batch)
                    _display_log(
                        step, last_display, logger,
                        lambda: _display_scalars(meter, stimer, eff, rmon),
                        eff, snt, lambda: _sentinel_host_state(state))
                if compile_done and not profile_done and not profiling:
                    jax.profiler.start_trace(FLAGS.profile_dir)
                    profiling = True
                    profile_stop_at = step + FLAGS.profile_steps
                if rmon is not None:
                    # the traced signature this dispatch specializes on
                    # (recompile sentry; ~µs, outside the timed window)
                    rmon.note_dispatch("train_step", batch)
                t0 = time.perf_counter()
                with trace_span("train_step", step=step), \
                        telemetry.armed("train_step", step=step):
                    state, step_m = step_fn(state, batch)
                stimer.add("dispatch", time.perf_counter() - t0)
                step += 1
                meter.step()
                stimer.steps()
                if sc.sync_every and step % sc.sync_every == 0:
                    # block on the metrics too: their tiny pmeans can
                    # still be in flight after the params' all-reduce
                    # completes, and a next program's gloo ops
                    # interleaving with them crashes the TCP pair
                    # (multi-process CPU; see collective_sync_cadence)
                    t0 = time.perf_counter()
                    with trace_span("device_sync", step=step), \
                            telemetry.armed("collective_sync", step=step):
                        jax.block_until_ready((state.params, step_m))
                    stimer.add("device", time.perf_counter() - t0)
                if not compile_done:
                    _close_compile_window(sc, state.params, step)
                    compile_done = True
                if profiling and step >= profile_stop_at:
                    jax.block_until_ready(state.params)
                    jax.profiler.stop_trace()
                    profiling = False
                    profile_done = True
                sc.periodic_eval(state, step)
                box.update(state, step)
                sc.checkpoint(state, step)
                if els is not None and els.poll(step):
                    # membership change due: the StateBox already holds
                    # this boundary's state — drain via the managed-exit
                    # save and re-form (raises ResizeRequired)
                    els.maybe_resize(step)
            jax.block_until_ready(state.params)
        finally:
            if profiling:
                jax.profiler.stop_trace()
            batches.close()

    return _finish(FLAGS, sc, model, state, ds, step, last_display,
                   full_eval=sp_full_eval)


def evaluate_only(FLAGS) -> dict[str, float]:
    """--eval_only: restore the latest checkpoint from ``--logdir`` and
    evaluate the FULL test split, no training. The reference has no
    evaluation entry point at all (SURVEY.md §5: the test split is never
    touched); this is the missing half of its checkpoint story — a saved
    model you can actually measure.

    Restores ONLY what evaluation needs — params, plus model_state
    (batch-norm statistics) for stateful models — so any checkpoint the
    framework writes evaluates regardless of the training-time
    ``--optimizer``/``--lr_schedule``/``--prng`` flags (optimizer slots
    and the rng key are never loaded). A stateful model's checkpoint
    without stored statistics is refused loudly rather than silently
    evaluated with untrained ones."""
    import numpy as np

    from distributed_tensorflow_tpu.checkpoint import latest_checkpoint
    from distributed_tensorflow_tpu.checkpoint.checkpoint import restore_latest

    found = latest_checkpoint(FLAGS.logdir)
    if found is None:
        raise FileNotFoundError(
            f"--eval_only: no checkpoint found in --logdir={FLAGS.logdir!r}"
        )
    ds = read_data_sets(FLAGS.data_dir, one_hot=True, dataset=FLAGS.dataset,
                        seed=FLAGS.seed,
                        seq_len=getattr(FLAGS, "seq_len", 256),
                        vocab_size=getattr(FLAGS, "vocab_size", 64))
    model = build_model_for(FLAGS, ds.meta)
    variables = model.init(jax.random.PRNGKey(FLAGS.seed))
    if getattr(model, "stateful", False):
        params_t, state_t = variables["params"], variables["state"]
    else:
        params_t, state_t = variables, ()

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        checkpoint_keys,
    )

    from distributed_tensorflow_tpu.utils.pytree import _BF16_TAG

    has_model_state = any(
        k.removeprefix(_BF16_TAG).startswith("model_state/")
        for k in checkpoint_keys(found[0]))
    template = {"params": params_t, "step": 0}
    if state_t != ():
        if not has_model_state:
            raise ValueError(
                f"--eval_only: checkpoint {found[0]} has no model_state "
                f"but model {FLAGS.model!r} is stateful (batch-norm) — "
                f"evaluating with untrained statistics would be silently "
                f"wrong"
            )
        template["model_state"] = state_t
    blob, step = restore_latest(FLAGS.logdir, template)
    m = evaluate(model, blob["params"], ds.test,
                 model_state=blob.get("model_state", ()),
                 batch_size=_eval_batch_for(model, ds.meta))
    print(f"step: {step} test accuracy: {m['accuracy']} "
          f"test loss: {m['loss']}")
    import json

    print(json.dumps({"step": step, "test_accuracy": m["accuracy"],
                      "test_loss": m["loss"], "dataset": FLAGS.dataset,
                      "data_source": ds.source}))
    return m


def _make_sp_full_split_eval(sp_eval_fn, stage, data_ways: int,
                             batch_size: int = 512):
    """Full-split evaluation THROUGH the sharded SP eval step, using the
    live on-mesh state (no host fetch, no dense-twin forward): the
    memory property that justifies SP holds during evaluation too.

    Single-process only — the sharded step is a collective over the
    global mesh, and in multi-host runs each process holds its OWN
    seeded split, so there is no coherent global batch to assemble; the
    multi-host path keeps the host-side twin eval (for the LM at long
    context the twin is REBUILT with blockwise attention — identical
    math, O(S*block) memory — so that path cannot reintroduce the dense
    O(S^2) wall; see the SP branch in train()).

    Remainder exactness: batches are quantized to the data axis; a final
    tail smaller than ``data_ways`` is evaluated by REPLICATING each
    tail example ``data_ways`` times — the mean over the replicated
    batch equals the mean over the tail exactly (equal per-example
    weights), so the weighted full-split metrics match the dense
    evaluation bit-for-bit in exact arithmetic."""
    import numpy as np

    def full_eval(state, split):
        xs_all, ys_all = split.images, split.labels
        n = len(xs_all)
        bs = max(data_ways, batch_size - batch_size % data_ways)
        total = {"loss": 0.0, "accuracy": 0.0}
        seen = 0
        i = 0
        while i < n:
            take = min(bs, n - i)
            take -= take % data_ways
            if take == 0:  # tail shorter than the data axis: replicate
                w = n - i
                xs = np.repeat(xs_all[i:], data_ways, axis=0)
                ys = np.repeat(ys_all[i:], data_ways, axis=0)
                i = n
            else:
                w = take
                xs, ys = xs_all[i:i + take], ys_all[i:i + take]
                i += take
            m = sp_eval_fn(state.params, stage((xs, ys)),
                           state.model_state)
            total = {k: total[k] + float(m[k]) * w for k in total}
            seen += w
        return {k: v / max(seen, 1) for k, v in total.items()}

    return full_eval


def _eval_batch_for(model, meta: dict) -> int:
    """Full-split evaluation batch size. The image-era default of 1000
    examples per eval batch is ~3 MB of activations; at LM context
    lengths the same 1000 is GIGABYTES (B*S*d activations + B*S*V
    logits — the 4k-context OOM this fixes). Scale so B*S stays
    ~256k tokens per eval batch."""
    if meta.get("kind") == "lm":
        return max(1, (1 << 18) // int(model.seq_len))
    return 1000


def _periodic_test_eval(FLAGS, sv, model, ds, logger, full_eval=None,
                        eff=None):
    """(state, step) -> None: full held-out evaluation every
    ``--eval_step`` steps (crossing semantics, so chunked loops that jump
    several steps per dispatch still evaluate once per boundary). Chief
    only — it is host-side work off the compiled path; the reference never
    evaluates on the test split at all (SURVEY.md §5 metrics), the north
    star requires it.

    With ``--validation_size`` the periodic evals run on the carved-out
    validation split (the classic protocol: tune against validation, touch
    the test split only at the end — the final ``--test_eval`` stays on
    test); without one they run on the test split directly."""
    from distributed_tensorflow_tpu.utils.pytree import (
        fetch_pytree,
        join_collective_fetch,
        needs_collective_fetch,
    )

    every = getattr(FLAGS, "eval_step", 0)
    if not every or every <= 0:
        noop = lambda state, step: None
        noop.prime = lambda step: None
        noop.last_result = lambda: None
        return noop
    val = getattr(ds, "validation", None)
    use_validation = val is not None and val.num_examples > 0
    split, name = (val, "validation") if use_validation else (ds.test, "test")
    state_box = {"done": 0, "last": None}

    def maybe_eval(state, step: int):
        if step // every <= state_box["done"]:
            return
        state_box["done"] = step // every
        # cross-host-sharded state: every process must join the collective
        # fetch (the boundary decision is step-based, so all hosts agree
        # without communicating); only the chief evaluates and prints. A
        # non-chief with locally-fetchable state contributes nothing.
        if not sv.is_chief:
            if needs_collective_fetch(state):
                # join the chief's cross-host gathers (params then
                # model_state, matching its fetch order) without paying a
                # full-model device->host copy nobody reads
                join_collective_fetch(state.params)
                join_collective_fetch(state.model_state)
                if not use_validation:
                    # record participation so the final eval's reuse
                    # decision stays symmetric with the chief's (no
                    # one-sided collective)
                    state_box["last"] = (step, None)
            return
        with trace_span("periodic_eval", step=step), \
                telemetry.armed("periodic_eval", step=step), \
                _charged(eff, "eval"):
            if full_eval is not None:
                # sharded SP eval on the live mesh state — no host fetch,
                # no dense-twin forward (single-process SP path)
                m = full_eval(state, split)
            else:
                params = fetch_pytree(state.params)
                model_state = fetch_pytree(state.model_state)
                m = evaluate(model, params, split, model_state=model_state,
                             batch_size=_eval_batch_for(model, ds.meta))
        if not use_validation:
            # end-of-run reuse is only sound when this WAS the test split;
            # chief and non-chief must gate identically or the final
            # eval's fetch decision goes one-sided (see _final_test_eval)
            state_box["last"] = (step, m)
        print(f"step: {step} {name} accuracy: {m['accuracy']} "
              f"{name} loss: {m['loss']}")
        logger.scalars(step, {f"{name}_accuracy": m["accuracy"],
                              f"{name}_loss": m["loss"]})

    def prime(step: int):
        # a resumed run starts counting boundaries from the restored step
        state_box["done"] = step // every

    maybe_eval.prime = prime
    # lets the end-of-run eval reuse a result computed at the final step
    # instead of re-running the full split and double-logging it
    maybe_eval.last_result = lambda: state_box["last"]
    return maybe_eval


def _final_test_eval(FLAGS, sv, periodic_eval, model, state, ds, logger,
                     step, full_eval=None):
    """End-of-run test evaluation (both loops): reuses the periodic eval's
    result when it already covered the final step. In multi-process runs
    the non-chief hosts only contribute the collective state fetch (when
    the sharding spans hosts) — the 10k-example inference and the print
    happen once, on the chief."""
    from distributed_tensorflow_tpu.utils.pytree import (
        fetch_pytree,
        join_collective_fetch,
        needs_collective_fetch,
    )

    if not FLAGS.test_eval:
        return None
    multiproc = jax.process_count() > 1
    last = periodic_eval.last_result()
    if last is not None and last[0] == step:
        test_metrics = last[1]  # scalars already logged at this step
        if test_metrics is None:
            # non-chief that joined the boundary-aligned collective fetch;
            # the chief printed/logged — nothing further to do here, and
            # skipping the fetch below mirrors the chief's reuse branch
            # (both sides must agree on whether a collective happens)
            return None
    else:
        if multiproc and not sv.is_chief:
            # only the collective case needs this process's participation;
            # locally-fetchable state would be a pointless full-model
            # device fetch discarded right after (same gate as the
            # periodic path)
            if needs_collective_fetch(state):
                join_collective_fetch(state.params)
                join_collective_fetch(state.model_state)
            return None
        if full_eval is not None:
            # sharded SP eval on the live mesh state (single-process)
            test_metrics = full_eval(state, ds.test)
        else:
            params = fetch_pytree(state.params)
            model_state = fetch_pytree(state.model_state)
            test_metrics = evaluate(model, params, ds.test,
                                    model_state=model_state,
                                    batch_size=_eval_batch_for(model,
                                                               ds.meta))
        logger.scalars(step, {"test_accuracy": test_metrics["accuracy"],
                              "test_loss": test_metrics["loss"]})
    print("test accuracy: ", test_metrics["accuracy"],
          "test loss: ", test_metrics["loss"])
    return test_metrics


class _HostCoordinator:
    """Cadenced cross-process agreement for the multi-host sync loops.

    Two decisions need host-level agreement: a stop (SIGTERM on one host,
    say) must take effect at the SAME step on every process — a process
    leaving the loop alone would deadlock the rest inside the next
    collective — and a checkpoint of cross-host-sharded state is itself a
    collective fetch every process must enter together
    (Supervisor.checkpoint_coordinated). Both ride ONE tiny allgather
    every ``--coord_steps`` steps rather than a DCN round-trip per loop
    iteration (the round-2 verdict's hot-path cost): between boundaries
    ``should_stop`` reads a cached flag and no host traffic happens.
    Crossing semantics (step // every) so chunked loops that jump several
    steps per dispatch still vote once per boundary; both loops MUST keep
    calling ``tick`` with the same step sequence or hosts deadlock in the
    vote. Worst-case stop latency is ``coord_steps`` extra steps —
    milliseconds of compute — and the final checkpoint still lands at the
    agreed exit step."""

    def __init__(self, sv, every: int, stimer=None, logger=None,
                 elastic_sv=None):
        import numpy as np
        from jax.experimental import multihost_utils

        self._sv = sv
        self._every = max(1, every)
        self._stop = False
        self._boundary = None
        self._np = np
        self._allgather = multihost_utils.process_allgather
        # elastic membership (r15): the vote carries each host's
        # liveness/departure bit, so a preemption notice on ONE host
        # becomes an agreed membership change on EVERY host at the same
        # boundary — epoch agreement rides the existing allgather, no
        # new collectives
        self._els = elastic_sv
        # straggler attribution (r12): the vote carries each host's mean
        # work-per-step (StepTimer.cumulative_work — host_wait+dispatch,
        # the column a straggler burns while its peers wait in the
        # collective); the chief turns the gathered column into the
        # step_skew_s / straggler_host scalars. Rides the EXISTING
        # allgather — no new sync points, two extra int32 per process.
        self._stimer = stimer
        self._logger = logger
        self._last_work = (0.0, 0)

    def should_stop(self) -> bool:
        return self._stop

    def _work_us_per_step(self) -> int:
        if self._stimer is None:
            return 0
        work_s, steps = self._stimer.cumulative_work()
        dw = work_s - self._last_work[0]
        dn = steps - self._last_work[1]
        self._last_work = (work_s, steps)
        if dn <= 0:
            return 0
        return min(int(dw / dn * 1e6), 2 ** 31 - 1)

    def tick(self, state, step: int) -> None:
        """Call once per loop iteration, after ``step`` advanced. At each
        boundary: one allgather of [stop?, chief-save-due?, token,
        work_us, departing?]; any stop vote stops everyone, a save vote
        routes every process into the coordinated checkpoint, and any
        departure bit delivers an agreed membership change to the
        elasticity supervisor (every host sees the same column, so all
        survivors install the same epoch at the same boundary — the
        drain then rides the normal exit machinery). The token column
        (random per process, row 0's wins) is the sharded checkpoint's
        per-attempt nonce — agreed HERE so the save itself stays
        collective-free. The work_us column is each host's mean
        work-per-step since the last vote (straggler attribution); the
        completed allgather is also the fleet's shared clock barrier —
        every host drops a ``coord_clock`` marker right after it, which
        tools/fleet_report.py uses to align the per-host span files
        onto one timeline."""
        import secrets

        boundary = step // self._every
        if boundary == self._boundary:
            return
        self._boundary = boundary
        work_us = self._work_us_per_step()
        depart = (self._els.local_departure_bit()
                  if self._els is not None else 0)
        with trace_span("coord_vote", step=step), \
                telemetry.armed("coord_vote_allgather", step=step):
            votes = self._allgather(self._np.asarray(
                [self._sv.should_stop(),
                 self._sv.checkpointer.cadence_due(),
                 secrets.randbits(31),
                 work_us,
                 depart],
                self._np.int32))
        # all hosts leave the allgather within network-jitter of each
        # other: the wall/monotonic pair sampled HERE is the per-host
        # clock-offset anchor (fleet_report matches boundary ids). The
        # marker also carries this host's own work_us: a straggler's
        # lost time hides in host_wait, which no per-step span covers —
        # persisting the vote's numerator into the span stream is what
        # lets the OFFLINE report attribute with the same precision as
        # the live scalar.
        telemetry.get_tracer().record_instant(
            "coord_clock", boundary=int(boundary), step=int(step),
            mono=time.monotonic(), work_us=int(work_us))
        votes = votes.reshape(-1, 5)
        if votes[:, 1].max():
            self._sv.checkpoint_coordinated(
                state, step, attempt=format(int(votes[0, 2]), "08x"))
        self._stop = bool(votes[:, 0].max())
        if self._els is not None and votes[:, 4].max():
            # every process sees the same departure column: the agreed
            # change becomes due on all of them at THIS boundary (the
            # loop's poll right after this tick picks it up)
            self._els.on_vote(votes[:, 4], step)
        if self._logger is not None and len(votes) > 1:
            work = votes[:, 3]
            if int(work.max()) > 0:
                self._logger.scalars(step, {
                    "step_skew_s": round(
                        float(int(work.max()) - int(work.min())) / 1e6, 6),
                    "straggler_host": float(int(work.argmax())),
                })


def _train_pipeline(FLAGS, ds, model, opt, state, mode,
                    model_axis) -> TrainResult:
    """--pipeline training: GPipe-style staged transformer blocks over
    the mesh's "model" axis (parallel/pipeline_parallel.py).

    The live state holds STACKED stage-sharded blocks; checkpoints stay
    in the standard layout (fetch_state_pp unstacks at every display /
    eval / cadence boundary, which is also when the StateBox updates —
    so clean exits and SIGTERM drains save the exact final state; a
    hard kill can lose at most the steps since the last boundary).
    Display prints the step's own training metrics (the device-resident
    mode's documented trade — the per-step host batch the reference's
    pre-update eval wants would stall the pipeline). --clip_norm runs
    the AXIS-AWARE transform (pp_clip_transform): the squared norm
    assembles in canonical block order over the stage axis before
    scaling, so replicated leaves stay bit-identical across stages (and
    trajectories across --virtual_stages layouts). --virtual_stages V
    runs the INTERLEAVED schedule (parallel/pp_schedule.py): each
    device owns V round-robin block groups and the fill/drain bubble
    shrinks ~V-fold — same math, bit-identical to V=1; checkpoints
    stay in the standard layout whatever V. With --device_data the
    split stages data-sharded into HBM (1/D a data row) and
    ``_train_device`` drives the chunked sampler
    (device_step.make_pp_device_train_step: every step samples its
    per-shard batch inside ``shard_map``) under this same contract."""
    from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS
    from distributed_tensorflow_tpu.parallel.pipeline_parallel import (
        fetch_state_pp,
        make_pp_train_step,
        pp_clip_transform,
        shard_state_pp,
        stage_batch_pp,
    )
    from distributed_tensorflow_tpu.parallel.pp_schedule import (
        build_zb_schedule,
        normalize_pp_schedule,
        validate_pp_layout,
        validate_zb_layout,
    )

    if ds.meta.get("kind") != "lm":
        raise ValueError("--pipeline stages transformer blocks; use "
                         "--model lm --dataset lm")
    if mode != "sync":
        raise ValueError("--pipeline requires sync mode (a device mesh)")
    if model_axis < 2:
        raise ValueError(f"--pipeline stages blocks --model_axis ways; "
                         f"--model_axis={model_axis} stages nothing")
    if jax.process_count() > 1:
        raise ValueError("--pipeline is single-process in this version "
                         "(the stage ring would need the multi-host "
                         "coordinator); use --seq_parallel "
                         "--sp_span_hosts for cross-host model axes")
    if getattr(FLAGS, "augment", False):
        raise ValueError("--augment is not supported with --pipeline")
    if max(1, getattr(FLAGS, "accum_steps", 1)) > 1:
        raise ValueError("--accum_steps is redundant with --pipeline: "
                         "microbatching IS the pipeline schedule — set "
                         "--pp_microbatches instead")

    vstages = max(1, int(getattr(FLAGS, "virtual_stages", 1)))
    micro = int(getattr(FLAGS, "pp_microbatches", 0)) or model_axis
    sched_name = normalize_pp_schedule(
        getattr(FLAGS, "pp_schedule", "auto"), vstages)
    # layout constraints up front (clear errors instead of mid-trace):
    # K*V must divide the blocks, V>1 schedules microbatch rounds of K,
    # and zb additionally needs >= 2 blocks per virtual-stage group
    validate_pp_layout(model.num_blocks, model_axis, vstages,
                       microbatches=micro)
    if sched_name == "zb":
        validate_zb_layout(model.num_blocks, model_axis, vstages,
                           microbatches=micro)
        zs = build_zb_schedule(model_axis, micro, vstages)
        # the schedule's cost facts land in the span stream once, so
        # trace_view/fleet timelines show WHICH table the run compiled
        telemetry.get_tracer().record_instant(
            "zb_schedule", k_stages=model_axis, microbatches=micro,
            virtual_stages=vstages, ticks=zs.num_ticks, **zs.counts,
            useful_tick_fraction=round(zs.useful_tick_fraction, 4))
    clip = (pp_clip_transform(FLAGS.clip_norm, virtual_stages=vstages)
            if getattr(FLAGS, "clip_norm", 0.0) > 0 else None)
    mesh = make_mesh(MeshSpec(data=-1, model=model_axis))
    n_chips = mesh.devices.size
    data_ways = mesh.shape[DATA_AXIS]
    if FLAGS.batch_size % data_ways:
        raise ValueError(f"--batch_size={FLAGS.batch_size} must divide "
                         f"over the {data_ways}-way data axis")
    if (FLAGS.batch_size // data_ways) % micro:
        raise ValueError(
            f"each data shard's slice ({FLAGS.batch_size // data_ways}) "
            f"must split into {micro} microbatches (--pp_microbatches)")

    to_live = lambda s: shard_state_pp(s, mesh, virtual_stages=vstages)
    to_host = lambda s: fetch_state_pp(s, model, k_stages=model_axis,
                                       virtual_stages=vstages)
    if getattr(FLAGS, "device_data", False):
        return _train_device(
            FLAGS, ds, model, opt, state, mesh, n_chips, _DeviceLayout(
                span="pp_chunk_zb" if sched_name == "zb" else "pp_chunk",
                put_data=lambda: put_device_data(ds.train, mesh,
                                                 data_sharded=True),
                build_chunk=lambda n: device_step.make_pp_device_train_step(
                    model, opt, mesh, FLAGS.batch_size, micro,
                    keep_prob=FLAGS.keep_prob, chunk=n, grad_transform=clip,
                    virtual_stages=vstages, schedule=sched_name),
                to_live=to_live, to_host=to_host))

    step_fn = make_pp_train_step(model, opt, mesh, micro,
                                 keep_prob=FLAGS.keep_prob,
                                 grad_transform=clip,
                                 virtual_stages=vstages,
                                 schedule=sched_name)
    sc = _scaffold(FLAGS, ds, model, opt, mesh, n_chips)
    sv, logger, meter, stimer = sc.sv, sc.logger, sc.meter, sc.stimer
    eff, rmon, snt, els = sc.eff, sc.rmon, sc.snt, sc.els
    last_display = {}
    eval_every = max(0, getattr(FLAGS, "eval_step", 0))

    with sv.managed(state) as box:
        step = box.step
        _log_recovery(sv, logger, step, eff)
        sc.periodic_eval.prime(step)
        pp_state = to_live(box.state)
        compile_done = False
        meter.reset()
        while not sv.should_stop() and step < FLAGS.training_iter:
            t0 = time.perf_counter()
            batch = ds.train.next_batch(FLAGS.batch_size)
            staged = stage_batch_pp(mesh, batch)
            stimer.add("host_wait", time.perf_counter() - t0)
            if rmon is not None:
                rmon.note_dispatch("pp_step", staged)
            t0 = time.perf_counter()
            # the zb schedule gets its own span name so the PR-6
            # timeline distinguishes B/W-split steps from AD-backward
            # ones (the zb_schedule instant carries the tick counts)
            span_name = ("pp_step_zb" if sched_name == "zb"
                         else "pp_step")
            with trace_span(span_name, step=step,
                            schedule=sched_name), \
                    telemetry.armed(span_name, step=step):
                pp_state, m = step_fn(pp_state, staged)
            stimer.add("dispatch", time.perf_counter() - t0)
            step += 1
            meter.step(FLAGS.batch_size)
            stimer.steps()
            if not compile_done:
                _close_compile_window(sc, pp_state.params, step)
                compile_done = True
            # a due membership change pulls the next checkpoint boundary
            # to THIS step (the standard-layout fetch below is the drain
            # state the re-formed world restores)
            due = els is not None and els.poll(step)
            boundary = (step % FLAGS.display_step == 0
                        or (eval_every and step % eval_every == 0)
                        or sv.checkpointer.cadence_due()
                        or due)
            if boundary:
                # the standard-layout fetch blocks on the step's device
                # work — the PP host loop's one device-wait site (there
                # is no cadenced block_until_ready here)
                t0 = time.perf_counter()
                with trace_span("boundary_fetch", step=step), \
                        telemetry.armed("pp_boundary_fetch", step=step), \
                        _charged(eff, "ckpt"):
                    host = to_host(pp_state)
                stimer.add("device", time.perf_counter() - t0)
                box.update(host, step)
                if step % FLAGS.display_step == 0:
                    last_display = {k: float(v) for k, v in m.items()}
                    _display_log(
                        step, last_display, logger,
                        lambda: _display_scalars(meter, stimer, eff, rmon),
                        eff, snt, host)
                sc.periodic_eval(host, step)
                sc.checkpoint(host, step)
                if due:
                    els.maybe_resize(step)
        jax.block_until_ready(pp_state.params)
        host = to_host(pp_state)
        box.update(host, step)

    return _finish(FLAGS, sc, model, host, ds, step, last_display)


def _train_zero(FLAGS, ds, model, opt, state, mode, accum, augment_fn,
                model_axis) -> TrainResult:
    """--zero training: ZeRO-sharded synchronous data parallelism
    (parallel/zero.py). Level 1 shards the optimizer state 1/D per data
    rank (grads reduce-scatter, one all_gather rebuilds the updated
    replicated params); level 3 keeps the params themselves sharded and
    gathers them inside forward/backward. Trajectories are BIT-IDENTICAL
    to replicated sync DP (tests/test_zero.py) — only the collective
    pattern and the per-chip footprint change.

    The live state holds the ZeRO (flat-chunk) layout between steps;
    checkpoints stay in the STANDARD layout (``fetch_state_zero`` at
    display / eval / cadence boundaries, which is also when the StateBox
    updates — the PP loops' contract: clean exits and SIGTERM drains
    save the exact final state, a hard kill loses at most the steps
    since the last boundary, and a ``--zero`` run restores a replicated
    checkpoint and vice versa). --clip_norm runs the AXIS-AWARE
    transform (``zero_clip_transform``): every in-step grad leaf is a
    distinct 1/D shard, so squared-norm partials psum over the data
    axis before one scale applies everywhere."""
    from distributed_tensorflow_tpu.parallel.zero import (
        _check_level,
        fetch_state_zero,
        make_zero_eval_step,
        make_zero_train_step,
        shard_state_zero,
        zero_clip_transform,
    )

    level = _check_level(FLAGS.zero)
    # the library-layer re-checks (the flags validator is the CLI front
    # door; non-CLI callers land here)
    if mode != "sync":
        raise ValueError(f"--zero={level} requires sync mode (a device "
                         f"mesh with a data axis to shard over); got "
                         f"mode={mode!r}")
    if model_axis > 1 or getattr(FLAGS, "pipeline", False) or \
            getattr(FLAGS, "seq_parallel", False) or \
            getattr(FLAGS, "expert_parallel", False):
        raise ValueError(f"--zero={level} shards the whole TrainState "
                         f"over the DATA axis and cannot compose with a "
                         f"model-axis strategy (--model_axis>1/--pipeline/"
                         f"--seq_parallel/--expert_parallel) — drop one")
    if jax.process_count() > 1:
        raise ValueError(f"--zero={level} is single-process in this "
                         f"version (cross-host state shards would need "
                         f"the sharded-checkpoint collective fetch)")
    mesh = make_mesh()
    n_chips = mesh.devices.size
    if n_chips == 1:
        print(f"--zero={level} on a 1-chip mesh: the data axis has "
              f"nothing to shard over — identical math to replicated "
              f"DP, no memory or comm saving (legal, but pointless)")
    if FLAGS.batch_size % n_chips:
        raise ValueError(
            f"--batch_size={FLAGS.batch_size} must be divisible by the "
            f"{n_chips} devices in the data mesh")
    if accum > 1 and (FLAGS.batch_size // n_chips) % accum:
        raise ValueError(
            f"each device's batch slice ({FLAGS.batch_size // n_chips} "
            f"examples) must split into {accum} equal microbatches")
    clip = (zero_clip_transform(FLAGS.clip_norm)
            if getattr(FLAGS, "clip_norm", 0.0) > 0 else None)
    overlap = bool(getattr(FLAGS, "zero_overlap", False))
    bucket_mb = float(getattr(FLAGS, "zero_bucket_mb", 4.0) or 4.0)
    if overlap:
        # the overlap pattern's analytic facts land in the span stream
        # once (the prefetched gather + bucketed scatter are inside the
        # compiled step — this instant is their host-visible footprint)
        from distributed_tensorflow_tpu.parallel.zero import (
            n_buckets,
            zero_exposed_comm_bytes,
            zero_memory_budget,
        )

        # one consistent axis width for every fact in the instant (a
        # 1-chip run prices the 2-way fallback config like the bench)
        d_eff = max(2, n_chips)
        g = zero_memory_budget(model, opt, d_eff)["param_bytes"]
        telemetry.get_tracer().record_instant(
            "zero_overlap", level=level, bucket_mb=bucket_mb,
            buckets=n_buckets(model, d_eff, bucket_mb),
            exposed_bytes=zero_exposed_comm_bytes(
                g, g, level, d_eff, True, bucket_mb))

    eval_fn = make_zero_eval_step(model, mesh, level)
    stage = lambda b: shard_batch(mesh, b)
    to_live = lambda s: shard_state_zero(s, mesh, level)
    to_host = lambda s: fetch_state_zero(s, model, level)
    if getattr(FLAGS, "device_data", False):
        # the split replicated, as under plain DP: every rank samples its
        # own rows with the DATA-folded key, the rows of a replicated-DP
        # run, so a resume inside a chunk lands on its trajectory bit for bit
        return _train_device(
            FLAGS, ds, model, opt, state, mesh, n_chips, _DeviceLayout(
                span="zero_chunk_overlap" if overlap else "zero_chunk",
                put_data=lambda: put_device_data(ds.train, mesh),
                build_chunk=lambda n: device_step.make_zero_device_train_step(
                    model, opt, mesh, level, FLAGS.batch_size,
                    keep_prob=FLAGS.keep_prob, chunk=n, grad_transform=clip,
                    augment_fn=augment_fn, overlap=overlap,
                    bucket_mb=bucket_mb),
                to_live=to_live, to_host=to_host, display=(eval_fn, stage)))

    step_fn = make_zero_train_step(model, opt, mesh, level,
                                   keep_prob=FLAGS.keep_prob,
                                   grad_transform=clip, accum_steps=accum,
                                   augment_fn=augment_fn,
                                   overlap=overlap, bucket_mb=bucket_mb)
    sc = _scaffold(FLAGS, ds, model, opt, mesh, n_chips)
    sv, logger, meter, stimer = sc.sv, sc.logger, sc.meter, sc.stimer
    eff, rmon, snt, els = sc.eff, sc.rmon, sc.snt, sc.els
    last_display = {}
    eval_every = max(0, getattr(FLAGS, "eval_step", 0))

    with sv.managed(state) as box:
        step = box.step
        _log_recovery(sv, logger, step, eff)
        sc.periodic_eval.prime(step)
        z_state = to_live(box.state)
        host = box.state
        batches = prefetch_to_device(
            batch_iterator(ds.train, FLAGS.batch_size,
                           raw=FLAGS.raw_input),
            size=2,
            stage=stage,
        )
        compile_done = False
        profiling = False
        profile_done = not FLAGS.profile_dir
        try:
            meter.reset()
            while not sv.should_stop() and step < FLAGS.training_iter:
                t0 = time.perf_counter()
                batch = next(batches)
                stimer.add("host_wait", time.perf_counter() - t0)
                if step % FLAGS.display_step == 0:
                    # reference display semantics: dropout-off eval of
                    # the upcoming batch before the update
                    # (MNISTDist.py:179-182) — level 3 gathers the
                    # param chunks inside the sharded eval step
                    last_display = _display_eval(
                        step, eval_fn, z_state.params, z_state.model_state,
                        eff, stimer, batch=batch)
                    # `host` is this displayed step's state in the
                    # standard layout (fetched at the same boundary)
                    _display_log(
                        step, last_display, logger,
                        lambda: _display_scalars(meter, stimer, eff, rmon),
                        eff, snt, host)
                if compile_done and not profile_done and not profiling:
                    jax.profiler.start_trace(FLAGS.profile_dir)
                    profiling = True
                    profile_stop_at = step + FLAGS.profile_steps
                if rmon is not None:
                    rmon.note_dispatch("zero_step", batch)
                t0 = time.perf_counter()
                # own span name under --zero_overlap so the timeline
                # separates the bucketed/prefetched collective pattern
                zspan = "zero_step_overlap" if overlap else "zero_step"
                with trace_span(zspan, step=step), \
                        telemetry.armed(zspan, step=step):
                    z_state, step_m = step_fn(z_state, batch)
                stimer.add("dispatch", time.perf_counter() - t0)
                step += 1
                meter.step()
                stimer.steps()
                if sc.sync_every and step % sc.sync_every == 0:
                    t0 = time.perf_counter()
                    with trace_span("device_sync", step=step), \
                            telemetry.armed("collective_sync", step=step):
                        jax.block_until_ready((z_state.params, step_m))
                    stimer.add("device", time.perf_counter() - t0)
                if not compile_done:
                    _close_compile_window(sc, z_state.params, step)
                    compile_done = True
                if profiling and step >= profile_stop_at:
                    jax.block_until_ready(z_state.params)
                    jax.profiler.stop_trace()
                    profiling = False
                    profile_done = True
                due = els is not None and els.poll(step)
                boundary = (step % FLAGS.display_step == 0
                            or (eval_every and step % eval_every == 0)
                            or sv.checkpointer.cadence_due()
                            or step >= FLAGS.training_iter
                            or due)
                if boundary:
                    with trace_span("boundary_fetch", step=step), \
                            telemetry.armed("zero_boundary_fetch",
                                            step=step), \
                            _charged(eff, "ckpt"):
                        host = to_host(z_state)
                        box.update(host, step)
                    sc.periodic_eval(host, step)
                    sc.checkpoint(host, step)
                    if due:
                        els.maybe_resize(step)
            jax.block_until_ready(z_state.params)
        finally:
            if profiling:
                jax.profiler.stop_trace()
            batches.close()
        host = to_host(z_state)
        box.update(host, step)

    return _finish(FLAGS, sc, model, host, ds, step, last_display)




def _train_device(FLAGS, ds, model, opt, state, mesh, n_chips,
                  layout: _DeviceLayout) -> TrainResult:
    """--device_data training, every layout of it (plain: one chip, DP,
    TP, SP, EP; ZeRO; pipeline): the train split resident in HBM, every
    step's batch sampled on the device from the step's PRNG, ``lax.scan``
    running ``--device_chunk`` steps a dispatch (training/device_step):
    per training step nothing crosses the host boundary.

    ``layout`` says what differs (``_DeviceLayout``). Where the live state
    is not the checkpoint's layout it is fetched into that layout only at
    boundaries: a display step, an ``--eval_step`` crossed inside the
    chunk, a save that is due, the last step, a resize that is due. That
    is when the StateBox updates, so clean exits and SIGTERM drains save
    the exact final state and a hard kill loses at most the steps since
    the last boundary. A display is the reference's (dropout off, before
    the update, ``MNISTDist.py:179-182``) on one fresh host batch, or,
    where the layout stages none, the chunk's last training metrics."""
    with trace_span("data_put"):
        # the transfer is asynchronous: the span ends with the split
        # resident on the device
        data = jax.block_until_ready(layout.put_data())
    chunk = max(1, math.gcd(FLAGS.display_step, max(1, FLAGS.device_chunk)))
    if chunk != FLAGS.device_chunk:
        print(f"--device_chunk={FLAGS.device_chunk} clamped to {chunk} so "
              f"chunks land on --display_step={FLAGS.display_step} "
              f"boundaries (dispatch amortization shrinks accordingly)")
    chunk_fns: dict[int, Callable] = {}  # one compiled chunk a length

    sc = _scaffold(FLAGS, ds, model, opt, mesh, n_chips)
    sv, logger, meter, stimer = sc.sv, sc.logger, sc.meter, sc.stimer
    eff, rmon, snt, els, coord = sc.eff, sc.rmon, sc.snt, sc.els, sc.coord
    eval_every = max(0, getattr(FLAGS, "eval_step", 0))
    last_display = {}
    chunks_done = 0

    def log_display(step):
        # the sentinel's last good state: the checkpoint-layout copy of
        # this boundary, or a host snapshot of the live (donated) state
        _display_log(
            step, last_display, logger,
            lambda: _display_scalars(meter, stimer, eff, rmon), eff, snt,
            host if layout.to_host is not None
            else lambda: _sentinel_host_state(live))

    with sv.managed(state) as box:
        host, step = box.state, box.step
        _log_recovery(sv, logger, step, eff)
        sc.periodic_eval.prime(step)
        live = layout.to_live(host) if layout.to_live is not None else host
        compile_done = False
        profiling = False
        profile_done = not FLAGS.profile_dir
        meter.reset()
        while not sc.should_stop() and step < FLAGS.training_iter:
            if layout.display is not None and step % FLAGS.display_step == 0:
                eval_fn, stage = layout.display
                last_display = _display_eval(
                    step, eval_fn, live.params, live.model_state, eff, stimer,
                    draw=lambda: stage(ds.train.next_batch(
                        local_batch_size(FLAGS.batch_size))))
                log_display(step)
            if compile_done and not profile_done and not profiling:
                jax.profiler.start_trace(FLAGS.profile_dir)
                profiling = True
                profile_stop_at = step + max(FLAGS.profile_steps, chunk)
            # realign to display boundaries after a resume from an arbitrary
            # checkpointed step, then cap at the remaining step budget
            to_boundary = -step % FLAGS.display_step or chunk
            length = min(chunk, to_boundary, FLAGS.training_iter - step)
            if rmon is not None:
                # the chunk's LENGTH is the signature the scan specializes on
                rmon.note_dispatch(layout.span, signature=(length,))
            t0 = time.perf_counter()
            with trace_span(layout.span, step=step, length=length), \
                    telemetry.armed(layout.span, step=step, length=length):
                fn = chunk_fns.get(length)
                if fn is None:
                    fn = chunk_fns[length] = layout.build_chunk(length)
                live, train_m = fn(live, data)
            stimer.add("dispatch", time.perf_counter() - t0)
            step += length
            meter.step(length * FLAGS.batch_size)
            stimer.steps(length)
            chunks_done += 1
            if sc.sync_every and \
                    chunks_done % max(1, sc.sync_every // chunk) == 0:
                # metrics included: their in-flight pmeans must not interleave
                # with the next program's gloo ops (collective_sync_cadence)
                t0 = time.perf_counter()
                with trace_span("device_sync", step=step), \
                        telemetry.armed("collective_sync", step=step):
                    jax.block_until_ready((live.params, train_m))
                stimer.add("device", time.perf_counter() - t0)
            if not compile_done:
                _close_compile_window(sc, live.params, step)
                compile_done = True
            if profiling and step >= profile_stop_at:
                jax.block_until_ready(live.params)
                jax.profiler.stop_trace()
                profiling = False
                profile_done = True
            # without a coordinator a due membership change makes this
            # chunk's end a boundary; with one the change becomes due in
            # the vote, so the poll follows the tick
            due = coord is None and els is not None and els.poll(step)
            if layout.to_host is None:
                host = live
            else:
                # an eval boundary by CROSSING: chunks align to
                # display_step, not eval_step, and can jump clean over a
                # multiple of it; periodic_eval evaluates once a crossing
                if not (step % FLAGS.display_step == 0
                        or (eval_every and (step - length) // eval_every
                            != step // eval_every)
                        or sv.checkpointer.cadence_due()
                        or step >= FLAGS.training_iter or due):
                    continue
                # the fetch blocks on the chunk's device work: booked in
                # the breakdown's device column
                t0 = time.perf_counter()
                with trace_span("boundary_fetch", step=step), \
                        telemetry.armed("boundary_fetch", step=step), \
                        _charged(eff, "ckpt"):
                    host = layout.to_host(live)
                stimer.add("device", time.perf_counter() - t0)
            box.update(host, step)
            if layout.display is None and step % FLAGS.display_step == 0:
                last_display = {k: float(v) for k, v in train_m.items()}
                log_display(step)
            sc.periodic_eval(host, step)
            sc.checkpoint(host, step)
            if coord is not None:
                due = els is not None and els.poll(step)
            if due:
                # the StateBox holds this boundary's state: drain via the
                # managed-exit save and re-form (raises ResizeRequired)
                els.maybe_resize(step)
        jax.block_until_ready(live.params)
        if profiling:
            jax.profiler.stop_trace()
        if layout.to_host is not None:
            host = layout.to_host(live)
            box.update(host, step)

    return _finish(FLAGS, sc, model, host, ds, step, last_display)
