"""Efficiency accounting: FLOPs budgets, MFU, and goodput.

After the PR-6 telemetry spine the repo can say where a step's
milliseconds went, but not how much of the HARDWARE they bought. This
module is the accounting layer behind three scalars every training loop
now emits next to ``images_per_sec``:

- ``model_flops_per_sec`` — model FLOPs actually retired per second
  (training FLOPs per example x examples/sec; the Megatron-LM
  "model FLOPs" convention — rematerialization and other implementation
  FLOPs deliberately NOT counted, so the number is comparable across
  implementations).
- ``mfu`` — model FLOPs utilization: ``model_flops_per_sec`` over the
  hardware's peak (Narayanan et al. 2021; Chowdhery et al. 2022's
  refinement is the same ratio with this module's model-FLOPs
  numerator). The headline metric of the large-scale-training
  literature, now a per-window scalar here.
- ``goodput`` — productive fraction of wall time: 1 minus the time
  charged to stalls (restore, checkpoint writes/fetches, display and
  periodic evals, the first-step XLA compile) over the wall time since
  the loop started. ``images_per_sec`` already prices the steady state;
  goodput prices everything AROUND it.

``flops_budget(model, batch)`` follows the ``zero_memory_budget`` dual
pattern: an ANALYTIC per-layer table that works chip-less (the loops and
the degraded bench record use it), plus an optional jitted-lowering
``cost_analysis()`` cross-check where the backend reports FLOPs
(``xla=True``; ``tools/trace_ops.py --flops`` prints both).

Peak FLOP/s resolves in order: ``--mfu_peak_flops`` override, a table of
known TPU chips (by ``device_kind``), else a one-shot cached matmul
calibration on the local backend — so MFU stays meaningful (measured
rate vs measured achievable peak) even on the CPU test mesh.

stdlib-only at import time (jax is imported lazily inside the functions
that need it) so the flags validator and bench's host-only phases can
import this from anywhere, like utils/telemetry.
"""

from __future__ import annotations

import threading
import time

# training FLOPs ~= forward + backward; the backward pass costs ~2x the
# forward (grads wrt both activations and weights) — the standard 3x
# accounting (Kaplan et al. 2020; Megatron-LM's 6ND has the same factor)
TRAIN_FLOPS_MULTIPLIER = 3

# bf16 peak FLOP/s per chip, keyed by the exact ``device_kind`` string
# JAX reports (Google Cloud TPU documentation, per-generation system
# architecture pages). "TPU v5 lite" is what a v5e chip reports —
# chip_smoke.py prints the string, tests/test_efficiency.py pins it. A
# ``tpu`` platform whose kind is not a key here is an error, not a
# default: an MFU over a guessed peak is not a measurement.
TPU_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 45e12,
}

# matmul calibration (the CPU test mesh only — never a ``tpu`` platform):
# one square f32 matmul timed best-of-reps; achieved FLOP/s stands in
# for peak, labeled ``matmul_calibration``. Cached per process — the
# loops must not pay it per run.
CALIBRATE_DIM = 1536
CALIBRATE_REPS = 3

_PEAK_CACHE: dict = {}
_PEAK_LOCK = threading.Lock()


def _conv_flops(kh, kw, cin, cout, hout, wout):
    return 2 * kh * kw * cin * cout * hout * wout


def _dense_flops(m, n):
    return 2 * m * n


def _ceil_div(a, b):
    return -(-a // b)


def _cnn_rows(model) -> list[dict]:
    s = model.image_size
    s2 = _ceil_div(s, 2)
    rows = [
        {"layer": "conv1 5x5", "flops": _conv_flops(5, 5, model.channels, 32, s, s)},
        {"layer": "conv2 5x5", "flops": _conv_flops(5, 5, 32, 64, s2, s2)},
        {"layer": "dense1", "flops": _dense_flops(model.flat_dim, model.hidden_units)},
        {"layer": "logits", "flops": _dense_flops(model.hidden_units, model.num_classes)},
    ]
    return rows


def _mlp_rows(model) -> list[dict]:
    return [
        {"layer": "hidden", "flops": _dense_flops(model.flat_dim, model.hidden_units)},
        {"layer": "logits", "flops": _dense_flops(model.hidden_units, model.num_classes)},
    ]


def _resnet_rows(model) -> list[dict]:
    s = model.image_size
    rows = [{"layer": "stem 3x3",
             "flops": _conv_flops(3, 3, model.channels, model.widths[0], s, s)}]
    cin = model.widths[0]
    size = s
    for si, width in enumerate(model.widths):
        for b in range(model.n):
            stride = 2 if (si > 0 and b == 0) else 1
            if stride == 2:
                size = _ceil_div(size, 2)
            f = (_conv_flops(3, 3, cin, width, size, size)
                 + _conv_flops(3, 3, width, width, size, size))
            if stride != 1 or cin != width:
                f += _conv_flops(1, 1, cin, width, size, size)
            rows.append({"layer": f"stage{si}/block{b}", "flops": f})
            cin = width
    rows.append({"layer": "head",
                 "flops": _dense_flops(model.widths[-1], model.num_classes)})
    return rows


def _transformer_rows(model) -> list[dict]:
    """MiniTransformer / TransformerLM (MoE included): per-EXAMPLE
    forward FLOPs. Attention is what the MODEL needs, not what a form
    of it computes: 2*S^2*d each for scores and values over the whole
    score matrix in the classifier, over its causal half in the LM (a
    token attends to S/2 keys on average — the count of
    ``benchmark/harness/flops.py``, so the ``mfu`` scalar of a display
    row agrees with the benchmark's ``step_mfu``; the blockwise and
    ring forms still compute the masked half, which is no model
    work); a top-1 switch MoE MLP moves each token through
    exactly one expert, so its per-token compute equals the dense MLP
    (capacity-dropped tokens make this a slight over-count, the
    standard convention)."""
    s = model.seq_len
    d = model.d_model
    mlp = model.mlp_dim
    rows = []
    if hasattr(model, "vocab_size"):  # TransformerLM: lookup embed, LM head
        head = {"layer": "lm_head", "flops": s * _dense_flops(d, model.vocab_size)}
    else:  # MiniTransformer: input projection + pooled classifier head
        rows.append({"layer": "embed_proj",
                     "flops": s * _dense_flops(model.token_dim, d)})
        head = {"layer": "cls_head", "flops": _dense_flops(d, model.num_classes)}
    causal = hasattr(model, "vocab_size")
    per_block = (
        4 * s * _dense_flops(d, d)        # q, k, v, out projections
        + 2 * (2 * s * s * d) // (2 if causal else 1)  # QK^T + attn*V
        + 2 * s * _dense_flops(d, mlp)    # MLP (or one switch expert) up+down
    )
    for b in range(model.num_blocks):
        rows.append({"layer": f"block{b}", "flops": per_block})
    rows.append(head)
    return rows


def _analytic_rows(model) -> list[dict]:
    name = type(model).__name__
    if name == "DeepCNN":
        return _cnn_rows(model)
    if name == "MLP":
        return _mlp_rows(model)
    if name in ("ResNet", "ResNet20", "ResNet32"):
        return _resnet_rows(model)
    if name in ("MiniTransformer", "TransformerLM"):
        return _transformer_rows(model)
    raise ValueError(
        f"no analytic FLOPs rule for model type {name!r} — efficiency "
        f"accounting knows deep_cnn/mlp/resnet*/transformer/lm")


def xla_cost_flops(model, batch_size: int) -> float | None:
    """The dual pattern's other half: FLOPs per TRAINING step from the
    jitted lowering's ``cost_analysis()`` where the backend reports it
    (None where it doesn't — never an error). Costs a lowering+compile:
    a CLI/bench tool, not a hot-loop call."""
    try:
        import jax
        import jax.numpy as jnp

        if getattr(model, "stateful", False):
            return None  # (params, state) protocol: skip the cross-check
        if hasattr(model, "vocab_size"):  # LM: token batch
            x = jnp.zeros((batch_size, model.seq_len), jnp.int32)
            y = jnp.zeros((batch_size, model.seq_len), jnp.int32)

            def loss_fn(params):
                logits = model.apply(params, x)
                lp = jax.nn.log_softmax(logits.astype(jnp.float32))
                return -jnp.mean(jnp.take_along_axis(lp, y[..., None],
                                                     axis=-1))
        else:
            feat = model.image_size * model.image_size * model.channels
            x = jnp.zeros((batch_size, feat), jnp.float32)
            y = jnp.zeros((batch_size, model.num_classes), jnp.float32)

            def loss_fn(params):
                logits = model.apply(params, x)
                lp = jax.nn.log_softmax(logits.astype(jnp.float32))
                return -jnp.mean(jnp.sum(y * lp, axis=-1))

        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params)
        step = jax.jit(jax.grad(loss_fn))
        cost = step.lower(params).compile().cost_analysis()
        flops = float(cost.get("flops", 0.0)) if cost else 0.0
        return flops if flops > 0 else None
    except Exception:  # noqa: BLE001 — absence of the stat, not an error
        return None


def flops_budget(model, batch_size: int = 1, *, xla: bool = False) -> dict:
    """STATIC per-layer FLOPs budget for one training step of ``model``
    at ``batch_size`` — the ``zero_memory_budget`` dual pattern: the
    analytic table needs no chip and no compute; ``xla=True`` adds the
    jitted-lowering ``cost_analysis()`` total as a cross-check where the
    backend reports it (``xla_flops_per_step``, else None).

    Returns rows of per-example FORWARD FLOPs plus:
    ``fwd_flops_per_example``, ``train_flops_per_example`` (the 3x
    fwd+bwd accounting), ``flops_per_step`` (train x batch), and
    ``source``."""
    batch_size = int(batch_size)
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    rows = _analytic_rows(model)
    fwd = sum(r["flops"] for r in rows)
    train = TRAIN_FLOPS_MULTIPLIER * fwd
    out = {
        "rows": rows,
        "batch_size": batch_size,
        "fwd_flops_per_example": fwd,
        "train_flops_per_example": train,
        "flops_per_step": train * batch_size,
        "source": "analytic",
        "xla_flops_per_step": None,
    }
    if xla:
        measured = xla_cost_flops(model, batch_size)
        if measured is not None:
            out["xla_flops_per_step"] = measured
            out["source"] = "analytic+xla_cost_analysis"
    return out


def _calibrate_matmul_peak() -> float:
    """Achieved FLOP/s of a square f32 matmul on the default backend —
    the measured-achievable peak that stands in on the CPU test mesh,
    where no spec table applies."""
    import jax
    import jax.numpy as jnp

    n = CALIBRATE_DIM
    x = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    jax.block_until_ready(f(x))  # compile outside the clock
    best = float("inf")
    for _ in range(CALIBRATE_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        best = min(best, time.perf_counter() - t0)
    return 2.0 * n ** 3 / best


def peak_flops_per_sec(override: float = 0.0) -> tuple[float, str]:
    """(peak FLOP/s per chip, source). Resolution order: an explicit
    ``override`` (--mfu_peak_flops); on a ``tpu`` platform the spec
    table by exact device_kind (an unknown kind raises); elsewhere (the
    CPU test mesh) the cached matmul calibration."""
    if override and override > 0:
        return float(override), "flag_override"
    with _PEAK_LOCK:
        if "peak" not in _PEAK_CACHE:
            import jax

            device = jax.devices()[0]
            kind = device.device_kind
            if kind in TPU_PEAK_FLOPS:
                _PEAK_CACHE["peak"] = (TPU_PEAK_FLOPS[kind],
                                       f"device_table:{kind}")
            elif device.platform == "tpu":
                raise ValueError(
                    f"no peak FLOP/s for device_kind {kind!r}: add its "
                    f"published bf16 peak to utils.efficiency."
                    f"TPU_PEAK_FLOPS or pass --mfu_peak_flops")
            else:
                _PEAK_CACHE["peak"] = (_calibrate_matmul_peak(),
                                       "matmul_calibration")
        return _PEAK_CACHE["peak"]


def _reset_peak_cache() -> None:
    """Testing hook."""
    with _PEAK_LOCK:
        _PEAK_CACHE.clear()


class GoodputMeter:
    """Run-level goodput: productive wall-time fraction.

    ``charge(dt, kind)`` books a stall — restore, checkpoint write or
    boundary fetch, display/periodic eval, the first-step compile —
    against the wall clock running since construction (``reset()``
    restarts it). ``scalars()`` returns the cumulative ratio: goodput
    is a property of the RUN, not of a window (a 30 s restore must keep
    depressing it, not scroll out of a window)."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._t0 = time.perf_counter()
        self._lost = 0.0
        self._by_kind: dict[str, float] = {}

    def charge(self, dt: float, kind: str = "other") -> None:
        dt = max(0.0, float(dt))
        self._lost += dt
        self._by_kind[kind] = self._by_kind.get(kind, 0.0) + dt

    @property
    def lost_s(self) -> float:
        return self._lost

    def by_kind(self) -> dict[str, float]:
        return dict(self._by_kind)

    def scalars(self) -> dict:
        wall = time.perf_counter() - self._t0
        # resize_s (r15): the elasticity supervisor's drain+reinit+
        # restore downtime is a NAMED stall category — always present
        # (0.0 when no membership change happened) so dashboards and
        # fleet_report can chart it without schema sniffing
        resize = round(self._by_kind.get("resize", 0.0), 4)
        if wall <= 0:
            return {"goodput": 1.0, "goodput_lost_s": 0.0,
                    "resize_s": resize}
        ratio = min(max((wall - self._lost) / wall, 0.0), 1.0)
        return {"goodput": round(ratio, 6),
                "goodput_lost_s": round(self._lost, 4),
                "resize_s": resize}


class EfficiencyMeter:
    """The loops' one-stop efficiency accountant: MFU + model FLOP/s
    from the analytic budget, goodput from explicit stall charges.

    ``scalars(images_per_sec)`` (global examples/sec across chips) is
    emitted at the display cadence next to ``images_per_sec``; costs two
    multiplies and a clock read — hot-path safe."""

    def __init__(self, model, batch_size: int, n_chips: int,
                 peak_override: float = 0.0):
        budget = flops_budget(model, batch_size)
        self.train_flops_per_example = budget["train_flops_per_example"]
        self.flops_per_step = budget["flops_per_step"]
        peak, src = peak_flops_per_sec(peak_override)
        self.peak_flops_total = peak * max(1, int(n_chips))
        self.peak_source = src
        # the goodput wall clock runs from construction and never
        # resets: the loops charge the restore, the compile-carrying
        # first dispatch, and every later stall against it, so the
        # ratio is cumulative over the RUN by construction
        self.goodput = GoodputMeter()

    def charge(self, dt: float, kind: str = "other") -> None:
        self.goodput.charge(dt, kind)

    def scalars(self, images_per_sec: float) -> dict:
        mfs = float(images_per_sec) * self.train_flops_per_example
        out = {
            "model_flops_per_sec": round(mfs, 1),
            "mfu": round(mfs / self.peak_flops_total, 6)
            if self.peak_flops_total > 0 else 0.0,
        }
        out.update(self.goodput.scalars())
        return out


def meter_from_flags(FLAGS, model, batch_size: int,
                     n_chips: int) -> EfficiencyMeter | None:
    """The one flag->feature mapping for ``--mfu`` / ``--mfu_peak_flops``,
    shared by every training loop. None when accounting is off or the
    model has no analytic rule (unknown custom models train fine, just
    without mfu scalars — accounting must never block training)."""
    if not bool(getattr(FLAGS, "mfu", True)):
        return None
    try:
        return EfficiencyMeter(
            model, batch_size, n_chips,
            peak_override=float(getattr(FLAGS, "mfu_peak_flops", 0.0) or 0.0))
    except Exception as e:  # noqa: BLE001 — accounting never kills a run
        print(f"efficiency accounting disabled: {e}")
        return None
