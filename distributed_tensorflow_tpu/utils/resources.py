"""Resource plane: live HBM accounting with an OOM postmortem, a
recompilation sentry, and the per-mode collective-comm ledger.

PR 6/7 built the TIME plane — spans say where a step's milliseconds
went, MFU/goodput say what they bought, sentinels say whether the run
is dying. The RESOURCE plane was blind: ``device.memory_stats()`` was
read only inside bench.py, nothing counted XLA compiles after the
first, and only ``--zero`` carried analytic wire-bytes facts. The
three ways the runtime's invisibility kills a production run are
exactly these blind spots: silent HBM exhaustion, recompile storms,
and unaccounted collective traffic. This module is the third and
closing observability pillar — three coupled instruments over the one
telemetry spine:

- **HBM accounting** — ``MemoryMeter`` samples ``device.memory_stats()``
  at the EXISTING display/sync cadences (no new sync points; the CPU
  test mesh, which reports no stats, falls back to summing
  ``jax.live_arrays()`` bytes — a real live number, labeled
  ``source="live_arrays"``). Every loop variant and the serving stack
  emit ``hbm_in_use_bytes`` / ``hbm_peak_bytes`` / ``hbm_headroom_pct``
  next to ``images_per_sec``; each fresh sample also lands as an
  ``hbm_sample`` instant span (so it rides the span sink, the flight
  ring, and ``tools/fleet_report.py``'s per-host table). The live
  numbers cross-check against a STATIC analytic budget
  (``resource_budget`` — ``jax.eval_shape`` per-leaf params/opt plus an
  activation estimate, generalized beyond ``zero_memory_budget`` to the
  PP/TP/EP/SP layouts via each mode's own sharding rule).
- **OOM postmortem** — a chained ``sys.excepthook`` recognizes
  ``XlaRuntimeError`` / RESOURCE_EXHAUSTED and, before the normal
  telemetry dump, records the analytic budget table and the top-N
  largest live buffers (``jax.live_arrays()``) into the flight ring —
  so an OOM is diagnosable from ``flightrec-*.jsonl`` alone: the last
  memory samples (already riding the ring), what the budget SAID the
  state should cost, and which buffers actually held the HBM.
- **Recompilation sentry** — ``CompileSentry`` counts and times every
  program the backend is asked for, compiled or loaded from the
  persistent cache (a ``jax.monitoring`` listener — jit's in-memory
  cache hits don't fire), emits one span for each of jax's three
  compile phases of every program (``compile_trace`` /
  ``compile_lower`` / ``compile_backend``), and keys dispatches by
  TRACED SIGNATURE (``observe(site, signature)``): the first signature
  per site is the expected first compile, every NEW signature after it
  is a recompile, and the report names the exact shape/dtype delta (the
  dimension that churned). ``--recompile_budget N`` arms a
  sentinel-ladder storm warning: more than N recompiles inside a
  rolling window prints the offending delta, drops a
  ``recompile_storm`` instant span, and dumps the flight recorder — the
  shape-churn failure mode the serving bucket system and schedules.py
  exist to prevent, now detectable when it regresses.
- **Comm ledger** — ``comm_ledger`` composes a static per-step analytic
  of collective wire bytes from the parallel modules' OWN row builders
  (``zero_comm_rows`` / ``pp_comm_rows`` / ``tp_comm_rows`` /
  ``ep_comm_rows`` / ``sp_comm_rows`` — the formulas live next to the
  collectives they price), surfaced as a ``comm_bytes_per_step`` scalar
  in every loop, a ``comm_ledger`` instant span (fleet_report's
  per-host column), and ``tools/trace_ops.py --comm``.

stdlib-only at import time (jax and the model/optimizer layers import
lazily inside the functions that need them) so the flags validator,
``tools/mem_report.py``, and bench's host-only phases can import this
from anywhere — the utils/telemetry contract.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque

from distributed_tensorflow_tpu.utils import telemetry

# error signatures that mean the device allocator gave up (the
# jaxlib XlaRuntimeError for RESOURCE_EXHAUSTED, and the strings the
# TPU/interpreter allocators put in the message)
OOM_SIGNS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
             "Allocation failure")
TOP_LIVE_BUFFERS = 8       # largest live buffers in the postmortem
MEM_SAMPLE_RING = 64       # samples MemoryMeter retains for dumps
RECOMPILE_WINDOW_S = 60.0  # rolling window behind --recompile_budget
MAX_SIGS_PER_SITE = 256    # signature-ledger cap (FIFO eviction)

# jax's compile phases (jax/_src/dispatch.py), each reported as a time span
# on the epoch clock with the program's name; the persistent cache's events
# fire on the compiling thread inside the backend phase
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_ASKED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"  # fires on a write
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

F32_BYTES = 4


# --------------------------------------------------------- HBM metering


def _device_memory_sample() -> dict | None:
    """One live memory reading across the local devices.

    TPU/GPU backends report ``memory_stats()`` per device (bytes_in_use
    / peak_bytes_in_use / bytes_limit — summed here, per-device detail
    kept); the CPU test mesh reports None, so the fallback sums the
    bytes of every live jax array in the process — a real (if
    host-side) live-buffer number, labeled so nobody mistakes it for
    HBM. None only when there is no backend at all."""
    try:
        import jax

        per = []
        for d in jax.local_devices():
            try:
                ms = d.memory_stats()
            except Exception:  # noqa: BLE001 — absence of the stat
                ms = None
            if ms and "bytes_in_use" in ms:
                per.append({
                    "device": int(getattr(d, "id", len(per))),
                    "in_use": int(ms["bytes_in_use"]),
                    "peak": int(ms.get("peak_bytes_in_use",
                                       ms["bytes_in_use"])),
                    "limit": int(ms.get("bytes_limit", 0) or 0),
                })
        if per:
            return {"in_use": sum(p["in_use"] for p in per),
                    "peak": sum(p["peak"] for p in per),
                    "limit": sum(p["limit"] for p in per),
                    "source": "memory_stats", "per_device": per}
        total = sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())
        return {"in_use": total, "peak": total, "limit": 0,
                "source": "live_arrays", "per_device": []}
    except Exception:  # noqa: BLE001 — accounting never kills a run
        return None


def headroom_pct(in_use: int, limit: int) -> float:
    """Percent of the reported limit still free; -1.0 when the backend
    reports no limit (the CPU fallback) — 'unknown', never 'plenty'."""
    if limit and limit > 0:
        return round(100.0 * max(0.0, 1.0 - in_use / limit), 4)
    return -1.0


class MemoryMeter:
    """Live HBM accounting at the display cadence.

    ``scalars()`` is the loops' call: it re-samples every
    ``sample_every``-th call (``--hbm_sample_every`` display boundaries;
    the sample is a runtime stat query / live-array walk — no device
    sync) and returns the standard scalar family. Every FRESH sample
    also lands as an ``hbm_sample`` instant span, which puts it in the
    span sink (fleet_report's per-host hbm column), the flight ring
    (the OOM postmortem's recent-samples section), and nowhere near the
    hot path. ``peak`` is max(backend peak, own running max) so the CPU
    fallback still has a peak story. ``sample_fn`` is the test seam."""

    SCALARS = ("hbm_in_use_bytes", "hbm_peak_bytes", "hbm_headroom_pct")

    def __init__(self, analytic_bytes: int | None = None,
                 sample_every: int = 1, sample_fn=None):
        self.analytic_bytes = (int(analytic_bytes)
                               if analytic_bytes else None)
        self.sample_every = max(1, int(sample_every))
        self._sample_fn = sample_fn or _device_memory_sample
        self._samples: deque = deque(maxlen=MEM_SAMPLE_RING)
        self._lock = threading.Lock()
        self._peak = 0
        self._calls = 0
        self._last: dict | None = None

    def sample(self, tag: str = "") -> dict | None:
        """Take one fresh reading now; returns it (or None with no
        backend). Cheap: a per-device stats query, no sync."""
        s = self._sample_fn()
        if s is None:
            return None
        with self._lock:
            self._peak = max(self._peak, int(s.get("peak") or s["in_use"]))
            s = dict(s, peak=self._peak, t=time.time())
            self._samples.append(s)
            self._last = s
        telemetry.get_tracer().record_instant(
            "hbm_sample", in_use=int(s["in_use"]), peak=int(s["peak"]),
            limit=int(s.get("limit", 0)), source=s.get("source", "?"),
            **({"tag": tag} if tag else {}))
        return s

    def scalars(self) -> dict:
        """The display-cadence scalar family (re-sampling every
        ``sample_every``-th call). ``hbm_headroom_pct`` is -1.0 when the
        backend reports no limit (documented sentinel, not 'plenty')."""
        with self._lock:
            calls, self._calls = self._calls, self._calls + 1
            last = self._last
        if last is None or calls % self.sample_every == 0:
            last = self.sample() or last
        if last is None:
            return {}
        out = {
            "hbm_in_use_bytes": float(last["in_use"]),
            "hbm_peak_bytes": float(last["peak"]),
            "hbm_headroom_pct": headroom_pct(last["in_use"],
                                             last.get("limit", 0)),
        }
        if self.analytic_bytes:
            out["hbm_analytic_bytes"] = float(self.analytic_bytes)
        return out

    def sample_if_stale(self, max_age_s: float = 1.0,
                        tag: str = "") -> dict | None:
        """A fresh-enough reading without resampling on every call —
        the serving health poll's entry point (a hot /healthz must not
        turn into a sample-per-request span flood)."""
        with self._lock:
            last = self._last
        if last is not None and time.time() - last["t"] < max_age_s:
            return last
        return self.sample(tag=tag) or last

    def last_samples(self, k: int = MEM_SAMPLE_RING) -> list:
        with self._lock:
            return list(self._samples)[-k:]

    @property
    def last(self) -> dict | None:
        with self._lock:
            return self._last


# ------------------------------------------------------ analytic budget


def _abstract_state(model, optimizer):
    """(abstract params, abstract opt_state|None) via jax.eval_shape —
    no compute, no chip (the zero_memory_budget pattern)."""
    import jax

    if optimizer is not None:
        from distributed_tensorflow_tpu.training.train_state import (
            create_train_state,
        )

        st = jax.eval_shape(lambda: create_train_state(model, optimizer))
        return st.params, st.opt_state
    variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if getattr(model, "stateful", False):
        variables = variables["params"]
    return variables, None


def _path_keys(path) -> tuple:
    """KeyPath -> tuple of dict keys / sequence indices — the ONE
    tree-path identity the divisor tables and the tp split-table
    lookups key by (the tuple sibling of ``utils.pytree.path_key``)."""
    return tuple(getattr(p, "key", getattr(p, "idx", None))
                 for p in path)


def _param_divisor_fn(mode: str, data_ways: int, model_axis: int,
                      zero_level: int, abstract_params):
    """(path, leaf) -> divisor: each mode's own sharding rule, spec-
    driven where a spec table exists (TP uses ``tp_param_specs``, EP the
    expert-leaf rule) rather than re-deriving layouts here."""
    import jax

    if mode == "zero3":
        return lambda path, leaf: data_ways
    if mode == "pp":
        # stage-sharded transformer blocks (num_blocks/K per device,
        # whatever V — interleaving permutes, it doesn't change the
        # per-device share); embed/head/norm replicate
        def div(path, leaf):
            return model_axis if "blocks" in _path_keys(path) else 1

        return div
    if mode == "tp":
        from jax.sharding import PartitionSpec as P

        from distributed_tensorflow_tpu.parallel.mesh import MODEL_AXIS
        from distributed_tensorflow_tpu.parallel.tensor_parallel import (
            tp_param_specs,
        )

        specs = tp_param_specs(abstract_params)
        flat = {_path_keys(path): spec
                for path, spec in jax.tree_util.tree_flatten_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P))[0]}

        def div(path, leaf):
            spec = flat.get(_path_keys(path))
            return (model_axis if spec is not None
                    and any(ax == MODEL_AXIS for ax in spec) else 1)

        return div
    if mode == "ep":
        from distributed_tensorflow_tpu.parallel.expert_parallel import (
            _is_expert_leaf,
        )

        return lambda path, leaf: (model_axis if _is_expert_leaf(path)
                                   else 1)
    # dp / sp / local / zero1: params replicate
    return lambda path, leaf: 1


def _activation_rows(model, per_chip_batch: int,
                     seq_scale: int = 1) -> list[dict]:
    """Coarse per-chip activation estimate (f32 bytes of the layer
    outputs a training step keeps live) — the budget's third column.
    An ESTIMATE by design: remat/donation/XLA fusion all shrink the
    real number; the point is the order of magnitude next to the exact
    params/opt rows. ``seq_scale`` divides the token axis (SP)."""
    b = max(1, int(per_chip_batch))
    name = type(model).__name__
    rows = []

    def add(layer, elements):
        rows.append({"layer": layer, "bytes": int(elements) * F32_BYTES})

    if name == "DeepCNN":
        s = model.image_size
        s2 = -(-s // 2)
        add("conv1+pool", b * s * s * 32 + b * s2 * s2 * 32)
        add("conv2+pool", b * s2 * s2 * 64)
        add("fc", b * model.hidden_units)
        add("logits", b * model.num_classes)
    elif name == "MLP":
        add("hidden", b * model.hidden_units)
        add("logits", b * model.num_classes)
    elif name in ("ResNet", "ResNet20", "ResNet32"):
        size = model.image_size
        for si, width in enumerate(model.widths):
            if si > 0:
                size = -(-size // 2)
            add(f"stage{si}", model.n * 2 * b * size * size * width)
        add("head", b * model.num_classes)
    elif name in ("MiniTransformer", "TransformerLM"):
        s = max(1, model.seq_len // max(1, seq_scale))
        d = model.d_model
        # per block: x + qkv(3) + attn out + mlp hidden + mlp out
        per_block = b * s * d * (6 + model.mlp_dim // d)
        if not getattr(model, "attn_block", None) and seq_scale == 1:
            # the dense score matrix, unless blockwise/ring streams it
            per_block += b * model.num_heads * s * s
        add(f"{model.num_blocks} blocks",
            model.num_blocks * per_block)
        if hasattr(model, "vocab_size"):
            ce_block = getattr(model, "ce_block", None)
            add("lm_head logits",
                b * min(s, ce_block or s) * model.vocab_size)
        else:
            add("cls_head", b * model.num_classes)
    else:
        raise ValueError(
            f"no activation rule for model type {name!r} — the resource "
            f"budget knows deep_cnn/mlp/resnet*/transformer/lm")
    return rows


def resource_budget(model, optimizer=None, batch_size: int = 1, *,
                    mode: str = "dp", data_ways: int = 1,
                    model_axis: int = 1, zero_level: int = 0,
                    virtual_stages: int = 1,
                    microbatches: int = 0, pp_schedule: str = "auto",
                    zero_overlap: bool = False,
                    zero_bucket_mb: float = 4.0) -> dict:
    """STATIC per-chip memory budget for ``model`` under one parallel
    layout — ``zero_memory_budget`` generalized across the mode matrix
    (``jax.eval_shape``, no chip, no compute): per-leaf param/opt bytes
    with each mode's own sharding divisor (ZeRO chunks over data, PP
    stages blocks, TP follows ``tp_param_specs``, EP the expert-leaf
    rule), transient grad bytes (full leaves in every mode), and a
    coarse activation estimate at the per-chip batch. The live
    ``MemoryMeter`` numbers cross-check against ``per_chip_total``
    (state + grads; activations listed separately — they are transient
    and the cross-check happens between steps)."""
    import math

    import jax
    import numpy as np

    data_ways = max(1, int(data_ways))
    model_axis = max(1, int(model_axis))
    if mode.startswith("zero"):
        zero_level = zero_level or int(mode[4:] or 0)
    params, opt_state = _abstract_state(model, optimizer)
    div_fn = _param_divisor_fn(mode, data_ways, model_axis, zero_level,
                               params)
    rows: list[dict] = []

    from distributed_tensorflow_tpu.utils.pytree import path_key

    def add_rows(kind, tree, divisor_fn, prefix: str = ""):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            n = math.prod(leaf.shape) if leaf.shape else 1
            isz = np.dtype(leaf.dtype).itemsize
            d = max(1, int(divisor_fn(path, leaf)))
            rows.append({
                "kind": kind,
                "leaf": (prefix + path_key(path)).rstrip("/") or "(scalar)",
                "bytes": n * isz,
                # ceil over ELEMENTS (what the chips actually allocate —
                # padding included, the zero_memory_budget convention)
                "per_chip_bytes": (-(-n // d)) * isz,
                "shard": d,
            })

    add_rows("param", params, div_fn)
    if opt_state is not None:
        pstruct = jax.tree.structure(params)
        # opt slots that mirror the params shard like them; ZeRO-1/3
        # additionally chunks every params-shaped slot over data
        opt_div = div_fn
        if mode in ("zero1", "zero3"):
            opt_div = lambda path, leaf: data_ways

        def walk_opt(entry, prefix: str):
            if jax.tree.structure(entry) == pstruct:
                add_rows("opt", entry, opt_div, prefix=prefix)
            elif isinstance(entry, dict):
                for k, v in entry.items():
                    walk_opt(v, f"{prefix}{k}/")
            else:
                add_rows("opt", entry, lambda p, l: 1, prefix=prefix)

        walk_opt(opt_state, "")

    act_rows = _activation_rows(
        model, -(-int(batch_size) // data_ways),
        seq_scale=model_axis if mode == "sp" else 1)

    def total(kind):
        return sum(r["per_chip_bytes"] for r in rows if r["kind"] == kind)

    p_chip, o_chip = total("param"), total("opt")
    g_chip = sum(r["bytes"] for r in rows if r["kind"] == "param")
    a_chip = sum(r["bytes"] for r in act_rows)
    return {
        "mode": mode, "data_ways": data_ways, "model_axis": model_axis,
        "zero_level": zero_level, "batch_size": int(batch_size),
        "rows": rows, "activation_rows": act_rows,
        "per_chip": {"params": p_chip, "opt": o_chip, "grads": g_chip,
                     "activations": a_chip},
        # the live cross-check target: persistent state + the transient
        # grad leaves every step materializes
        "per_chip_total": p_chip + o_chip + g_chip,
        "per_chip_state_bytes": p_chip + o_chip,
        "param_bytes_full": g_chip,
    }


# ----------------------------------------------------------- comm ledger


def comm_ledger(model, optimizer=None, batch_size: int = 1, *,
                mode: str = "dp", data_ways: int = 1, model_axis: int = 1,
                zero_level: int = 0, virtual_stages: int = 1,
                microbatches: int = 0, pp_schedule: str = "auto",
                zero_overlap: bool = False,
                zero_bucket_mb: float = 4.0,
                ps_wire: str = "f32", ps_mirror: bool = True,
                verify: bool = False) -> dict:
    """STATIC per-step analytic of collective wire bytes for one
    parallel layout, composed from the parallel modules' own row
    builders (the formula lives next to the collective it prices).
    Conventions match the existing docs: all-reduce moves ~2|G|,
    reduce-scatter |G|, all-gather |P|; activation payloads are f32.
    Rows carry ``exposed_bytes`` — the analytic critical-path share:
    ``zero_overlap``/``zero_bucket_mb`` price the ``--zero_overlap``
    bucketed/prefetched pattern, ``pp_schedule`` the tick table (zb's
    cotangent hops overlap the deferred-W slack). Returns {mode,
    rows: [{collective, axis, bytes, exposed_bytes, note}],
    comm_bytes_per_step, comm_exposed_bytes_per_step}.

    The byte accounting is jaxpr-exact as of r18 (``tools/dttcheck``
    proves it against the lowered computation, per mode):

    - ZeRO rows price the PADDED flat chunking (every leaf zero-pads
      to a multiple of D before psum_scatter/all_gather — the padding
      lanes ride the wire like the live ones);
    - the data-axis grad all-reduce prices each rank's ACTUAL payload
      (stage/expert/TP-sharded leaves contribute their 1/K shard, not
      the full leaf);
    - PP/EP/SP rows include the model-axis collectives the old ledger
      missed (replicated-leaf grad psums, the SP grad pmean) and the
      ring rows count every schedule tick/hop the program executes.

    ``verify=True`` machine-proves the returned ledger on the spot:
    the step is traced chip-free over an abstract CPU mesh
    (``tools/dttcheck.verify_ledger``) and any byte drift raises
    ``ValueError`` naming the offending (collective family, axis)
    group. A build/test-time instrument — it needs the repo's
    ``tools/`` on the path and an 8-device CPU mesh."""
    import math

    import jax
    import numpy as np

    data_ways = max(1, int(data_ways))
    model_axis = max(1, int(model_axis))
    if mode.startswith("zero"):
        zero_level = zero_level or int(mode[4:] or 0)
    params, _ = _abstract_state(model, None)
    flat_params = jax.tree_util.tree_flatten_with_path(params)[0]

    def _n(leaf) -> int:
        return math.prod(leaf.shape) if leaf.shape else 1

    param_bytes = sum(_n(l) * np.dtype(l.dtype).itemsize
                      for _, l in flat_params)
    grad_bytes = param_bytes
    # ZeRO's flat chunking zero-pads every leaf to a multiple of D
    # before the scatter/gather — the padding lanes are real wire
    # traffic (dttcheck-proven; the figures are what the chips move)
    padded_bytes = sum(
        (-(-_n(l) // data_ways)) * data_ways * np.dtype(l.dtype).itemsize
        for _, l in flat_params)
    # per-rank payloads for the data-axis all-reduce: sharded leaves
    # (PP stages, EP experts, TP splits) contribute their 1/K shard
    if mode in ("pp", "tp", "ep"):
        div_fn = _param_divisor_fn(mode, data_ways, model_axis,
                                   zero_level, params)
    else:
        div_fn = lambda path, leaf: 1  # noqa: E731
    per_rank_grad_bytes = 0
    rep_grad_bytes = 0
    for path, leaf in flat_params:
        isz = np.dtype(leaf.dtype).itemsize
        d = max(1, int(div_fn(path, leaf)))
        per_rank_grad_bytes += (_n(leaf) // d) * isz
        if d == 1:
            rep_grad_bytes += _n(leaf) * isz
    rows: list[dict] = []

    from distributed_tensorflow_tpu.parallel.zero import zero_comm_rows

    if mode in ("zero1", "zero3"):
        rows += zero_comm_rows(padded_bytes, padded_bytes, zero_level,
                               data_ways, overlap=bool(zero_overlap),
                               bucket_mb=float(zero_bucket_mb or 4.0))
    elif mode == "ps":
        from distributed_tensorflow_tpu.parallel.ps_emulation import (
            ps_comm_rows,
        )

        # per-worker pull/push cycle over the HOST wire, not ICI
        # (``ps_wire``/``ps_mirror`` mirror the --ps_wire/--ps_mirror
        # flags; the pull row is 0 bytes under the mirror cycle)
        rows += ps_comm_rows(param_bytes, grad_bytes,
                             wire=ps_wire, mirror=ps_mirror)
    elif data_ways > 1:
        # every other multi-chip mode pays the plain DP grad all-reduce
        # over its data rows (dp_comm_rows delegates to the one
        # all-reduce formula in zero_comm_rows level 0), at each rank's
        # ACTUAL payload — model-axis-sharded leaves ride at 1/K
        from distributed_tensorflow_tpu.parallel.data_parallel import (
            dp_comm_rows,
        )

        rows += dp_comm_rows(per_rank_grad_bytes, data_ways)

    is_tf = type(model).__name__ in ("MiniTransformer", "TransformerLM")
    seq = getattr(model, "seq_len", 0)
    d_model = getattr(model, "d_model", 0)
    if mode == "pp":
        from distributed_tensorflow_tpu.parallel.pipeline_parallel import (
            pp_comm_rows,
        )

        micro = int(microbatches) or model_axis
        per_shard = -(-int(batch_size) // data_ways)
        act = -(-per_shard // micro) * seq * d_model * F32_BYTES
        rows += pp_comm_rows(act, model_axis, micro,
                             virtual_stages=max(1, int(virtual_stages)),
                             schedule=pp_schedule,
                             rep_grad_bytes=rep_grad_bytes)
    elif mode == "tp" and model_axis > 1:
        from distributed_tensorflow_tpu.parallel.tensor_parallel import (
            tp_comm_rows,
        )

        per_shard = -(-int(batch_size) // data_ways)
        keys = {_path_keys(path) for path, _ in flat_params}
        if is_tf:
            # symmetric boundaries: attention-out + MLP-down per block,
            # each psums a (B, S, d_model) tensor both directions
            act = per_shard * seq * d_model * F32_BYTES
            n_sync = 2 * model.num_blocks
            rows += tp_comm_rows(n_sync * act, n_sync * act)
        elif ("weights", "wd1") in keys:
            # the CNN FC stack: forward psums the row-split OUT
            # matmul's (B, num_classes) partials; backward psums the
            # cotangent at wd1's column-split (B, fc_in) input
            fc_in = next(l.shape[0] for path, l in flat_params
                         if _path_keys(path) == ("weights", "wd1"))
            rows += tp_comm_rows(
                per_shard * model.num_classes * F32_BYTES,
                per_shard * fc_in * F32_BYTES)
        # models without a split table shard nothing -> no TP rows
    elif mode == "ep" and model_axis > 1:
        from distributed_tensorflow_tpu.parallel.expert_parallel import (
            ep_comm_rows,
        )

        per_shard = -(-int(batch_size) // data_ways)
        act = per_shard * seq * d_model * F32_BYTES
        rows += ep_comm_rows(act, getattr(model, "num_blocks", 1),
                             rep_grad_bytes=rep_grad_bytes)
    elif mode == "sp" and model_axis > 1:
        from distributed_tensorflow_tpu.parallel.sequence_parallel import (
            sp_comm_rows,
        )

        per_shard = -(-int(batch_size) // data_ways)
        kv_block = per_shard * (seq // model_axis) * d_model * F32_BYTES
        rows += sp_comm_rows(kv_block, model_axis,
                             getattr(model, "num_blocks", 1),
                             grad_bytes=grad_bytes)

    result = {
        "mode": mode, "data_ways": data_ways, "model_axis": model_axis,
        "rows": rows,
        "comm_bytes_per_step": int(sum(r["bytes"] for r in rows)),
        # rows without an exposure column (TP/EP/SP activation psums)
        # price as fully exposed — the conservative default
        "comm_exposed_bytes_per_step": int(sum(
            r.get("exposed_bytes", r["bytes"]) for r in rows)),
    }
    if verify:
        result["verified"] = _verify_ledger(
            model, optimizer, batch_size, result, mode=mode,
            data_ways=data_ways, model_axis=model_axis,
            zero_level=zero_level, virtual_stages=virtual_stages,
            microbatches=microbatches, pp_schedule=pp_schedule,
            zero_overlap=zero_overlap, zero_bucket_mb=zero_bucket_mb)
    return result


def _verify_ledger(model, optimizer, batch_size, ledger, **cfg) -> bool:
    """The ``comm_ledger(verify=True)`` hook body: trace the REAL step
    for this layout chip-free (tools/dttcheck) and require byte-exact
    agreement; any drift raises ValueError naming the group."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    try:
        from tools.dttcheck import verify_ledger
    except ImportError as e:
        raise RuntimeError(
            f"comm_ledger(verify=True) needs the repo's tools/ tree "
            f"(tools.dttcheck): {e}") from None
    if optimizer is None:
        # the proof needs a runnable update; collective volume does not
        # depend on the optimizer family (grads/slots mirror params)
        from distributed_tensorflow_tpu.training.train_state import sgd

        optimizer = sgd(0.01)
    findings = verify_ledger(model, optimizer, batch_size, ledger, **cfg)
    if findings:
        raise ValueError(
            "comm_ledger(verify=True): the analytic rows do not match "
            "the lowered computation:\n  "
            + "\n  ".join(f.message for f in findings))
    return True


# ---------------------------------------------------- recompile sentry


def batch_signature(batch) -> tuple:
    """The traced signature of a dispatch payload: (shape, dtype) per
    leaf — exactly what jax.jit specializes executables on. Cheap
    (a tree flatten of 1-3 leaves) so the loops can afford it per
    dispatch."""
    import jax

    return tuple(
        (tuple(getattr(a, "shape", ())),
         str(getattr(a, "dtype", type(a).__name__)))
        for a in jax.tree.leaves(batch))


def _sig_delta(old, new) -> str:
    """Human-readable description of what changed between two traced
    signatures — the dimension/dtype the storm report names."""
    if old is None:
        return "first signature"
    try:
        if len(old) != len(new):
            return f"arity {len(old)} -> {len(new)} leaves"
        for i, (o, n) in enumerate(zip(old, new)):
            if o == n:
                continue
            oshape, odt = o if isinstance(o, tuple) and len(o) == 2 \
                else (o, "?")
            nshape, ndt = n if isinstance(n, tuple) and len(n) == 2 \
                else (n, "?")
            if odt != ndt:
                return f"leaf{i} dtype {odt} -> {ndt}"
            if isinstance(oshape, tuple) and isinstance(nshape, tuple):
                if len(oshape) != len(nshape):
                    return (f"leaf{i} rank {len(oshape)} -> "
                            f"{len(nshape)} ({oshape} -> {nshape})")
                for dim, (a, b) in enumerate(zip(oshape, nshape)):
                    if a != b:
                        return (f"leaf{i} dim {dim}: {a} -> {b} "
                                f"(shape {oshape} -> {nshape})")
            return f"leaf{i} {o} -> {n}"
        return "identical (?)"
    except Exception:  # noqa: BLE001 — a weird signature must not crash
        return f"{old!r} -> {new!r}"


class CompileSentry:
    """Counts and times every XLA compile or cache load, detects
    recompiles by traced signature, and trips a storm warning past
    ``--recompile_budget``.

    Two sources, one ledger: the ``jax.monitoring`` compile listener
    (installed once per process, forwarding to the ACTIVE sentry)
    supplies ``compiles_total`` / ``compile_time_s`` — every program the
    backend was asked for, compiled or loaded from the persistent cache
    (jax's backend phase wraps both), jit's in-memory cache hits don't
    fire; ``compile_cache_hits`` counts those of them that were LOADED
    from the persistent compilation cache (utils/compile_cache.py), not
    compiled. The same listener emits one completed span a compile
    phase, with jax's own start and end and the program's name as
    ``fun``: ``compile_trace`` (the jaxpr; a function traced inside
    another trace or a lowering is part of that program and gets none),
    ``compile_lower`` (the MLIR module) and
    ``compile_backend`` (XLA's compile or the cache load), whose
    ``cache`` says which: ``hit`` (with ``retrieval_s``), ``miss`` (not
    in the persistent cache, so compiled; ``stored`` when it was then
    written there, inside the span) or ``off`` (no persistent cache
    asked). ``observe(site, signature)``
    — called by the loops at each dispatch and by the serving engine
    per bucket — supplies the recompile story: the first signature a
    site ever shows is its expected first compile; a NEW signature
    later is a recompile, and the delta (which dim/dtype churned) is
    retained. More than ``budget`` recompiles inside ``window_s``
    seconds prints a loud report naming the churning site and delta,
    drops a ``recompile_storm`` instant span, and dumps the flight
    recorder (the sentinel action-ladder's warn rung). ``budget=0``
    counts but never trips."""

    def __init__(self, budget: int = 0,
                 window_s: float = RECOMPILE_WINDOW_S):
        self.budget = max(0, int(budget))
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self.compiles_total = 0
        self.compile_time_s = 0.0
        self.compile_cache_hits = 0
        self.recompiles_total = 0
        self.storms = 0
        self._sites: dict = {}       # site -> {sig: hits}
        self._last_sig: dict = {}    # site -> most recent signature
        # (t, site, delta) recompiles inside the storm window. Bounded
        # BY CONSTRUCTION (dttsan SAN004): the window-pruning loop in
        # observe() keeps it small in practice, but a monitoring ring
        # must not rely on pruning logic for its bound — budget+1 is
        # exactly enough for len > budget to trip the storm report
        self._recent: deque = deque(
            maxlen=(self.budget + 1) if self.budget else 1024)
        self.last_delta: str | None = None
        # per thread: the traces and lowerings open (a function traced
        # inside either is part of that program, no program of its own)
        # and the cache's events of the backend phase open, attached to
        # its span when it closes
        self._thread = threading.local()

    def _cache_events(self) -> dict:
        seen = getattr(self._thread, "seen", None)
        if seen is None:
            seen = self._thread.seen = {}
        return seen

    def on_compile_start(self, event: str) -> None:
        if event in (TRACE_EVENT, LOWER_EVENT):
            self._thread.open = getattr(self._thread, "open", 0) + 1

    def on_compile_event(self, event: str, dur: float) -> None:
        if event == CACHE_RETRIEVAL_EVENT:
            self._cache_events()["retrieval_s"] = float(dur)
            return
        if event != BACKEND_EVENT:
            return
        with self._lock:
            self.compiles_total += 1
            self.compile_time_s += float(dur)

    def on_cache_event(self, event: str) -> None:
        if event in (CACHE_ASKED_EVENT, CACHE_HIT_EVENT, CACHE_WRITE_EVENT):
            self._cache_events()[event] = True
        if event != CACHE_HIT_EVENT:
            return
        with self._lock:
            self.compile_cache_hits += 1

    def on_compile_span(self, event: str, start: float, end: float,
                        fun: str) -> None:
        """One compile phase of one program, as a completed span on the
        compiling thread (a child of the span open there)."""
        if event in (TRACE_EVENT, LOWER_EVENT):
            self._thread.open = max(0, getattr(self._thread, "open", 0) - 1)
        if event == TRACE_EVENT:
            if not self._thread.open:
                telemetry.record_span("compile_trace", ts=start,
                                      dur_s=end - start, fun=fun)
        elif event == LOWER_EVENT:
            telemetry.record_span("compile_lower", ts=start,
                                  dur_s=end - start, fun=fun)
        elif event == BACKEND_EVENT:
            seen, self._thread.seen = self._cache_events(), None
            attrs = {"cache": "off"}
            if seen.get(CACHE_HIT_EVENT):
                attrs = {"cache": "hit",
                         "retrieval_s": seen.get("retrieval_s", 0.0)}
            elif seen.get(CACHE_ASKED_EVENT) and _cache_dir_set():
                attrs = {"cache": "miss",
                         "stored": bool(seen.get(CACHE_WRITE_EVENT))}
            telemetry.record_span("compile_backend", ts=start,
                                  dur_s=end - start, fun=fun, **attrs)

    def site_signatures(self, site: str) -> int:
        with self._lock:
            return len(self._sites.get(site, ()))

    def observe(self, site: str, signature) -> str | None:
        """Record one dispatch; returns the delta string when this was
        a recompile (a NEW signature on a known site), else None."""
        storm = None
        with self._lock:
            sigs = self._sites.setdefault(site, {})
            if signature in sigs:
                sigs[signature] += 1
                return None
            prev = self._last_sig.get(site)
            sigs[signature] = 1
            self._last_sig[site] = signature
            # bound the ledger: a client-controlled signature axis
            # (e.g. serve_decode's per-request max_new_tokens) must not
            # grow the MONITORING plane without limit in a long-lived
            # replica — evict oldest-first (a re-seen evicted signature
            # counts as a recompile again, which is the honest reading:
            # its executable likely aged out of jit's cache too)
            if len(sigs) > MAX_SIGS_PER_SITE:
                sigs.pop(next(iter(sigs)))
            if prev is None:
                return None  # the site's expected first compile
            self.recompiles_total += 1
            delta = _sig_delta(prev, signature)
            self.last_delta = f"{site}: {delta}"
            now = time.monotonic()
            self._recent.append((now, site, delta))
            while self._recent and now - self._recent[0][0] > self.window_s:
                self._recent.popleft()
            if self.budget and len(self._recent) > self.budget:
                storm = (site, delta, len(self._recent))
                self._recent.clear()  # one report per storm incident
                self.storms += 1
        if storm is not None:
            self._report_storm(*storm)
        return delta

    def _report_storm(self, site: str, delta: str, count: int) -> None:
        line = "=" * 70
        print(f"\n{line}\nRECOMPILE STORM: {count} recompiles inside "
              f"{self.window_s:.0f}s (budget {self.budget}) — latest at "
              f"site {site!r}: {delta}\n"
              f"  every new traced signature costs a full XLA compile; "
              f"a churning batch/bucket shape turns the step budget "
              f"into compile time (pad to stable buckets — the serving "
              f"power-of-two bucketing and schedules.py exist for "
              f"this)\n{line}", flush=True)
        telemetry.get_tracer().record_instant(
            "recompile_storm", site=site, delta=delta, count=count,
            budget=self.budget)
        telemetry.flight_recorder().dump(f"recompile_storm:{site}")

    def scalars(self) -> dict:
        with self._lock:
            return {
                "compiles_total": float(self.compiles_total),
                "compile_time_s": round(self.compile_time_s, 4),
                "compile_cache_hits": float(self.compile_cache_hits),
                "recompiles_total": float(self.recompiles_total),
            }


# one process-wide listener forwarding to the ACTIVE sentry (the
# monitoring API has no unregister; the indirection makes re-runs and
# tests safe — swap the sentry, not the listener)
_ACTIVE: dict = {"meter": None, "sentry": None, "budget": None}
_ACTIVE_LOCK = threading.Lock()
_LISTENER = {"installed": False}


def _cache_dir_set() -> bool:
    """Whether the persistent cache has a directory: jax asks for a
    program's key whenever the cache is enabled, with or without one."""
    import jax

    return bool(jax.config.jax_compilation_cache_dir)


def _install_compile_listener() -> None:
    with _ACTIVE_LOCK:
        if _LISTENER["installed"]:
            return
        _LISTENER["installed"] = True
    try:
        import jax

        def _on_duration(event, duration, **kw):
            s = _ACTIVE.get("sentry")
            if s is not None:
                s.on_compile_event(event, duration)

        def _on_event(event, **kw):
            s = _ACTIVE.get("sentry")
            if s is not None:
                s.on_cache_event(event)

        def _on_start(event, value, **kw):  # a phase's start time
            s = _ACTIVE.get("sentry")
            if s is not None:
                s.on_compile_start(event)

        def _on_time_span(event, start_time, end_time, fun_name="", **kw):
            s = _ACTIVE.get("sentry")
            if s is not None:
                s.on_compile_span(event, start_time, end_time, fun_name)

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_scalar_listener(_on_start)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
    except Exception as e:  # noqa: BLE001 — no jax, no compile events
        print(f"resources: compile listener unavailable: {e}")


def activate(meter: MemoryMeter | None = None,
             sentry: CompileSentry | None = None,
             budget: dict | None = None) -> None:
    """Install the instruments the process-wide hooks (compile
    listener, OOM excepthook, checkpoint sample notes) forward to.
    Passing None clears a slot."""
    with _ACTIVE_LOCK:
        _ACTIVE["meter"] = meter
        _ACTIVE["sentry"] = sentry
        _ACTIVE["budget"] = budget


def active_meter() -> MemoryMeter | None:
    return _ACTIVE.get("meter")


def active_sentry() -> CompileSentry | None:
    return _ACTIVE.get("sentry")


def note_signature(site: str, signature) -> None:
    """Module-level dispatch note for layers that don't hold a monitor
    (the serving engine) — forwards to the active sentry, no-op
    otherwise."""
    s = _ACTIVE.get("sentry")
    if s is not None:
        s.observe(site, signature)


def sample_note(tag: str) -> None:
    """One memory sample attributed to a named boundary (checkpoint
    save/restore — the big allocation events); no-op without an active
    meter. Never raises."""
    m = _ACTIVE.get("meter")
    if m is None:
        return
    try:
        m.sample(tag=tag)
    except Exception:  # noqa: BLE001 — accounting never kills a run
        pass


# -------------------------------------------------------- OOM postmortem


def _is_oom(exc_type, exc) -> bool:
    name = getattr(exc_type, "__name__", "")
    text = f"{name}: {exc}"
    return "XlaRuntimeError" in name or any(s in text for s in OOM_SIGNS)


def _top_live_buffers(n: int = TOP_LIVE_BUFFERS) -> list[dict]:
    """The N largest live jax arrays (shape/dtype/bytes) — which
    buffers actually hold the memory when the allocator gives up."""
    try:
        import jax

        rows = [{"shape": list(getattr(a, "shape", ())),
                 "dtype": str(getattr(a, "dtype", "?")),
                 "nbytes": int(getattr(a, "nbytes", 0))}
                for a in jax.live_arrays()]
        rows.sort(key=lambda r: -r["nbytes"])
        return rows[:n]
    except Exception:  # noqa: BLE001 — the postmortem must still land
        return []


def oom_postmortem(exc=None, reason: str | None = None) -> str | None:
    """Record the OOM story into the flight ring — the last memory
    samples are already there (every ``hbm_sample`` instant rides it);
    this adds the analytic budget table and the top-N largest live
    buffers — then dump. Returns the flightrec path (None when no sink
    is configured). Safe to call from any layer on any suspected-OOM
    error; the chained excepthook calls it automatically."""
    fr = telemetry.flight_recorder()
    fr.record("note", {
        "note": f"OOM postmortem: "
                f"{type(exc).__name__ if exc is not None else 'manual'}: "
                f"{str(exc)[:400]}"})
    m = _ACTIVE.get("meter")
    if m is not None:
        try:
            m.sample(tag="oom")  # one last reading, if the runtime answers
        except Exception:  # noqa: BLE001
            pass
    budget = _ACTIVE.get("budget")
    if budget:
        top = sorted(budget.get("rows", ()),
                     key=lambda r: -r["per_chip_bytes"])[:TOP_LIVE_BUFFERS]
        fr.record("hbm_budget", {
            "mode": budget.get("mode"),
            "per_chip": budget.get("per_chip"),
            "per_chip_total": budget.get("per_chip_total"),
            "activation_bytes": sum(
                r["bytes"] for r in budget.get("activation_rows", ())),
            "largest_leaves": [
                {"leaf": r["leaf"], "kind": r["kind"],
                 "per_chip_bytes": r["per_chip_bytes"]} for r in top],
        })
    for row in _top_live_buffers():
        fr.record("live_buffer", row)
    return fr.dump(reason or (
        f"oom:{type(exc).__name__}" if exc is not None else "oom:manual"))


_OOM_HOOK = {"installed": False}


def install_oom_hook() -> None:
    """Chain an OOM recognizer onto ``sys.excepthook`` (in front of the
    telemetry flight-recorder hook, which installed first): a crashing
    ``XlaRuntimeError``/RESOURCE_EXHAUSTED enriches the ring with the
    budget table and largest live buffers BEFORE the postmortem dump,
    so the OOM is diagnosable from flightrec-*.jsonl alone. Idempotent."""
    with _ACTIVE_LOCK:
        if _OOM_HOOK["installed"]:
            return
        _OOM_HOOK["installed"] = True
    prev = sys.excepthook

    def _hook(exc_type, exc, tb):
        try:
            if _is_oom(exc_type, exc):
                oom_postmortem(exc)
        except Exception:  # noqa: BLE001 — never mask the real crash
            pass
        prev(exc_type, exc, tb)

    sys.excepthook = _hook


# ------------------------------------------------------ monitor + flags


class ResourceMonitor:
    """The loops' one-stop resource accountant: bundles the memory
    meter, the compile sentry, and the comm ledger behind the two calls
    the loops make — ``scalars()`` at the display cadence and
    ``note_dispatch(site, batch|signature)`` per dispatch."""

    def __init__(self, meter: MemoryMeter | None,
                 sentry: CompileSentry | None,
                 ledger: dict | None):
        self.meter = meter
        self.sentry = sentry
        self.ledger = ledger

    def scalars(self) -> dict:
        out: dict = {}
        if self.meter is not None:
            out.update(self.meter.scalars())
        if self.sentry is not None:
            out.update(self.sentry.scalars())
        if self.ledger is not None:
            out["comm_bytes_per_step"] = float(
                self.ledger["comm_bytes_per_step"])
            out["comm_exposed_bytes_per_step"] = float(
                self.ledger.get("comm_exposed_bytes_per_step",
                                self.ledger["comm_bytes_per_step"]))
        return out

    def note_dispatch(self, site: str, batch=None, signature=None) -> None:
        if self.sentry is None:
            return
        sig = signature if signature is not None else batch_signature(batch)
        self.sentry.observe(site, sig)


def parallel_config_from_flags(FLAGS, n_chips: int) -> dict:
    """Derive the budget/ledger layout config from the parsed flags —
    the one flags->layout mapping the loops, bench, and tools share."""
    model_axis = max(1, int(getattr(FLAGS, "model_axis", 1) or 1))
    zero = int(getattr(FLAGS, "zero", 0) or 0)
    if zero:
        mode, model_axis = f"zero{zero}", 1
    elif getattr(FLAGS, "pipeline", False):
        mode = "pp"
    elif getattr(FLAGS, "expert_parallel", False):
        mode = "ep"
    elif getattr(FLAGS, "seq_parallel", False):
        mode = "sp"
    elif model_axis > 1:
        mode = "tp"
    else:
        mode = "dp"
    return {
        "mode": mode,
        "data_ways": max(1, int(n_chips) // model_axis),
        "model_axis": model_axis,
        "zero_level": zero,
        "virtual_stages": max(1, int(getattr(FLAGS, "virtual_stages", 1)
                                     or 1)),
        "microbatches": int(getattr(FLAGS, "pp_microbatches", 0) or 0),
        "pp_schedule": getattr(FLAGS, "pp_schedule", "auto") or "auto",
        "zero_overlap": bool(getattr(FLAGS, "zero_overlap", False)),
        "zero_bucket_mb": float(getattr(FLAGS, "zero_bucket_mb", 4.0)
                                or 4.0),
    }


def monitor_from_flags(FLAGS, model, optimizer, batch_size: int,
                       n_chips: int,
                       model_axis: int | None = None) -> ResourceMonitor | None:
    """The one flag->feature mapping for the resource plane
    (``--hbm_sample_every`` / ``--recompile_budget``), shared by every
    training loop and the serving entry point. None under
    ``--telemetry=false`` (the plane rides the spine — its samples,
    storm spans, and postmortems are all telemetry artifacts).
    Installs the process-wide hooks (compile listener, OOM excepthook)
    and emits the ``comm_ledger`` instant span the fleet report reads.

    ``model_axis`` overrides the flag-derived layout with an explicit
    TP degree — the serving entry point passes ``--serve_tp`` (a
    TP-sharded replica's budget must price the 1/K params each chip
    actually holds, not the training namespace's --model_axis)."""
    if not bool(getattr(FLAGS, "telemetry", True)):
        return None
    cfg = parallel_config_from_flags(FLAGS, n_chips)
    if model_axis is not None and int(model_axis) > 1:
        cfg.update(mode="tp", model_axis=int(model_axis),
                   data_ways=max(1, int(n_chips) // int(model_axis)),
                   zero_level=0)
    budget = ledger = None
    try:
        budget = resource_budget(model, optimizer, batch_size, **cfg)
    except Exception as e:  # noqa: BLE001 — accounting never blocks a run
        print(f"resource accounting: analytic budget unavailable: {e}")
    if optimizer is not None:
        # the ledger prices a TRAINING step's collectives; a serving
        # caller (no optimizer) has no grad traffic to price
        try:
            ledger = comm_ledger(model, optimizer, batch_size, **cfg)
        except Exception as e:  # noqa: BLE001
            print(f"resource accounting: comm ledger unavailable: {e}")
    sample_every = int(getattr(FLAGS, "hbm_sample_every", 1) or 0)
    # the cross-check anchor is the PERSISTENT state (params+opt):
    # samples land at display boundaries, between steps, where grads
    # and activations are transient (and --device_data's resident
    # split is a documented live-over-analytic delta)
    meter = (MemoryMeter(
        analytic_bytes=budget["per_chip_state_bytes"] if budget else None,
        sample_every=sample_every) if sample_every > 0 else None)
    sentry = CompileSentry(
        budget=int(getattr(FLAGS, "recompile_budget", 0) or 0))
    _install_compile_listener()
    install_oom_hook()
    activate(meter=meter, sentry=sentry, budget=budget)
    if ledger is not None:
        telemetry.get_tracer().record_instant(
            "comm_ledger", mode=ledger["mode"],
            comm_bytes_per_step=ledger["comm_bytes_per_step"],
            data_ways=ledger["data_ways"],
            model_axis=ledger["model_axis"])
    return ResourceMonitor(meter, sentry, ledger)
