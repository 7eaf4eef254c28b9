"""The dttperf step-time model: one analytic prediction per
(parallel-mode x model) cell, composed ONLY from verified duals.

``predict_step_time(plan, model, chips)`` prices one training step as

    max(compute_s, exposed_comm_s) + host_fixed_s

where every term has a machine-checked provenance:

- ``compute_s`` — ``utils.efficiency.flops_budget`` (the analytic
  per-layer FLOPs table, 3x fwd train accounting) over ``chips`` x the
  hardware's peak FLOP/s (``utils.efficiency.TPU_PEAK_FLOPS`` spec
  row), divided by the pipeline schedule's useful-tick fraction
  (``parallel.pp_schedule.schedule_useful_fraction`` — the same tick
  table bench records) when the plan pipelines: bubbles stretch the
  compute term, they don't add wire bytes.
- ``exposed_comm_s`` — ``utils.resources.comm_ledger``'s
  ``comm_exposed_bytes_per_step`` (jaxpr-proven byte-exact by
  tools/dttcheck as of r18; overlap-hidden bytes already subtracted)
  over the interconnect bandwidth — ICI for on-mesh collectives, the
  host TCP wire for the PS emulation topology.
- ``host_fixed_s`` — the fixed per-step host cost under the
  device-resident chunked dispatch (CHUNK steps ride one dispatch, so
  the per-step share is micro-seconds; the HARDWARE table documents
  the figure).

The prediction is a CEILING (efficiency 1.0 against spec peak), not a
point estimate: DTP001 bands MEASURED rates as a fraction of it, so a
regression shows up as the measured/predicted ratio leaving the
phase's declared band. The plan dict is normalized through
``tools.dttcheck.scenarios.ledger_config`` — the layout the predictor
prices is byte-identical to the one dttcheck proves.

ROADMAP item 1's auto-planner imports this function as its scorer; it
must stay chip-free (``flops_budget`` is pure Python, ``comm_ledger``
is ``jax.eval_shape``) and cheap enough to call per candidate plan.
"""

from __future__ import annotations

#: per-hardware constants the terms divide by. Peak FLOP/s figures are
#: the public spec rows (``utils.efficiency.TPU_PEAK_FLOPS``); ICI is
#: the public per-chip interconnect figure; the host wire is a NOMINAL
#: 1 Gbit/s host network path for the PS cycle's TCP — not measured on
#: today's installation, and link-bound rates are DTP001-exempt, so the
#: figure only shapes the PS cell's predicted ceiling.
HARDWARE: dict = {
    "v5lite": {
        "peak_flops_per_chip": 197e12,   # bf16, TPU_PEAK_FLOPS["TPU v5 lite"]
        "ici_bytes_per_sec": 2.0e11,     # 4 x 400 Gbps ICI links / chip
        "host_wire_bytes_per_sec": 1.25e8,  # ~1 Gbit/s, nominal
        "host_fixed_s": 2.0e-5,          # per-step share of the chunked
                                         # dispatch (CHUNK=50 steps ride
                                         # one host round trip)
    },
}

DEFAULT_HARDWARE = "v5lite"

#: per-model-family default per-data-shard batch when the caller gives
#: no ``global_batch`` — the bench flagship configs (PER_CHIP_BATCH for
#: the image models, the LM phases' token batches).
DEFAULT_PER_SHARD_BATCH_IMAGE = 2048
DEFAULT_PER_SHARD_BATCH_LM = 32


def predict_step_time(plan, model, chips: int, *,
                      global_batch: int | None = None,
                      hardware=DEFAULT_HARDWARE) -> dict:
    """Predicted step time for ``model`` laid out per ``plan`` (the
    ``parallel_config_from_flags`` / ``comm_ledger`` kwargs shape) on
    ``chips`` chips. Returns the full term decomposition with per-term
    provenance (``terms``), the step time, and the implied
    examples/sec ceiling DTP001 bands measured rates against."""
    from distributed_tensorflow_tpu.parallel.pp_schedule import (
        schedule_useful_fraction,
    )
    from distributed_tensorflow_tpu.utils.efficiency import flops_budget
    from distributed_tensorflow_tpu.utils.resources import comm_ledger

    from tools.dttcheck.scenarios import ledger_config

    hw = HARDWARE[hardware] if isinstance(hardware, str) else dict(hardware)
    plan = dict(plan or {})
    mode = plan.pop("mode", "dp")
    plan = ledger_config(mode, **plan)
    chips = max(1, int(chips))
    if global_batch is None:
        per_shard = (DEFAULT_PER_SHARD_BATCH_IMAGE
                     if hasattr(model, "image_size")
                     else DEFAULT_PER_SHARD_BATCH_LM)
        global_batch = per_shard * plan["data_ways"]
    global_batch = int(global_batch)

    budget = flops_budget(model, global_batch)
    compute_s = budget["flops_per_step"] / (
        hw["peak_flops_per_chip"] * chips)
    useful = 1.0
    compute_src = ("utils.efficiency.flops_budget (analytic per-layer "
                   "table, 3x fwd) / (peak_flops_per_chip x chips)")
    if mode == "pp":
        useful = schedule_useful_fraction(
            plan["pp_schedule"], plan["model_axis"],
            plan["microbatches"] or plan["model_axis"],
            plan["virtual_stages"])
        compute_s /= max(useful, 1e-9)
        compute_src += (" / parallel.pp_schedule.schedule_useful_"
                        "fraction (bubbles stretch compute)")

    ledger = comm_ledger(model, None, global_batch, **plan)
    wire = "host_wire" if mode == "ps" else "ici"
    bw = hw[f"{wire}_bytes_per_sec"]
    comm_s = ledger["comm_exposed_bytes_per_step"] / bw

    step_s = max(compute_s, comm_s) + hw["host_fixed_s"]
    return {
        "mode": mode,
        "model": type(model).__name__,
        "chips": chips,
        "global_batch": global_batch,
        "hardware": hardware if isinstance(hardware, str) else "custom",
        "plan": plan,
        "flops_per_step": budget["flops_per_step"],
        "train_flops_per_example": budget["train_flops_per_example"],
        "useful_fraction": round(useful, 6),
        "compute_s": compute_s,
        "comm_bytes_per_step": ledger["comm_bytes_per_step"],
        "comm_exposed_bytes_per_step":
            ledger["comm_exposed_bytes_per_step"],
        "comm_s": comm_s,
        "host_s": hw["host_fixed_s"],
        "step_time_s": step_s,
        "bound": "comm" if comm_s > compute_s else "compute",
        "examples_per_sec": global_batch / step_s,
        "examples_per_sec_per_chip": global_batch / step_s / chips,
        "terms": [
            {"term": "compute", "seconds": compute_s,
             "source": compute_src},
            {"term": "exposed_comm", "seconds": comm_s,
             "source": "utils.resources.comm_ledger comm_exposed_"
                       "bytes_per_step (jaxpr-proven by tools/dttcheck)"
                       f" / {wire}_bytes_per_sec"},
            {"term": "host", "seconds": hw["host_fixed_s"],
             "source": "HARDWARE fixed per-step dispatch share "
                       "(device-resident chunked loop)"},
        ],
    }
