"""The fused causal attention's share of the chip's bf16 peak
(``ops/flash_attention.py``: the forward and the backward kernel, and
whatever else runs under the program's ``attention`` scope).

The operations: the family's ``attention`` count a token
(``scope_flops_per_token``: forward and backward of QK^T and PV over the
causal half, nothing recomputed counted twice: ``--remat`` runs the forward
kernel a second time and the count does not, so the share reads lower for
it) times the tokens a chip takes a step. The time: the ``attention``
scope's own time inside one whole run of the step's program, the median
over the whole runs of the traced window, on the slowest device
(``harness/scopes.py``: ``by_run``). So the time is split by program and
count and time cover the same operations: the display evals' forward
passes, which ``attention_device_pct`` includes, are another program's and
are left out of both, and so is a step cut by an edge of the trace. The
kernels are bound by the MXU and the VPU, not by HBM (q, k, v, out and
their gradients cross it once), so the roofline is the peak of
``harness/peaks.json``.

None where there is no trace or no peak, where the program names no such
scope, where the family gives no count for it, or where no step ran whole.
"""

import statistics

from benchmark.harness import scopes

SCOPE = "attention"


def read(run):
    devices = scopes.of_run(run)
    if not devices or run.get("peaks") is None:
        return None
    cell = run["cell"]
    count = cell.family().scope_flops_per_token(cell.sizes).get(SCOPE)
    program = run["trace"].get("step_module")
    per_step = [d["scopes"].get(SCOPE, {}).get("by_run", {}).get(program)
                for d in devices.values()]
    slowest_ns = max((statistics.median(ns) for ns in per_step if ns),
                     default=0)
    if not count or not slowest_ns:
        return None
    flops = count * cell.tokens_per_step / cell.chips
    return 100.0 * flops / (slowest_ns * 1e-9) / run["peaks"]["bf16_flops_per_s"]
