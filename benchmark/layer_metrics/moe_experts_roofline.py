"""The routed experts' share of the chip's bf16 peak: the grouped products
of ``ops/moe.py`` (and whatever else runs under the program's
``moe_experts`` scope).

The operations: the family's ``moe_experts`` count a token
(``scope_flops_per_token``: forward and backward of the experts' three
matrices on the rows that uniform routing sends to the experts held here;
the EXPECTED count, from sizes alone: how far a seed's routing was from it
is the display row's ``moe_rows_per_expert_mean``, and ``--remat``'s second
forward is not counted, so the share reads lower for it) times the tokens a
chip takes a step. The time: the ``moe_experts`` scope's own time inside one
whole run of the step's program, the median over the whole runs of the
traced window, on the slowest device (``harness/scopes.py``: ``by_run``),
exactly as ``attention_roofline`` reads ``attention``. An expert here sees
about 2,048 rows a step, a (2,048 x 2,048 x 768) product: above the chip's
ridge, so the roofline is the peak of ``harness/peaks.json``.

None where there is no trace or no peak, where the program names no such
scope, where the family gives no count for it, or where no step ran whole.
"""

import statistics

from benchmark.harness import scopes

SCOPE = "moe_experts"


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
