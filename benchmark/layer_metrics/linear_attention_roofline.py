"""The linear layers' core against its roofline: the time the family's
counts for the ``linear_attention`` scope would take at the chip's bf16
peak or at its HBM bandwidth, whichever is longer, over the time the scope
took.

The operations: the family's ``linear_attention`` count a token
(``scope_flops_per_token``: the recurrence's decay, S^T k, rank-one update
and read-out, 7 dk dv a value head and layer, three times for forward and
backward); the bytes: its ``scope_bytes_per_token`` (the core's inputs read
and its output written forward, the inputs and dO read and the inputs'
gradients written backward; the state is never counted). Both are functions
of the sizes alone, the same whatever chunk or kernel computes the rule, and
both are multiplied by the tokens a chip takes a step. ``--remat``'s second
forward is in the time and not in the counts. The time: the scope's own
time inside one whole run of the step's program, the median over the whole
runs of the traced window, on the slowest device (``harness/scopes.py``:
``by_run``), as ``attention_roofline`` reads ``attention``.

None where there is no trace or no peak, where the program names no such
scope, where the family gives no count for it, or where no step ran whole.
"""

import statistics

from benchmark.harness import scopes

SCOPE = "linear_attention"


def read(run):
    devices = scopes.of_run(run)
    if not devices or run.get("peaks") is None:
        return None
    cell = run["cell"]
    family = cell.family()
    count = family.scope_flops_per_token(cell.sizes).get(SCOPE)
    moved = family.scope_bytes_per_token(cell.sizes).get(SCOPE)
    program = run["trace"].get("step_module")
    per_step = [d["scopes"].get(SCOPE, {}).get("by_run", {}).get(program)
                for d in devices.values()]
    slowest_ns = max((statistics.median(ns) for ns in per_step if ns),
                     default=0)
    if not count or not moved or not slowest_ns:
        return None
    tokens = cell.tokens_per_step / cell.chips
    peaks = run["peaks"]
    bound_s = max(count * tokens / peaks["bf16_flops_per_s"],
                  moved * tokens / peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (slowest_ns * 1e-9)
