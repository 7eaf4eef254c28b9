"""The window layers' fused attention's share of the chip's bf16 peak: as
``attention_roofline``, for the program's ``attention_window`` scope
(``ops/flash_attention.py`` over the banded grid of ``Mask("window")``).

The operations: the family's ``attention_window`` count a token
(``scope_flops_per_token``: forward and backward of QK^T and PV over the
VISIBLE pairs of the sliding layers, W S - W (W - 1) / 2 a sequence and
head, never over the tiles that run: the masked half of a diagonal tile is
work the kernel does and the count leaves out) times the tokens a chip
takes a step. The time: the ``attention_window`` scope's own time inside one
whole run of the step's program, the median over the whole runs of the
traced window, on the slowest device (``harness/scopes.py``: ``by_run``).
The display evals are another program's and in neither.

None where there is no trace or no peak, where the program names no such
scope, where the family gives no count for it, or where no step ran whole.
"""

import statistics

from benchmark.harness import scopes

SCOPE = "attention_window"


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
