"""Share of the traced window the worst device spent in operations of the
program's ``moe_experts`` scope: the two grouped products of every routed
layer (``ops/moe.py:grouped_matmul``, forward and backward) and the gate's
silu between them. Own time over all programs of the window, the display
eval included (``harness/scopes.py``); None where the program names no such
scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "moe_experts")
