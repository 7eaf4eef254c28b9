"""Share of the traced window the worst device spent in operations of the
program's ``moe_shared`` scope: the gated expert that every row of a routed
layer takes beside the routed ones (two dense products and the gate's silu,
forward and backward, and the sum with the routed part). Own time over all
programs of the window, the display eval included (``harness/scopes.py``);
None where the program names no such scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "moe_shared")
