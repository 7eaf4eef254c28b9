"""Share of the traced window the worst device spent in operations of the
program's ``linear_attention`` scope: all that a linear layer (Gated
DeltaNet) runs between its input projections and its output projection,
the causal conv and its SiLU, the gates, the l2 norms, the chunked delta
rule and the gated norm, forward and backward. Own time over all programs
of the window, the display eval included (``harness/scopes.py``); None where
the program names no such scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "linear_attention")
