"""Share of the traced window the worst device spent in operations of the
program's ``attention_window`` scope: the attention core of the layers whose
mask is the causal window (``ops/attention.py:Mask("window")``: the fused
kernels over their banded grid, forward, the flash backward and what remat
recomputes of it), which lies beside the full layers' ``attention`` scope and
never inside it. Own time over all programs of the window, the display eval
included (``harness/scopes.py``); None where the program names no such
scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "attention_window")
