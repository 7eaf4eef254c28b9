"""Share of the traced window the worst device spent in operations of the
program's ``mlp`` scope: the MLP half of every block (LN2, both dense layers, ReLU, residual).
Own time over all programs of the window, the display eval included
(``harness/scopes.py``); None where the program names no such scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "mlp")
