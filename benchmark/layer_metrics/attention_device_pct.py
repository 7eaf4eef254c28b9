"""Share of the traced window the worst device spent in operations of the
program's ``attention`` scope: the attention core (scores, online softmax, weighted sum; forward, the flash backward and what remat recomputes of it).
Own time over all programs of the window, the display eval included
(``harness/scopes.py``); None where the program names no such scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "attention")
