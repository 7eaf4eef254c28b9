"""Share of the traced window the worst device spent in operations of the
program's ``optimizer`` scope: the optimizer's update (Adam on the f32 masters, and the clip where one is set).
Own time over all programs of the window, the display eval included
(``harness/scopes.py``); None where the program names no such scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "optimizer")
