"""Share of the traced window the worst device spent in operations of the
program's ``loop_exit`` scope: what a stack run several times adds around its
layers: the exit gate's unit after every pass, the exit distribution, the
weighting of the passes' losses and the entropy term, forward and backward,
and the loop's own work (the passes' outputs stacked, the shared layers'
gradients summed over the passes). The layers stay under their own scopes,
the head run after every pass under ``lm_head``. Own time over all programs
of the window, the display eval included (``harness/scopes.py``); None where
the program names no such scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "loop_exit")
