"""Share of the traced window the worst device spent in operations of the
program's ``moe_router`` scope: everything of a routed layer but its
experts' products: the norm before it, the float32 logits and softmax, the
top-k, the sort of the (row, expert) pairs by expert, the gather of the
sorted rows and the scatter-add of the results. Own time over all programs
of the window, the display eval included (``harness/scopes.py``); None where
the program names no such scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "moe_router")
