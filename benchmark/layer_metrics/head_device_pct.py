"""Share of the traced window the worst device spent in operations of the
program's ``lm_head`` scope: the head (final LayerNorm, vocabulary matmul, cross-entropy; forward and streamed backward).
Own time over all programs of the window, the display eval included
(``harness/scopes.py``); None where the program names no such scope."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, "lm_head")
