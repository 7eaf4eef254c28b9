"""Share of the traced window the worst device spent in operations under no
scope of the program's catalog: the guard of the scope metrics. It holds the
loop's plumbing and whatever a fusion at a block's edge took the wrong
instruction's name from; near 100, the executables came from a cache filled
before the scopes existed. None where the program has no catalog."""

from benchmark.harness import scopes


def read(run):
    return scopes.scope_pct(run, scopes.UNSCOPED)
