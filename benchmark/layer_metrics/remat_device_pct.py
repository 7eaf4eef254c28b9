"""Share of the traced window the worst device spent in operations whose
path holds ``rematted_computation``: the forward work ``--remat`` does a
second time in the backward pass. None where the program recomputes
nothing."""

from benchmark.harness import scopes


def read(run):
    devices = scopes.of_run(run)
    if not devices or not run["trace"]["window_s"]:
        return None
    if not any(d["has_remat"] for d in devices.values()):
        return None
    ns = scopes.worst_ns(devices, lambda d: d["remat_ns"])
    return 100.0 * ns / 1e9 / run["trace"]["window_s"]
