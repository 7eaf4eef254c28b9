"""Share of the traced window in which a collective ran on a device and no
compute operation did, worst device. For cells across chips; no cell of
``BENCHMARK.json`` lists it yet (PERF.md, Open questions: the four-chip
cell was measured in PR 24 and left out)."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"] or t.get("collective_exposed_s") is None:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
