"""Seconds of ``setup_s`` that no span accounts for: ``setup_s`` less
``launch_s`` less the main thread's top-level spans (``parent`` null) that
ended before the window's opening row. The guard of the set-up spans: where
it is more than a tenth of ``setup_s`` a span is missing in the program.
None where the program writes no ``train_start`` or no span ids."""

from benchmark.layer_metrics import launch_s

MAIN = "MainThread"


def read(run):
    launch = launch_s.read(run)
    if launch is None:
        return None
    opened = run["window"]["open"]["time"]
    top = [s for s in run["spans"]
           if "id" in s and s.get("parent") is None
           and s.get("thread") == MAIN and s["ts"] + s["dur_s"] <= opened]
    if not top:
        return None
    return run["setup_s"] - launch - float(sum(s["dur_s"] for s in top))
