"""Seconds of the trainer's ``data_build`` span before the window opened:
``read_data_sets``, the split built on the host from the seed."""

from benchmark.harness import spans


def read(run):
    built = spans.named(run["spans"], "data_build",
                        t1=run["window"]["open"]["time"])
    if not built:
        return None
    return float(sum(s["dur_s"] for s in built))
