"""Seconds the trainer spent tracing its functions to jaxprs before the
window opened: the union of its ``compile_trace`` spans (jax's
``jaxpr_trace_duration``, one a program, ``fun`` its name) that ended
before the opening row: traces on two threads, or one inside another, count
their seconds once. None where the program writes no such span."""


def phase(run, name):
    """The spans of one compile phase that ended before the window's
    opening row."""
    opened = run["window"]["open"]["time"]
    return [s for s in run["spans"]
            if s["name"] == name and s["ts"] + s["dur_s"] <= opened]


def union_s(spans):
    """Seconds covered by at least one of the spans."""
    total, covered_to = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s["ts"]):
        end = s["ts"] + s["dur_s"]
        if end > covered_to:
            total += end - max(s["ts"], covered_to)
            covered_to = end
    return total


def read(run):
    spans = phase(run, "compile_trace")
    return union_s(spans) if spans else None
