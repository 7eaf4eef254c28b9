"""Seconds of the final save's ``ckpt_write`` span (serialising and writing
the fetched state; the fetch from the device comes before it)."""

from benchmark.harness import spans


def read(run):
    final = spans.named(run["spans"], "ckpt_write", run["sigterm_at"])
    if not final:
        return None
    return float(final[-1]["dur_s"])
