"""The measured window, read from the trainer's own ``metrics.jsonl``.

The trainer writes two rows at every display: the display loss, right after
the ``float()`` that waits for every step before it, and the scalars. The
first is a synced (step, wall time) pair. The window opens at the first such
row at or past ``open_step`` (set-up, compile and the first-steps probe lie
before it) and closes at the first one at or after open + ``seconds``. Every
end-to-end number is taken over all the steps and all the time between the
two rows, display evals and stalls included.
"""

from __future__ import annotations

import json

SYNC_KEY = "mini_batch_loss"
SCALAR_KEY = "step_dispatch_s"


def parse_rows(text: str) -> list[dict]:
    rows = []
    for line in text.splitlines():
        line = line.strip()
        if not line.endswith("}"):
            continue  # a row still being written
        try:
            rows.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    return rows


def read_rows(path: str) -> list[dict]:
    try:
        with open(path) as f:
            return parse_rows(f.read())
    except FileNotFoundError:
        return []


def synced(rows: list[dict]) -> list[dict]:
    return [r for r in rows if SYNC_KEY in r]


def find_window(rows: list[dict], seconds: float, open_step: int):
    """(open row, close row or None) among the synced rows."""
    opened = None
    for r in synced(rows):
        if opened is None:
            if r["step"] >= open_step:
                opened = r
        elif r["time"] >= opened["time"] + seconds:
            return opened, r
    return opened, None


def scalars_at(rows: list[dict], step: int) -> dict | None:
    for r in rows:
        if r["step"] == step and SCALAR_KEY in r:
            return r
    return None


def reduce_window(rows: list[dict], seconds: float, open_step: int,
                  tokens_per_step: int, chips: int) -> dict:
    """The window's numbers; raises if it never closed."""
    opened, closed = find_window(rows, seconds, open_step)
    if opened is None or closed is None:
        raise RuntimeError(
            f"the window did not close: {len(synced(rows))} synced rows, "
            f"open {opened and opened['step']}")
    inside = [r for r in synced(rows)
              if opened["time"] <= r["time"] <= closed["time"]
              and opened["step"] <= r["step"] <= closed["step"]]
    steps = closed["step"] - opened["step"]
    span = closed["time"] - opened["time"]
    before, after = max(zip(inside, inside[1:]), key=lambda ab: (
        ab[1]["time"] - ab[0]["time"]) / (ab[1]["step"] - ab[0]["step"]))
    slowest = (after["time"] - before["time"]) / (after["step"] - before["step"])
    first, last = (scalars_at(rows, opened["step"]),
                   scalars_at(rows, closed["step"]))
    compiles = None
    if first and last and "compiles_total" in first:
        compiles = int(last["compiles_total"] - first["compiles_total"])
    return {
        "open": opened, "close": closed, "rows": len(inside),
        "steps": steps, "seconds": span,
        "tokens_per_s_per_chip": steps * tokens_per_step / span / chips,
        "step_ms_slowest": slowest * 1e3,
        # where it was: a stall is one interval, and the trainer's spans
        # say what the host did in it (``spans.inside``)
        "slowest_interval": {"from_step": before["step"],
                             "to_step": after["step"],
                             "start": before["time"],
                             "seconds": after["time"] - before["time"]},
        "step_ms_mean": span / steps * 1e3,
        "losses": [r[SYNC_KEY] for r in inside],
        "compiles_in_window": compiles,
        "scalars_open": first, "scalars_close": last,
    }
