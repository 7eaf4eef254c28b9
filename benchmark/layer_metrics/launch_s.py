"""Seconds from the start of the process to the trainer's ``train_start``
instant (written as soon as its telemetry is configured): imports, flags,
the compile cache's placement, the backend's start. The process started
``setup_s`` before the window's opening row. ``run["spans"]`` holds no
instants, so the trainer's spans file is read (``scopes.logdir_of``)."""

import glob
import json
import os

from benchmark.harness import scopes


def train_start(run):
    """Epoch seconds of the first ``train_start`` instant, or None."""
    logdir = scopes.logdir_of(run)
    if not logdir:
        return None
    for path in sorted(glob.glob(os.path.join(logdir, "spans-worker-*.jsonl"))):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("name") == "train_start" and "ts" in rec:
                    return float(rec["ts"])
    return None


def read(run):
    started = train_start(run)
    if started is None:
        return None
    return started - (run["window"]["open"]["time"] - run["setup_s"])
