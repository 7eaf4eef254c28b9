"""Device milliseconds of one whole training step in the trace: the summed
device time of the step's program (the one that took most of the device's
time), over the times it ran whole inside the trace.

Listed for the one-chip cells only. In the four-chip trace of PR 24 the
program's events on the ``XLA Modules`` line added up to 978-982 ms a step
where the operations' busy time and the host clock both said about 1,090
(PERF.md, Open questions): until such a trace has been looked at by hand the
number is not reported across chips."""


def read(run):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["step_s"] / t["steps"]
