"""Seconds from the SIGTERM at the window's close to ``train()`` returning:
the steps already enqueued, the fetch of the whole state and the final
checkpoint. What a preempted job has to fit into its grace period."""


def read(run):
    return float(run["drain_s"])
