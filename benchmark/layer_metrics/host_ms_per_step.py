"""Host milliseconds a step: the trainer's ``step_dispatch_s`` plus
``step_host_wait_s`` (both already per step) at the window's closing row."""


def read(run):
    row = run["window"]["scalars_close"]
    if not row or "step_dispatch_s" not in row:
        return None
    return 1e3 * (row["step_dispatch_s"] + row.get("step_host_wait_s", 0.0))
