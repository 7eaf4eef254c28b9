"""Milliseconds a step in the slowest interval between two synced display
rows of the window: where a stall of the host or of the device shows."""


def read(run):
    return float(run["window"]["step_ms_slowest"])
