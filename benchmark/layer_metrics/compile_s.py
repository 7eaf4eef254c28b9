"""Seconds the trainer spent compiling or loading programs before the
window opened: its own ``compile_time_s`` scalar at the opening row."""


def read(run):
    row = run["window"]["scalars_open"]
    if not row or "compile_time_s" not in row:
        return None
    return float(row["compile_time_s"])
