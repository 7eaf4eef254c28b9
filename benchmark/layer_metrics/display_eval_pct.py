"""Share of the traced window the device spent in programs other than the
training step: in this trainer the display eval (one forward pass of a fresh
batch at every display). The trainer's ``display_eval`` span is no measure
of it: the host runs ahead of the device, so that span is mostly the wait
for the steps already enqueued."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"] or not t["other_programs"]:
        return None
    return 100.0 * t["other_programs_s"] / t["window_s"]
