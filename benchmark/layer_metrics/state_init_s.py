"""Seconds of the trainer's ``state_init`` span before the window opened:
the model, the optimizer and the fresh train state from the seed, until
they are on the device."""

from benchmark.harness import spans


def read(run):
    made = spans.named(run["spans"], "state_init",
                       t1=run["window"]["open"]["time"])
    if not made:
        return None
    return float(sum(s["dur_s"] for s in made))
