"""The whole step's share of the chip's bf16 peak: the operations a token
needs (forward and backward, causal half of attention, nothing recomputed
counted; ``harness/flops.py``) times the window's tokens a second and chip,
over the peak of ``harness/peaks.json``."""

from benchmark.harness import flops


def read(run):
    if run["peaks"] is None:
        return None
    cell = run["cell"]
    per_token = flops.train_flops_per_token(cell.sizes, cell.mix["seq_len"])
    rate = run["window"]["tokens_per_s_per_chip"]
    return 100.0 * per_token * rate / run["peaks"]["bf16_flops_per_s"]
