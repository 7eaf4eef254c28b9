"""The whole step's share of the chip's bf16 peak: the operations a token
needs (forward and backward, causal half of attention, nothing recomputed
counted: ``train_flops_per_token`` of the configuration's family,
``benchmark/reference/<family>.py``) times the window's tokens a second and
chip, over the peak of ``harness/peaks.json``."""


def read(run):
    if run["peaks"] is None:
        return None
    cell = run["cell"]
    per_token = cell.family().train_flops_per_token(cell.sizes)
    rate = run["window"]["tokens_per_s_per_chip"]
    return 100.0 * per_token * rate / run["peaks"]["bf16_flops_per_s"]
