"""Rows of the fullest held expert over the mean rows a held expert, worst
routed layer over mean of the layers, at the window's closing display row:
the trainer's ``moe_rows_per_expert_max`` / ``moe_rows_per_expert_mean``
(``ops/moe.py:routed_experts`` counts them from the router's choice in the
display eval). 1 is uniform routing; the grouped products' time follows the
sum of the rows, the sorted buffer's room the fullest. None where the
program writes no such counters."""


def read(run):
    close = run["window"]["close"]
    if not close:
        return None
    top, mean = (close.get("moe_rows_per_expert_max"),
                 close.get("moe_rows_per_expert_mean"))
    if top is None or not mean:
        return None
    return top / mean
