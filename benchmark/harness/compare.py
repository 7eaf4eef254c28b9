"""The comparison that decides ``correct`` for a training cell.

Program and reference each give, for the first three steps from the seed:
each step's loss, the norm of every leaf of the first gradient, and the norm
of every leaf's change over the three steps. Compared are

- ``loss_gap_step<i>``: |program - reference| / |reference|;
- ``grad_norm_gap``: over the leaves, the gap between the two norms of a
  leaf against the reference's norm of that leaf or of the median leaf,
  whichever is larger (some gradients are all but zero), worst leaf;
- ``grad_difference_median``: the norm of (program's first gradient less the
  reference's) of a leaf against the same denominator, median leaf. The two
  gaps of norms above are blind to rounding: an error of zero mean all but
  cancels in a norm, and a float8 control reads only 3 times what bfloat16
  reads. The difference itself does not cancel, and its median leaf is steady
  from seed to seed;
- ``change_norm_gap``: the same of the change, over the leaves whose
  reference gradient is at least a thousandth of the median leaf's: a leaf
  whose gradient is nought to rounding moves under Adam by round-off alone;
- ``ckpt_mismatch``: arrays of the checkpoint the drain wrote that differ
  from the state the last step left, of a sample drawn from the seed, plus
  one if the step differs. An exact comparison: its limit is 0.

Each number has a limit of its own in ``benchmark/limits/<cell>.json``, set
from readings on the chip (the file's ``readings`` and ``PERF.md`` give them).
"""

from __future__ import annotations

import statistics

ZERO_GRADIENT_SHARE = 1e-3


def _worst_leaf(program: dict, reference: dict, leaves) -> tuple[float, str]:
    floor = statistics.median(reference[k] for k in reference)
    worst, name = 0.0, ""
    for k in leaves:
        gap = abs(program[k] - reference[k]) / max(reference[k], floor)
        if not gap <= worst:  # NaN counts as worst
            worst, name = gap, k
    return worst, name


def training_numbers(program: dict, reference: dict) -> dict:
    """name -> (value, detail) for every number of the first steps."""
    if set(program["grad_norms"]) != set(reference["grad_norms"]):
        raise ValueError("program and reference have different leaves: "
                         f"{sorted(set(program['grad_norms']) ^ set(reference['grad_norms']))}")
    out = {}
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"]),
                               start=1):
        out[f"loss_gap_step{i}"] = (abs(p - r) / abs(r),
                                    f"program {p!r} reference {r!r}")
    ref_g = reference["grad_norms"]
    gap, leaf = _worst_leaf(program["grad_norms"], ref_g, ref_g)
    out["grad_norm_gap"] = (gap, f"worst leaf {leaf}")
    if "grad_differences" in reference:
        floor = statistics.median(ref_g.values())
        shares = {k: d / max(ref_g[k], floor)
                  for k, d in reference["grad_differences"].items()}
        middle = statistics.median(shares.values())
        out["grad_difference_median"] = (
            middle if middle == middle else float("inf"),
            f"worst leaf {max(shares, key=shares.get)} "
            f"{max(shares.values())!r}")
    floor = ZERO_GRADIENT_SHARE * statistics.median(ref_g.values())
    moved = [k for k in ref_g if ref_g[k] >= floor]
    gap, leaf = _worst_leaf(program["change_norms"],
                            reference["change_norms"], moved)
    out["change_norm_gap"] = (
        gap, f"worst leaf {leaf}; {len(ref_g) - len(moved)} leaves with "
        f"all-but-zero reference gradient left out")
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, checks): each number beside its limit. A number that the
    limits do not name, or one that is not below its limit, makes the run
    incorrect. A limit of ``null`` says that the number is not compared in
    this cell (neither the control nor a fault reads above sound runs, so a
    limit could only fail sound runs); it is printed all the same."""
    checks, correct = {}, True
    for name, (value, detail) in numbers.items():
        if name in limits and limits[name] is None:
            checks[name] = {"value": value, "limit": None, "ok": True,
                            "detail": f"not compared; {detail}"}
            continue
        limit = limits.get(name)
        ok = limit is not None and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit, "ok": ok,
                        "detail": detail}
    return correct, checks
