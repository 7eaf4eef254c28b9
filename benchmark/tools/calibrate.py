"""The control and the planted faults of a training cell, read on the chip.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 [--out FILE]

For every seed the plain reference of the cell's family runs the first three
steps in float32 (what the program is compared with), then once more in the control's
precision (float8 operands in every linear layer, the step below the
configuration's bfloat16) and once with the half-batch fault (the mean taken
over the first half of the rows) and once with the state left unchanged
(learning rate 0), each put in the program's place in the cell's own
comparison. Across chips a third pass leaves the exchange out
(one chip's rows alone). One JSON line a seed; the benchmark's own runs
never run this. ``PERF.md`` records the readings the limits were set from.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import compare, manifest  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--one-chip", action="store_true",
                    help="a four-chip cell's readings on a one-chip machine")
    ap.add_argument("--any-device", action="store_true",
                    help="rehearse on the CPU at a tiny size")
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    # the reference, and so the control and the plants, run on one device
    # whatever the cell's chips: a four-chip cell is calibrated on one chip
    device = bench_run.check_device(1 if args.one_chip else cell.chips,
                                    not args.any_device)
    rows = cell.mix["batch_per_chip"] * cell.chips
    plants = {"control_fp8": {"precision": "fp8"},
              "fault_half_batch": {"keep_rows": list(range(rows // 2))},
              "fault_state_unchanged": {"learning_rate": 0.0}}
    if cell.chips > 1:
        plants["fault_no_exchange"] = {
            "keep_rows": list(range(cell.mix["batch_per_chip"]))}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        reference = bench_run.reference_numbers(cell, seed,
                                                keep_first_gradient=True)
        own = reference.pop("first_gradient")
        line = {"workload": cell.name, "seed": seed, "device": device,
                "reference_losses": reference["losses"]}
        for name, kw in plants.items():
            # the comparison is symmetric in whose gradient is "the other"
            planted = bench_run.reference_numbers(
                cell, seed, first_gradient_of_other=own, **kw)
            line[name] = {k: v for k, (v, _) in compare.training_numbers(
                planted, dict(reference, grad_differences=planted[
                    "grad_differences"])).items()}
        line["seconds"] = time.time() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")


if __name__ == "__main__":
    main()
