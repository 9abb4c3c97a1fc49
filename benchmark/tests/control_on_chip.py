#!/usr/bin/env python3
"""The program's numbers and the control's, at the cell's own size, on
the chip, over several seeds:

  chiprun -- python3 benchmark/tests/control_on_chip.py \
      --workload tpch-sf1.power --seeds 3,5,7 --seconds 12

For each seed: one run of the cell through `run_cell` (its window, its
comparison with the reference), then the same comparison with the
control in the program's place: the reference accumulated in float32.
`--shape-from-seed 1` draws the sizes (lines per order, quantities)
from the seed too, where the data set pins them: the program sizes
kernels by them (PERF.md, findings). Prints, for each seed, the numbers
compared; the limits were set from these (PERF.md, section 2). The
benchmark's own runs never run this."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import checks                                               # noqa: E402
import run                                                  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--shape-from-seed", type=int, default=0)
    args = ap.parse_args()
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        k = {}
        result = run.run_cell(
            args.workload, seed, args.seconds, False, keep=k,
            shape_seed=seed if args.shape_from_seed else None)
        control = checks.compare(
            k["dataset"], k["tables"], k["queries"],
            substitute=checks.control_lower_precision(k["dataset"],
                                                      k["tables"]))
        control.pop("details")
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "program": result["compared"], "control_float32": control,
            "metrics": {n: v["value"]
                        for n, v in result["metrics"].items()}}),
              flush=True)
        bad += (not result["correct"]) + (control["answers_wrong"] == 0)
    print("controls", "as expected" if not bad else f"{bad} UNEXPECTED")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
