"""Readings for the limit of `correct` in a `run_trace` cell, on the chip
at the cell's own size.

    python3 perfbench/control_trace.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

`control.py` cannot reach such a cell: its answers name no reference rows,
and each derives its own snapshot from the churn reference. So for each
seed, in one process, this runs the cell as the benchmark does and prints
one JSON line: the program's worst relative gap against the churn
reference (the lower reading) and the control's, where the churn
reference with bfloat16 accumulators and float32 statistics stands in the
program's place for the same answers (the upper reading).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, allow_cpu: bool = False, spec_overrides=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from perfbench import compare, run
    out = []
    for seed in args.seeds:
        result, answers, _ = run.run_cell(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"],
            allow_cpu, spec_overrides)
        worst = 0.0
        for a in answers:
            want = a.derive({})
            got = a.derive({}, acc_dtype="bfloat16", stat_dtype=np.float32)
            worst = max([worst] + [compare.gap(got[k], want[k])
                                   for k in a.got])
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"],
                "program_gap": result["compared"]["worst_rel_gap"]["value"],
                "control_gap": worst, "answers": len(answers)}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    main()
