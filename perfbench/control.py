"""Readings for the limit of `correct`, on the chip at a cell's own size.

    python3 perfbench/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process, runs the cell as the benchmark does and
prints one JSON line: the program's worst relative gap against the plain
reference (the lower reading) and the control's, where the reference with
bfloat16 accumulators stands in the program's place for the same answers
(the upper reading). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

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
        result, answers, cfg = run.run_cell(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"],
            allow_cpu, spec_overrides)
        cycles = dict(run.load_cell(args.workload)["spec"],
                      **(spec_overrides or {}))["cycles"]
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"],
                "program_gap": result["compared"]["worst_rel_gap"]["value"],
                "control_gap": compare.control(cfg, answers, cycles),
                "answers": len(answers)}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    main()
