"""The two readings a cell's limits are set from, on the chip, in one process.

    python3 bench/readings.py --workload <cell> --seeds <n> --seconds <s> [--first <seed>]

For each of ``n`` seeds it makes one full run of the cell at its own load
and size (set-up, a window of ``s`` seconds, the reference) and records,
for every number compared, the program's reading and the control's: the
plain reference computed in bfloat16 (the nearest precision below the
configurations' float32) put in the program's place, read at the same
inputs.  The lower reading of a number is the largest the program gives
over the seeds, the upper the smallest the control gives.  One JSON line
per seed and a summary go to standard output.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from harness import cli, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first", type=int, default=7_000_000_001)
    args = ap.parse_args(argv)
    bench = spec.Bench()
    program, control = [], []
    for i in range(args.seeds):
        run_args = argparse.Namespace(workload=args.workload, seed=args.first + 7919 * i,
                                      seconds=args.seconds, trace=0)
        r = cli.run_cell(bench, run_args, time.time(), control=True)
        program.append(r["checks"]["logit_err"]["value"])
        control.append(r["control"]["logit_err"])
        print(json.dumps({"seed": run_args.seed, "correct": r["correct"],
                          "program": r["checks"], "control": r["control"],
                          "metrics": r["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower_logit_err": max(program), "upper_logit_err": min(control),
                      "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
