"""Readings that set the limits of the correctness check, on the chip.

    python chipbench/control.py --workload <cell> --seeds 11,12,... --seconds <s>

For each seed, in one process: one run of the cell exactly as
``run.py`` makes it (set-up, window, the reference on the seeded sample),
with the control put in the program's place for the check: at the same
sample and positions, the tokens that the reference computed with every
matmul operand in float8 e4m3 (the precision below the configuration's
bfloat16) puts first are scored by the same comparison, so the run's
``correct`` is the control's and has to come out false.  Prints per seed
the program's widest logit gap (the lower reading), the control's (the
upper one) and the control's ``correct``, then a summary as the last
line.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from chipbench import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.run_cell(Path.cwd(), args.workload, seed, args.seconds, False, control=True,
                         t_start=run.T_START if not rows else time.time())
        row = {"seed": seed, **r["readings"], "control_correct": r["correct"],
               "metrics": {k: v["value"] for k, v in r["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = max(r["program_gap_max"] for r in rows)
    upper = min(r["control_gap_max"] for r in rows)
    print(json.dumps({"workload": args.workload, "seeds": len(rows), "lower": lower,
                      "upper": upper, "ratio": upper / lower if lower else None,
                      "control_correct_any": any(r["control_correct"] for r in rows)}))


if __name__ == "__main__":
    main()
