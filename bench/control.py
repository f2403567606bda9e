"""Readings that set a cell's limits: the program's numbers and the
control's, over several seeds, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

Per seed it runs the cell's set-up and a window at the cell's own load,
then prints the numbers the check compares for what the program produced
and, on the first ``--control-seeds`` seeds, for the control: the plain
reference at Precision.HIGH, the precision just below the configuration's,
put in the program's place.  The limit of
each number lies between the largest program reading and the smallest
control reading (PERF.md lists both).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.run import require_chips  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool) -> dict:
    drv = harness.load_class(cell.mix["kind"], cell.root)(cell, seed)
    drv.setup()
    drv.window(seconds)
    drv.close()
    out = {"seed": seed, "program": drv.check()}
    if control:
        out["control"] = drv.control()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the control on the first this many seeds")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    why = require_chips(cell.chips)
    if why:
        print(f"control: {why}", file=sys.stderr)
        return 2
    for i, s in enumerate(args.seeds.split(",")):
        print(json.dumps(readings(cell, int(s), args.seconds,
                                  i < args.control_seeds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
