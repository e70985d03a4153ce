"""Readings that the limits of ``correct`` are set from, for one cell:

  python3 bench/control.py <cell> <seconds> <seed> [<seed> ...]

Each seed is one run of the cell as the benchmark makes it (the same
set-up, traffic and window, at the cell's own size), in one process.
For each it prints one JSON line with the program's readings of every
number the reference can compare and the control's: the same reference
put in the program's place one precision lower (int8 weights,
activations and K/V for the configuration's bfloat16), drawing its own
token by the mix's sampling rule at the same served positions, with
``correct`` as the control decides it. The lower reading of a limit is
the largest the program gives over the seeds, the upper the smallest
the control gives.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv) -> int:
    from bench import harness
    cell, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    rows = []
    for seed in seeds:
        run = harness.run_cell(cell, seed, seconds, False, control=True)
        row = {"seed": seed, "control_correct": run.result["correct"],
               "setup_s": run.setup_s,
               "metrics": {k: v["value"]
                           for k, v in run.result["metrics"].items()},
               **run.readings}
        rows.append(row)
        print(json.dumps(row), flush=True)
    for name in rows[0]["program"]:
        prog = [r["program"][name] for r in rows]
        ctrl = [r["control"][name] for r in rows]
        print(json.dumps({"number": name, "program_max": max(prog),
                          "control_min": min(ctrl), "program": prog,
                          "control": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
