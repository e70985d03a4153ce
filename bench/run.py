"""Run one cell of the benchmark once:

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are read from
``BENCHMARK.json`` and the files it names under ``bench/``. The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit); the same numbers end standard error. A run
whose first device is not a TPU, or that has fewer chips than the cell
asks for, exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()     # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        run = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T0)
    except harness.NoAccelerator as e:
        print(f"bench: {e}; there is no CPU path", file=sys.stderr)
        return 2
    res = run.result
    print(f"bench: readings {json.dumps(run.readings)}", file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
