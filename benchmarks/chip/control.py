#!/usr/bin/env python3
"""The control of a cell's check: the reference in bfloat16, on given seeds.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \\
        [--seconds 20]

For each seed it makes the cell's inputs as a run does, puts the reference
computed in bfloat16 (weights, currents and every accumulation) in the
program's place, and prints the numbers the check compares, one JSON line
a seed.  The check has to refuse it: each limit lies below the smallest of
these readings.  A fleet cell's request sequence is the one a run with
``--seconds`` would serve.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def readings(cell, seed, seconds) -> dict:
    """The control's readings of one seed: the cell's driver makes them
    (``drivers/<driver>.py``'s ``control_readings``)."""
    from chip import harness

    return harness.driver(cell).control_readings(cell, seed, seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]
    from chip import harness

    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": readings(cell, seed, args.seconds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
