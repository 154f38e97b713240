#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for: the program under test is imported from ``<root>/src``.
Off a TPU, or on fewer chips than the cell asks for, it exits with code 2
and prints no result.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` profiles a part of the window and reports its per-layer
metrics, the device's busy and window seconds, and a breakdown.  See
`chip.harness` for what is printed.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]
    from chip import harness

    return harness.main(args, T0)


if __name__ == "__main__":
    sys.exit(main())
