#!/usr/bin/env python3
"""Faults planted under the timed path, and runs of a cell with one.

    python3 benchmarks/chip/faults.py --workload <cell> --fault <name> \\
        --seeds 1 2 3 --seconds 20

Each fault wraps `InterfaceSession.run_batched`, the entry every cell's
window drives (the offline drivers call it, `ServeEngine` steps its groups
through it), and breaks what it hands back.  For each seed the command
runs the cell once as ``run.py`` does, with the fault planted, and prints
the numbers compared beside their limits, one JSON line a seed: the check
has to refuse every line.  The benchmark's own runs never plant a fault;
``tests/test_chipbench_control.py`` plants each one in a tiny run.
"""

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def halve_the_batch(out, kw):
    """Half of the lanes left out: the rest's results stand in for them."""
    cur, st = out
    half = cur.shape[0] // 2
    idx = np.arange(cur.shape[0]) % half
    return cur[idx], type(st)(*(f[idx] for f in st))


def alter_one_answer(out, kw):
    """One current of every lane, and the events of lane 0, altered."""
    cur, st = out
    return cur.at[:, 0, 0, 0].add(1.0), st._replace(
        events=st.events.at[0].add(1.0))


def state_unchanged(out, kw):
    """The step hands back the state it was given (zeros when none)."""
    cur, st = out
    if kw.get("stats0") is not None:
        return cur, kw["stats0"]
    return cur, type(st)(*(f * 0 for f in st))


FAULTS = {"halve_the_batch": halve_the_batch,
          "alter_one_answer": alter_one_answer,
          "state_unchanged": state_unchanged}


@contextlib.contextmanager
def planted(fault: str):
    """`InterfaceSession.run_batched` broken by ``fault`` inside the body."""
    from repro.interface.session import InterfaceSession

    original, broken_by = InterfaceSession.run_batched, FAULTS[fault]

    def broken(self, spikes, *args, **kw):
        return broken_by(original(self, spikes, *args, **kw), kw)

    InterfaceSession.run_batched = broken
    try:
        yield
    finally:
        InterfaceSession.run_batched = original


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]
    from chip import harness

    cell = harness.load_cell(args.workload)
    try:
        counter = harness.start(cell)
    except harness.Refused as why:
        print(f"faults: {why}", file=sys.stderr)
        return 2
    device = harness.device_info(cell.chips)
    for seed in args.seeds:
        with planted(args.fault):
            record = harness.drive(cell, seed, args.seconds, False,
                                   time.perf_counter(), counter=counter)
        out = harness.result(cell, record, False, device)
        print(json.dumps({"workload": cell.name, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
