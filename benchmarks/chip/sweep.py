#!/usr/bin/env python3
"""Find the highest request rate a fleet cell sustains: a sweep on the chip.

    python3 benchmarks/chip/sweep.py --workload fleet_paper --seed 1 \\
        --seconds 8 --rates 500 1000 1500 2000

One set-up, then one open-loop window at each rate in turn (each waits for
its last request).  Prints one JSON line a rate: requests, latency
percentiles, the requests still open when the window closed (a backlog
that grows with the window), the served event rate and how late the
generator ran; then the check's readings over everything served.  The cell
runs at a fixed rate below the knee this finds; the benchmark's own runs
never sweep.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]
    from chip import harness

    cell = harness.load_cell(args.workload)
    try:
        harness.start(cell)
    except harness.Refused as why:
        print(f"sweep: {why}", file=sys.stderr)
        return 2
    import numpy as np

    from chip.drivers.fleet import Fleet

    fleet = Fleet(cell, args.seed)
    print(f"sweep: set-up {harness.elapsed(t0):.3f} s", flush=True)
    for rate in args.rates:
        win = fleet.window(rate, args.seconds)
        lat = win["latency_s"] * 1e3
        pct = {f"p{q}_ms": float(np.percentile(lat, q)) if len(lat) else None
               for q in (50, 95, 99)}
        print(json.dumps({
            "rate_per_s": rate, "requests": win["requests"],
            "failed": win["failed"], **pct,
            "max_ms": float(lat.max()) if len(lat) else None,
            "open_at_close": win["open_at_close"],
            "served_events_per_s": win["served_events"] / win["window_s"],
            "lag_p95_ms": float(np.percentile(win["lag_s"], 95) * 1e3),
            "wall_s": win["wall_s"]}), flush=True)
    print(json.dumps({"readings": fleet.check()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
