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
    import jax
    import numpy as np

    from chip import compare, data
    from chip.drivers import fleet, offline

    mix, config = cell.mix, cell.config
    fab = config["fabric"]
    _, conn = data.connectivity(data.seed_key(seed, 0), config)
    if mix["driver"] == "offline":
        make = data.raster_fn(mix["generator"], mix["params"], mix["ticks"],
                              fab)
        hosts = [np.asarray(make(jax.random.split(
            data.seed_key(seed, 1 + b), mix["lanes"])))
            for b in range(mix["batches"])]
        rng = np.random.default_rng(seed)
        rng.integers(1, mix["sample_calls_from"])
        sample = np.sort(rng.choice(mix["lanes"], mix["sample_lanes"],
                                    replace=False))
        ref_rows, ref_cur = offline.expected(config, conn, hosts, sample)
        ctl_rows, ctl_cur = offline.expected(config, conn, hosts, sample,
                                             control=True)
        out = compare.stats_gaps(np.concatenate(ctl_rows),
                                 np.concatenate(ref_rows))
        out["currents"] = max(compare.currents_gap(c, r)
                              for cs, rs in zip(ctl_cur, ref_cur)
                              for c, r in zip(cs, rs))
        return out
    make = data.raster_fn(mix["generator"], mix["params"],
                          mix["request_ticks"], fab)
    pool = np.asarray(make(jax.random.split(data.seed_key(seed, 1),
                                            mix["pool"])))
    picks = [list(p) for p in zip(*fleet.warmup_picks(mix))]
    rng = np.random.default_rng(seed)
    _, tenant, pick = fleet.plan(rng, mix["rate_per_s"], seconds,
                                 mix["tenants"], mix["pool"])
    for t, p in zip(tenant, pick):
        picks[t].append(int(p))
    ref = fleet.tenant_totals(config, conn, pool, picks)
    ctl = fleet.tenant_totals(config, conn, pool, picks, control=True)
    out = compare.stats_gaps(ctl, ref)
    out["unserved"] = 0.0
    return out


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
