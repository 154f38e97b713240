"""Offline batched sweeps: `InterfaceSession.run_batched`, back to back.

Set-up makes the wiring and ``batches`` raster batches of ``lanes x
ticks`` ticks on the device from the seed, compiles the session and runs
every batch once (the one program of the call compiles there).  The
window then calls ``run_batched`` on the batches in turn, each call ended
by ``block_until_ready``, until ``seconds`` have passed; the last call
finishes past the window and the rate is taken over all calls and all
their time.

A mix may set ``"shard"`` (``"chips"``): set-up then places each batch
where the program says the sharded call reads it
(`InterfaceSession.state_sharding`), so that the window times the scan
and no copy between devices, and every call passes ``shard=``.  Without
the key the calls are the plain ``run_batched(batch)``.

The check compares the accumulated `StepStats` of every lane of every call
with the reference, and the currents of ``sample_lanes`` lanes drawn from
the seed, in the first call and in one more drawn from the seed among the
first ``sample_calls_from`` (the window's later calls repeat these
batches).
"""

from __future__ import annotations

import time

import numpy as np

from chip import compare, data, harness, work
from chip.drivers import common
from chip.reference import Reference, accumulate


def shrink(mix: dict) -> None:
    """The mix, in place, at a size a CPU test run can hold."""
    mix.update(lanes=4, ticks=16)


def sample_of(mix, seed):
    """``(calls kept, lanes sampled)``: where the currents are compared."""
    rng = np.random.default_rng(seed)
    keep = {0, int(rng.integers(1, mix["sample_calls_from"]))}
    sample = np.sort(rng.choice(mix["lanes"], mix["sample_lanes"],
                                replace=False))
    return keep, sample


def expected(config, conn, hosts, sample, control=False):
    """The reference's answers for host copies of the batches.

    Returns ``(rows, currents)``: per batch, the (lanes, fields)
    accumulated stats of every lane, and the currents of the ``sample``
    lanes.  ``control`` computes them in bfloat16 instead.
    """
    ref = Reference(config, conn)
    rows, cur = [], []
    for host in hosts:
        lanes, ticks = host.shape[:2]
        per_tick = ref.tick_stats(host.reshape(lanes * ticks, -1))
        rows.append(accumulate(per_tick.reshape(lanes, ticks, -1),
                               bf16_control=control))
        cur.append([ref.currents(host[lane], bf16_control=control)
                    for lane in sample])
    return rows, cur


def control_readings(cell, seed, seconds):
    """The numbers the check compares, with the reference in bfloat16 in
    the program's place, on the batches and lanes a run of ``seed`` makes
    (``seconds`` does not change them)."""
    import jax

    mix, config = cell.mix, cell.config
    _, conn = data.connectivity(data.seed_key(seed, 0), config)
    make = data.raster_fn(mix["generator"], mix["params"], mix["ticks"],
                          config["fabric"])
    hosts = [np.asarray(make(jax.random.split(data.seed_key(seed, 1 + b),
                                              mix["lanes"])))
             for b in range(mix["batches"])]
    _, sample = sample_of(mix, seed)
    ref_rows, ref_cur = expected(config, conn, hosts, sample)
    ctl_rows, ctl_cur = expected(config, conn, hosts, sample, control=True)
    out = compare.stats_gaps(np.concatenate(ctl_rows),
                             np.concatenate(ref_rows))
    out["currents"] = max(compare.currents_gap(c, r)
                          for cs, rs in zip(ctl_cur, ref_cur)
                          for c, r in zip(cs, rs))
    return out


def placed(session, shard, batches):
    """``(run_batched keywords, batches, what the session took)``.

    With no ``shard`` the batches stay as made and no keyword is passed.
    """
    import jax

    if shard is None:
        return {}, batches, "unsharded"
    where = session.state_sharding(shard)
    batches = [jax.device_put(b, where) for b in batches]
    mesh = session.chip_mesh
    took = (f"mesh of {mesh.devices.size} devices" if mesh is not None
            else "vmap fallback" if session.config.chips > 1
            else "flat (one simulated chip)")
    return {"shard": shard}, batches, f"shard {shard}: {took}"


def run(cell, seed, seconds, t0, counter=None, trace_dir=None):
    import jax
    import jax.numpy as jnp

    from repro.interface import Interface

    mix, config = cell.mix, cell.config
    fab = config["fabric"]
    lanes, ticks, k = mix["lanes"], mix["ticks"], mix["batches"]

    params, conn = data.connectivity(data.seed_key(seed, 0), config)
    session = Interface(common.program_config(config)).compile(
        common.interface_params(params))
    make = data.raster_fn(mix["generator"], mix["params"], ticks, fab)
    batches = [make(jax.random.split(data.seed_key(seed, 1 + b), lanes))
               for b in range(k)]
    count = jax.jit(lambda b: jnp.sum(b, dtype=jnp.int32))
    events = [int(count(b)) for b in batches]
    kw, batches, mode = placed(session, mix.get("shard"), batches)

    for batch in batches:
        jax.block_until_ready(session.run_batched(batch, **kw))
    keep, sample = sample_of(mix, seed)
    setup_s = harness.elapsed(t0)

    length = min(seconds, mix["trace_seconds"]) if trace_dir else seconds
    requests0 = counter.requests if counter else 0
    stats, kept, calls = [], {}, 0
    with common.traced(trace_dir):
        start = time.perf_counter()
        while True:
            b = calls % k
            with jax.profiler.TraceAnnotation("bench.call"):
                out = jax.block_until_ready(
                    session.run_batched(batches[b], **kw))
            stats.append((b, out[1]))
            if calls in keep:
                kept[calls] = (b, out[0])
            del out
            calls += 1
            if time.perf_counter() - start >= length:
                break
        wall = time.perf_counter() - start
    compiles = (counter.requests - requests0) if counter else 0
    peak = harness.memory_peak(cell.chips)
    done = sum(events[i % k] for i in range(calls))

    # ---- the check, after the window: the reference on the host ----------
    check_t0 = time.perf_counter()
    hosts = [np.asarray(batch) for batch in batches]
    del batches
    ref_rows, ref_cur = expected(config, conn, hosts, sample)
    program = np.concatenate([compare.stats_rows(s) for _, s in stats])
    expect = np.concatenate([ref_rows[b] for b, _ in stats])
    readings = compare.stats_gaps(program, expect)
    readings["currents"] = max(
        compare.currents_gap(np.asarray(cur[int(lane)]), ref_cur[b][i])
        for b, cur in kept.values() for i, lane in enumerate(sample))

    notes = [f"impl {session.config.impl}; {mode}; {calls} calls of "
             f"{lanes} lanes x {ticks} ticks in {wall:.3f} s; {done} events; "
             f"{compiles} compiles in the window; currents compared on "
             f"calls {sorted(kept)} lanes {sample.tolist()}",
             f"the check against the reference took "
             f"{time.perf_counter() - check_t0:.3f} s"]
    e2e = {"events_per_s": done / wall, "setup_s": setup_s}
    traced = {"lane_ticks": calls * lanes * ticks, "events": done,
              "work": work.tick_work(config, lanes, ticks, done, calls),
              "device_kind": jax.devices()[0].device_kind,
              "chips": cell.chips}
    return harness.Record(setup_s=setup_s, e2e=e2e, attempted=calls,
                          failed=0, readings=readings,
                          memory_peak_bytes=peak, notes=notes, traced=traced)
