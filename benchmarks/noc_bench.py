"""NoC benchmark: broadcast vs. unicast-mesh vs. multicast-tree, random
vs. optimized neuron placement, old-API vs. session-API wall clock, the
event-driven session tick vs. the dense-sweep oracle, and the multi-chip
hierarchy sweep.

    PYTHONPATH=src python benchmarks/noc_bench.py [--cores 4,16,64] [--ticks 16]
        [--tick-cores 16] [--tick-neurons 256] [--chips 1,2,4]
        [--json [BENCH_interface.json]] [--trace obs_trace.json]

Sweeps:

1. **Transport scheme** (fixed random connectivity, fixed spikes): per-tick
   CAM searches, NoC link events (hops) and energy for the three schemes.
   Broadcast pays `events x cores` searches; the mesh schemes pay one
   search per *subscribed* core, and the multicast tree additionally
   collapses replicated link traversals into shared trunk edges.

2. **Placement** (cluster-structured connectivity, scrambled): traffic
   cost and CAM searches under identity / random / greedy hyperedge-
   overlap placement, evaluated both by the analytic objective and by
   stepping the re-placed fabric through an `InterfaceSession`.

3. **API wall clock**: the deprecated per-tick pattern (`fabric.step`
   jitted once, dispatched from a Python loop every tick) against
   `InterfaceSession.run` (one jit-compiled `lax.scan` over all ticks),
   so the session speedup is measured, not asserted.

4. **Session tick** (DYNAPs-scale, default 16 cores x 256 neurons/core):
   the event-driven tick (precompiled CAM routing indices + vectorized
   arbiter latency plans) against the pre-optimization oracle (dense
   tag-vs-every-source sweep + per-core discrete-event arbiter scan),
   both under the same jit + lax.scan session harness.  Currents are
   asserted bit-identical before timing.  ``--json`` writes the records
   (plus ``schema_version``, ``platform``/``jax_version`` host identity,
   the git SHA, and the full CLI config, so uploaded artifacts are
   comparable across runs) to BENCH_interface.json; CI gates on it via
   ``benchmarks/check_regression.py``.  Timed records carry streaming
   ``tick_ms_p50/p95/p99`` percentiles over the repeat wall-clocks next
   to the min-based ``new_tick_ms``, and scenario records embed
   ``stats_per_tick`` so ``python -m repro.obs.report`` can render the
   per-tier (arbiter/CAM/NoC/chip) breakdown.  ``--trace PATH`` writes a
   Chrome-trace JSON (open in Perfetto / chrome://tracing) of the
   compile / device-transfer / run / block-until-ready spans.

4b. **Sparsity** (always on, session-tick shape): the dense event path
   vs. ``impl="pallas_sparse"`` (the fused `repro.kernels.sparse_tick`
   event tick) across event rates - Bernoulli sweeps plus the
   ``sparse_poisson`` scenario.  Records land in the ``--json`` payload
   tagged ``scenario="sparsity_*"``; ``check_regression.py`` gates their
   latency against the baseline and enforces the in-run >= 3x
   sparse-vs-dense floor on the ``sparse_poisson`` point.

5. **Chip hierarchy** (``--chips``): the same total fabric partitioned
   into 1..K chips (`repro.noc.hierarchy`): chip-local vs. inter-chip
   hops/latency/energy, and the sharded session
   (``run(shard="chips")``, vmap fallback on one device) asserted
   bit-identical to the unsharded path.

6. **Traffic scenarios** (``--scenario all`` or a comma list): one
   precompiled session, every registered `repro.traffic` scenario run
   through it - per-scenario tick wall clock, events/tick, and the
   scenario's analytic expected rate.  The records carry a ``scenario``
   key in the ``--json`` payload so ``check_regression.py`` gates each
   scenario's tick latency separately.

7. **Serving** (``--serve [TENANTS]``, default 8): sustained multi-tenant
   load through `repro.serve.ServeEngine` - all tenants share ONE
   precompiled session and step as lanes of a single jitted masked
   ``run_batched``.  After a warmup round (compile paid, metrics reset),
   measured rounds record sustained ``events_per_sec`` and per-flush
   tick-latency percentiles into a ``__serve__``-tagged record;
   ``check_regression.py`` gates the latency fields normally and
   events/sec inverted (a throughput *drop* beyond threshold fails).
   One tenant's accumulated stats are asserted bit-identical to a solo
   ``session.run`` over its concatenated stream.

8. **Chaos** (``--chaos [ROUNDS]``, default 12): the serve engine under
   a seeded mixed `repro.ft` fault plan (transfer/execute failures, slow
   devices, per-tenant lane faults; one tenant additionally compiled
   with a fabric-level `FaultModel`).  Asserts graceful degradation:
   every chaos charge fires, the accounting identity closes exactly,
   every lane recovers, and the masked jit cache never grows.  Emits a
   ``__chaos__``-tagged record; ``--chaos-report PATH`` writes the JSONL
   serve report (fault counters + recovery percentiles) for
   ``python -m repro.obs.report`` and the CI artifact.

Also asserts the PR acceptance criteria: at >= 16 cores, multicast-tree +
optimized placement reduces total CAM searches and NoC link events vs. the
broadcast baseline; re-placed fabrics conserve total synaptic current; the
session path is not slower than the Python loop; and the event-driven tick
is >= 5x the oracle at 16 cores x 256 neurons/core.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import traffic
from repro.core import fabric
from repro.interface import Interface, StepStats
from repro.interface import pipeline as interface_pipeline
from repro.launch.cache import enable_compile_cache
from repro.noc import placement, topology
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

# Bump when the --json record/payload shape changes incompatibly; the
# committed baseline and check_regression.py key off the record fields,
# so readers use this plus `platform` to decide comparability.
# v3: --serve emits a "__serve__"-tagged sustained-load record carrying
# events_per_sec (gated inverted: lower is a regression).
# v4: the sparsity sweep emits "sparsity_*"-tagged records carrying
# dense_tick_ms / sparse_speedup next to the usual latency fields.
# v5: --serve additionally emits "__serve_async__" (background pump,
# carrying pump_threads + the in-run async_vs_sync throughput ratio),
# "__serve_autoscale__" (grow/shrink lane cycle) and "__serve_shard__"
# (chips=2 cross-device tenant group) records.
SCHEMA_VERSION = 5

DEFAULT_CORES = (4, 16, 64)
NEURONS = 16          # per core: kept small so the 64-core dense sweep fits
RATE = 0.2


def _spikes(cfg, seed=1, ticks=None):
    shape = (cfg.cores, cfg.neurons_per_core)
    if ticks is not None:
        shape = (ticks,) + shape
    return jax.random.bernoulli(jax.random.PRNGKey(seed), RATE, shape)


def scheme_sweep(core_sweep):
    print("== transport scheme sweep (random connectivity, rate %.2f) ==" % RATE)
    print(f"{'cores':>5} {'scheme':>14} {'events':>7} {'cam_searches':>12} "
          f"{'noc_hops':>9} {'noc_energy':>11} {'noc_latency':>11}")
    results = {}
    for cores in core_sweep:
        base = fabric.FabricConfig(cores=cores, neurons_per_core=NEURONS,
                                   cam_entries_per_core=2 * NEURONS)
        params = fabric.random_connectivity(jax.random.PRNGKey(0), base)
        sp = _spikes(base)
        cur_ref = None
        for scheme in ("broadcast", "unicast", "multicast_tree"):
            cfg = dataclasses.replace(base, noc=topology.NocConfig(scheme))
            cur, st = Interface(cfg).compile(params).step(sp)
            if cur_ref is None:
                cur_ref = cur
            assert bool(jnp.all(cur == cur_ref)), "currents must not depend on scheme"
            results[(cores, scheme)] = st
            print(f"{cores:>5} {scheme:>14} {float(st.events):>7.0f} "
                  f"{float(st.cam_searches):>12.0f} {float(st.noc_hops):>9.0f} "
                  f"{float(st.noc_energy):>11.0f} {float(st.noc_latency):>11.1f}")
    return results


def placement_sweep(core_sweep):
    print("\n== placement sweep (clustered connectivity, scrambled) ==")
    print(f"{'cores':>5} {'placement':>10} {'traffic_cost':>12} "
          f"{'cam_searches':>12} {'step_searches':>13} {'step_hops':>9}")
    results = {}
    for cores in core_sweep:
        cfg = fabric.FabricConfig(cores=cores, neurons_per_core=NEURONS,
                                  cam_entries_per_core=4 * NEURONS,
                                  noc=topology.NocConfig("multicast_tree"))
        params = placement.clustered_connectivity(
            0, cfg, cluster_size=NEURONS, fan_in=4)
        a = placement.fanout_adjacency(params, cfg)
        total = cores * NEURONS
        placements = {
            "identity": placement.identity_placement(total),
            "random": placement.random_placement(7, total),
            "greedy": placement.greedy_overlap_placement(a, cores, NEURONS),
        }
        sp = _spikes(cfg)
        base_current = None
        for name, perm in placements.items():
            cost = placement.traffic_cost(a, perm, cores, NEURONS)
            searches = placement.cam_search_count(a, perm, cores, NEURONS)
            p2, cfg2 = placement.apply_placement(params, cfg, perm)
            # spikes follow their neurons to the new layout
            flat = np.asarray(sp).reshape(-1)
            sp2 = np.zeros(total, dtype=bool)
            sp2[np.asarray(perm)] = flat
            cur2, st2 = Interface(cfg2).compile(p2).step(
                jnp.asarray(sp2.reshape(cores, NEURONS)))
            tot = float(jnp.sum(cur2))
            if base_current is None:
                base_current = tot
            assert abs(tot - base_current) < 1e-3 * max(1.0, abs(base_current)), \
                "placement must conserve total synaptic current"
            results[(cores, name)] = (cost, searches, st2)
            print(f"{cores:>5} {name:>10} {cost:>12.0f} {searches:>12.0f} "
                  f"{float(st2.cam_searches):>13.0f} {float(st2.noc_hops):>9.0f}")
    return results


def api_timing_sweep(core_sweep, ticks, repeats=3):
    """Deprecated per-tick Python loop vs. session scan, wall-clock."""
    print(f"\n== API wall clock ({ticks} ticks, best of {repeats}) ==")
    print(f"{'cores':>5} {'old_loop_ms':>12} {'session_ms':>11} {'speedup':>8}")
    results = {}
    for cores in core_sweep:
        gc.collect()
        cfg = fabric.FabricConfig(cores=cores, neurons_per_core=NEURONS,
                                  cam_entries_per_core=2 * NEURONS)
        params = fabric.random_connectivity(jax.random.PRNGKey(0), cfg)
        sp_t = _spikes(cfg, ticks=ticks)

        # --- old API: per-tick jit, dispatched from a Python loop ----------
        tables = fabric.noc_tables(params, cfg)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            step_fn = jax.jit(lambda p, s: fabric.step(p, s, cfg, tables))

            def old_loop():
                acc = None
                for t in range(ticks):
                    cur, st = step_fn(params, sp_t[t])
                    acc = st if acc is None else jax.tree.map(jnp.add, acc, st)
                jax.block_until_ready((cur, acc))
                return cur, acc

            old_cur, old_acc = old_loop()                      # compile
            t_old = min(_timed(old_loop) for _ in range(repeats))

        # --- session API: one lax.scan, tables/plans prebuilt --------------
        session = Interface(cfg).compile(params)

        def session_run():
            out = session.run(sp_t)
            jax.block_until_ready(out)
            return out

        new_cur, new_acc = session_run()                       # compile
        t_new = min(_timed(session_run) for _ in range(repeats))

        assert bool(jnp.all(old_cur == new_cur[-1])), \
            "session currents must match the per-tick loop"
        assert abs(float(old_acc.events) - float(new_acc.events)) < 1e-3

        speedup = t_old / max(t_new, 1e-9)
        results[cores] = (t_old, t_new, speedup)
        print(f"{cores:>5} {t_old * 1e3:>12.2f} {t_new * 1e3:>11.2f} "
              f"{speedup:>7.1f}x")
    return results


def tick_sweep(core_sweep, neurons, entries, ticks, repeats=3):
    """Event-driven session tick vs. the dense-sweep + DES oracle."""
    print(f"\n== session tick: event-driven vs dense oracle "
          f"({neurons} neurons/core, {entries} CAM entries, {ticks} ticks, "
          f"best of {repeats}) ==")
    print(f"{'cores':>5} {'oracle_tick_ms':>15} {'fast_tick_ms':>13} "
          f"{'speedup':>8} {'identical':>9}")
    records = []
    for cores in core_sweep:
        gc.collect()
        cfg = fabric.FabricConfig(cores=cores, neurons_per_core=neurons,
                                  cam_entries_per_core=entries)
        params = fabric.random_connectivity(jax.random.PRNGKey(0), cfg)
        with obs_trace.span("tick_sweep.device_transfer", cores=cores):
            sp = jax.device_put(jax.random.bernoulli(
                jax.random.PRNGKey(2), RATE, (ticks, cores, neurons)))
            jax.block_until_ready(sp)

        session = Interface(cfg).compile(params)

        def fast_run():
            with obs_trace.span("tick_sweep.run", cores=cores):
                out = session.run(sp)
            with obs_trace.span("tick_sweep.block_until_ready", cores=cores):
                jax.block_until_ready(out)
            return out

        tables, arb_plan = session.tables, session.arb_plan

        @jax.jit
        def oracle_run(p, sp_t):
            def body(acc, s_t):
                cur, st = interface_pipeline.interface_tick(
                    p, s_t, cfg, tables, arb_plan, oracle=True)
                return acc.accumulate(st), cur
            acc, cur = jax.lax.scan(body, StepStats.zeros(), sp_t)
            return cur, acc

        def slow_run():
            out = oracle_run(params, sp)
            jax.block_until_ready(out)
            return out

        cur_new, acc_new = fast_run()                          # compile
        cur_old, acc_old = slow_run()                          # compile
        identical = bool(jnp.all(cur_new == cur_old))
        assert identical, "event-driven currents drifted from the dense oracle"
        assert float(acc_new.events) == float(acc_old.events)
        assert float(acc_new.cam_searches) == float(acc_old.cam_searches)

        hist = obs_metrics.Histogram("fast_tick_ms")
        times = [_timed(fast_run) for _ in range(repeats)]
        for t in times:
            hist.add(t / ticks * 1e3)
        t_new = min(times)
        t_old = min(_timed(slow_run) for _ in range(repeats))
        speedup = t_old / max(t_new, 1e-9)
        pct = hist.summary()
        records.append({"cores": cores, "neurons_per_core": neurons,
                        "cam_entries_per_core": entries, "ticks": ticks,
                        "old_tick_ms": t_old / ticks * 1e3,
                        "new_tick_ms": t_new / ticks * 1e3,
                        "tick_ms_p50": pct["p50"],
                        "tick_ms_p95": pct["p95"],
                        "tick_ms_p99": pct["p99"],
                        "speedup": speedup,
                        "currents_bit_identical": identical})
        print(f"{cores:>5} {t_old / ticks * 1e3:>15.3f} "
              f"{t_new / ticks * 1e3:>13.3f} {speedup:>7.1f}x "
              f"{str(identical):>9}")
    return records


SPARSITY_RATES = (0.005, 0.02, 0.05, 0.1, 0.2)


def sparsity_sweep(cores, neurons, entries, ticks, repeats=3):
    """Rate-proportional sparse tick vs. the dense event path.

    Events/tick on the x-axis: Bernoulli frames at `SPARSITY_RATES` plus
    the registered ``sparse_poisson`` scenario (the headline point the
    acceptance gate reads).  Both paths run the same precompiled-session
    harness on the same spikes in the same process, so the recorded
    ``sparse_speedup`` is an in-run ratio - robust to machine-speed
    drift, unlike the absolute wall clocks.  Currents are asserted
    bit-identical before timing.  Per-rate records are tagged
    ``scenario="sparsity_*"`` in the ``--json`` payload: latency gates
    via the committed baseline as usual, and ``check_regression.py``
    additionally enforces the >= 3x sparse-vs-dense floor on the
    ``sparsity_sparse_poisson`` record at >= 16 cores x 256 n/core.

    The dense fallback is part of the sweep by construction: the highest
    rates exceed the default event capacity (n/8), so those records time
    the overflow `lax.cond` taking the dense branch.
    """
    print(f"\n== sparsity sweep: dense event path vs impl='pallas_sparse' "
          f"({cores} cores x {neurons} neurons/core, {entries} CAM entries, "
          f"{ticks} ticks, best of {repeats}) ==")
    print(f"{'point':>16} {'events/tick':>11} {'dense_ms':>9} "
          f"{'sparse_ms':>9} {'speedup':>8} {'identical':>9}")
    cfg = fabric.FabricConfig(cores=cores, neurons_per_core=neurons,
                              cam_entries_per_core=entries)
    params = fabric.random_connectivity(jax.random.PRNGKey(0), cfg)
    dense = Interface(cfg).compile(params)
    sparse = Interface(dataclasses.replace(
        cfg, impl="pallas_sparse")).compile(params)

    points = [("sparse_poisson",
               traffic.generate("sparse_poisson", 6, ticks, cfg))]
    for rate in SPARSITY_RATES:
        points.append((f"p{rate:g}", jax.random.bernoulli(
            jax.random.PRNGKey(int(rate * 1e4)), rate,
            (ticks, cores, neurons))))

    records = []
    for name, sp in points:
        gc.collect()

        def dense_run():
            out = dense.run(sp)
            jax.block_until_ready(out)
            return out

        def sparse_run():
            with obs_trace.span("sparsity.run", point=name):
                out = sparse.run(sp)
            jax.block_until_ready(out)
            return out

        cur_d, acc_d = dense_run()                             # compile
        cur_s, acc_s = sparse_run()                            # compile
        identical = bool(jnp.all(cur_d == cur_s))
        assert identical, \
            f"sparse currents drifted from the dense event path at {name}"
        assert float(acc_d.events) == float(acc_s.events)

        hist = obs_metrics.Histogram("sparse_tick_ms")
        times_s = [_timed(sparse_run) for _ in range(repeats)]
        for t in times_s:
            hist.add(t / ticks * 1e3)
        t_sparse = min(times_s)
        t_dense = min(_timed(dense_run) for _ in range(repeats))
        speedup = t_dense / max(t_sparse, 1e-9)
        pct = hist.summary()
        rec = {"scenario": f"sparsity_{name}", "cores": cores,
               "neurons_per_core": neurons,
               "cam_entries_per_core": entries, "ticks": ticks,
               "events_per_tick": float(acc_s.events) / ticks,
               "dense_tick_ms": t_dense / ticks * 1e3,
               "new_tick_ms": t_sparse / ticks * 1e3,
               "tick_ms_p50": pct["p50"],
               "tick_ms_p95": pct["p95"],
               "tick_ms_p99": pct["p99"],
               "sparse_speedup": speedup,
               "currents_bit_identical": identical}
        records.append(rec)
        print(f"{name:>16} {rec['events_per_tick']:>11.1f} "
              f"{rec['dense_tick_ms']:>9.3f} {rec['new_tick_ms']:>9.3f} "
              f"{speedup:>7.2f}x {str(identical):>9}")
    return records


def scenario_sweep(names, cores, neurons, entries, ticks, repeats=3):
    """Per-scenario session tick wall clock on one precompiled session."""
    print(f"\n== traffic scenario sweep ({cores} cores x {neurons} "
          f"neurons/core, {entries} CAM entries, {ticks} ticks, best of "
          f"{repeats}) ==")
    print(f"{'scenario':>19} {'exp_rate':>8} {'events/tick':>11} "
          f"{'tick_ms':>8} {'enc_lat/tick':>12}")
    cfg = fabric.FabricConfig(cores=cores, neurons_per_core=neurons,
                              cam_entries_per_core=entries)
    params = fabric.random_connectivity(jax.random.PRNGKey(0), cfg)
    session = Interface(cfg).compile(params)
    records = []
    for name in names:
        gc.collect()
        with obs_trace.span("scenario.generate", scenario=name):
            sp = traffic.generate(name, 4, ticks, cfg)

        def run():
            with obs_trace.span("scenario.run", scenario=name):
                out = session.run(sp)
            with obs_trace.span("scenario.block_until_ready", scenario=name):
                jax.block_until_ready(out)
            return out

        _, acc = run()                                         # compile/warm
        hist = obs_metrics.Histogram("scenario_tick_ms")
        times = [_timed(run) for _ in range(repeats)]
        for t in times:
            hist.add(t / ticks * 1e3)
        t = min(times)
        pct = hist.summary()
        rate = traffic.expected_rate(name, cores, neurons)
        rec = {"scenario": name, "cores": cores,
               "neurons_per_core": neurons,
               "cam_entries_per_core": entries, "ticks": ticks,
               "new_tick_ms": t / ticks * 1e3,
               "tick_ms_p50": pct["p50"],
               "tick_ms_p95": pct["p95"],
               "tick_ms_p99": pct["p99"],
               "expected_rate": rate,
               "events_per_tick": float(acc.events) / ticks,
               "encode_latency_per_tick": float(acc.encode_latency) / ticks,
               # per-tick-mean StepStats: the per-tier (arbiter/CAM/NoC/
               # chip) breakdown `python -m repro.obs.report` renders
               "stats_per_tick": acc.summary(ticks=ticks)}
        records.append(rec)
        print(f"{name:>19} {rate:>8.3f} {rec['events_per_tick']:>11.1f} "
              f"{rec['new_tick_ms']:>8.3f} "
              f"{rec['encode_latency_per_tick']:>12.1f}")
    return records


def serve_sweep(tenants, cores, neurons, entries, ticks, repeats=3,
                pump_threads=1):
    """Sustained multi-tenant load through the serving engine.

    Registers ``tenants`` specs (same fabric config, mixed scenarios) on
    one `ServeEngine` - they land on ONE shared precompiled session and
    step as lanes of a single jitted masked ``run_batched``.  One
    warmup round pays compilation, metrics reset, then ``repeats``
    rounds of submit+drain measure sustained events/sec and the
    per-flush tick-latency percentiles.  One tenant's accumulated
    `StepStats` are asserted bit-identical to a solo ``session.run``
    over its full concatenated stream, so the batched serve path is
    held to the same contract the conformance grid checks.

    Schema v5 adds three records after the baseline ``__serve__`` one:

    * ``__serve_async__`` - the same fleet drained by the background
      pump (`engine.start`, ``pump_threads`` threads).  Carries the
      in-run ``async_vs_sync`` events/sec ratio that
      check_regression.py floors, so the async path may never fall
      meaningfully behind the synchronous drain it replaced.
    * ``__serve_autoscale__`` - a grow/shrink lane-capacity cycle
      (register, serve, register, serve, deregister, serve) asserting
      the surviving tenant's stats stay bit-identical to a solo run
      and the ledger closes at every step.
    * ``__serve_shard__`` - a ``shard="chips"`` tenant group on a
      chips=2 config, asserted bit-identical to the flat solo session.
    """
    from repro.serve import ServeEngine, TenantSpec, default_connectivity

    print(f"\n== serve sweep ({tenants} tenants on one session, {cores} "
          f"cores x {neurons} neurons/core, {entries} CAM entries, "
          f"{ticks} ticks/round x {repeats} rounds) ==")
    cfg = fabric.FabricConfig(cores=cores, neurons_per_core=neurons,
                              cam_entries_per_core=entries)
    names = traffic.scenario_names()
    engine = ServeEngine(flush_ticks=ticks, flush_deadline_s=0.0)
    specs = [TenantSpec(f"tenant{i}", cfg, scenario=names[i % len(names)],
                        seed=i)
             for i in range(tenants)]
    for spec in specs:
        engine.register(spec)
    assert len(engine.groups) == 1, \
        "compatible tenants must share one precompiled session"

    for spec in specs:                                     # warmup: compile
        engine.submit_scenario(spec.name, ticks)
    engine.drain()
    warm_rounds = 1
    engine.reset_metrics()

    for _ in range(repeats):
        for spec in specs:
            engine.submit_scenario(spec.name, ticks)
        engine.drain()

    # serve-path contract: one tenant's accumulated stats must be bit-
    # identical to a solo run over its full (warmup + measured) stream
    probe = specs[0]
    stream = jnp.concatenate([probe.stream(ticks, round=r)
                              for r in range(warm_rounds + repeats)])
    _, acc_solo = Interface(cfg).compile(
        default_connectivity(cfg, probe.connectivity_seed)).run(stream)
    acc_srv = engine.tenant_stats(probe.name)
    identical = all(float(a) == float(np.asarray(b))
                    for a, b in zip(acc_solo, acc_srv))
    assert identical, "serve-path stats drifted from the solo session run"

    report = engine.serve_report()
    fleet = report[-1]
    served = engine.ticks_served()
    # key on ticks-per-round (like every other sweep) so the baseline
    # stays matchable when --tick-repeats changes; served total is data
    rec = {"scenario": "__serve__", "cores": cores,
           "neurons_per_core": neurons, "cam_entries_per_core": entries,
           "ticks": ticks, "ticks_served": served,
           "tenants": tenants, "groups": len(engine.groups),
           "flush_ticks": ticks,
           # mean serve-step wall clock per live tick: the headline the
           # regression gate compares, next to the streaming percentiles
           "new_tick_ms": fleet["busy_s"] / max(served, 1) * 1e3,
           "tick_ms_p50": fleet["tick_ms_p50"],
           "tick_ms_p95": fleet["tick_ms_p95"],
           "tick_ms_p99": fleet["tick_ms_p99"],
           "events_per_sec": fleet["events_per_sec"],
           "events_per_tick": fleet["events"] / max(served, 1),
           "serve_bit_identical": identical}
    print(f"{'tenants':>7} {'ticks':>6} {'events/s':>10} {'tick_ms':>8} "
          f"{'p50':>7} {'p99':>7} {'identical':>9}")
    print(f"{tenants:>7} {served:>6} {rec['events_per_sec']:>10.0f} "
          f"{rec['new_tick_ms']:>8.3f} {rec['tick_ms_p50']:>7.3f} "
          f"{rec['tick_ms_p99']:>7.3f} {str(identical):>9}")
    records = [rec]
    key = {"cores": cores, "neurons_per_core": neurons,
           "cam_entries_per_core": entries, "ticks": ticks}

    # ---- async phase: same fleet, drained by the background pump --------
    eng2 = ServeEngine(flush_ticks=ticks, flush_deadline_s=0.0)
    for spec in specs:
        eng2.register(spec)
    for spec in specs:                                     # warmup: compile
        eng2.submit_scenario(spec.name, ticks)
    eng2.drain()
    eng2.reset_metrics()
    # enqueue every round BEFORE the pump starts: the sync baseline drains
    # a full queue, so the async ratio must measure the pump against the
    # same fully-packed chunks, not against half-empty eager flushes
    for _ in range(repeats):
        for spec in specs:
            eng2.submit_scenario(spec.name, ticks)
    eng2.start(poll_interval_s=1e-4, threads=pump_threads)
    deadline = time.monotonic() + 600.0
    while (eng2.queue_depth()
           or any(g.backlog_ticks() for g in eng2.groups.values())):
        if time.monotonic() > deadline:
            raise RuntimeError("background pump failed to drain the fleet")
        time.sleep(0.002)
    eng2.stop()
    assert eng2.pump_errors() == [], eng2.pump_errors()
    acct = eng2.accounting()
    assert acct["closes"], f"async serve accounting violation: {acct}"
    acc_async = eng2.tenant_stats(probe.name)
    identical_async = all(float(a) == float(np.asarray(b))
                          for a, b in zip(acc_solo, acc_async))
    assert identical_async, \
        "async serve-path stats drifted from the solo session run"
    fleet2 = eng2.serve_report()[-1]
    served2 = eng2.ticks_served()
    rec_async = {"scenario": "__serve_async__", **key,
                 "ticks_served": served2, "tenants": tenants,
                 "pump_threads": pump_threads,
                 "new_tick_ms": fleet2["busy_s"] / max(served2, 1) * 1e3,
                 "tick_ms_p50": fleet2["tick_ms_p50"],
                 "tick_ms_p95": fleet2["tick_ms_p95"],
                 "tick_ms_p99": fleet2["tick_ms_p99"],
                 "events_per_sec": fleet2["events_per_sec"],
                 # in-run ratio: both sides timed in this process, so the
                 # gate can floor it even on a platform mismatch
                 "async_vs_sync": fleet2["events_per_sec"]
                 / max(rec["events_per_sec"], 1e-12),
                 "serve_bit_identical": identical_async}
    records.append(rec_async)
    print(f"  async pump ({pump_threads} thread(s)): "
          f"{rec_async['events_per_sec']:.0f} events/s "
          f"({rec_async['async_vs_sync']:.2f}x sync), identical="
          f"{identical_async}")

    # ---- autoscale phase: grow/shrink lane-capacity cycle ---------------
    eng3 = ServeEngine(flush_ticks=ticks, flush_deadline_s=0.0)
    t0 = TenantSpec("scale0", cfg, scenario=names[0], seed=101)
    t1 = TenantSpec("scale1", cfg, scenario=names[1 % len(names)], seed=102)
    eng3.register(t0)                                      # capacity 1
    eng3.submit_scenario("scale0", ticks)
    eng3.drain()
    assert eng3.accounting()["closes"]
    eng3.register(t1)                                      # grow -> 2
    eng3.submit_scenario("scale0", ticks)
    eng3.submit_scenario("scale1", ticks)
    eng3.drain()
    assert eng3.accounting()["closes"]
    eng3.deregister("scale1")                              # shrink -> 1
    eng3.submit_scenario("scale0", ticks)
    eng3.drain()
    assert eng3.accounting()["closes"]
    group3 = next(iter(eng3.groups.values()))
    stream3 = jnp.concatenate([t0.stream(ticks, round=r) for r in range(3)])
    _, acc3_solo = Interface(cfg).compile(
        default_connectivity(cfg, t0.connectivity_seed)).run(stream3)
    acc3 = eng3.tenant_stats("scale0")
    identical_scale = all(float(a) == float(np.asarray(b))
                          for a, b in zip(acc3_solo, acc3))
    assert identical_scale, \
        "autoscale grow/shrink cycle drifted from the solo session run"
    fleet3 = eng3.serve_report()[-1]
    served3 = eng3.ticks_served()
    faults3 = fleet3.get("faults", {})
    rec_scale = {"scenario": "__serve_autoscale__", **key,
                 "ticks_served": served3,
                 "new_tick_ms": fleet3["busy_s"] / max(served3, 1) * 1e3,
                 "capacities_seen": sorted(group3.capacities_seen),
                 "autoscale_grow": faults3.get("autoscale_grow", 0),
                 "autoscale_shrink": faults3.get("autoscale_shrink", 0),
                 "jit_cache_entries": group3.jit_cache_entries(),
                 "serve_bit_identical": identical_scale}
    records.append(rec_scale)
    print(f"  autoscale cycle: capacities {rec_scale['capacities_seen']}, "
          f"grow={rec_scale['autoscale_grow']} "
          f"shrink={rec_scale['autoscale_shrink']}, identical="
          f"{identical_scale}")

    # ---- shard phase: cross-device tenant group (chips=2) ---------------
    chips = 2
    assert cores % chips == 0, \
        f"--scenario-cores must be divisible by {chips} for the shard phase"
    cfg_s = dataclasses.replace(cfg, chips=chips)
    eng4 = ServeEngine(flush_ticks=ticks, flush_deadline_s=0.0)
    s0 = TenantSpec("shard0", cfg_s, scenario=names[0], seed=201,
                    shard="chips")
    s1 = TenantSpec("shard1", cfg_s, scenario=names[1 % len(names)],
                    seed=202, shard="chips")
    eng4.register(s0)
    eng4.register(s1)
    assert len(eng4.groups) == 1, \
        "shard-compatible tenants must share one group"
    eng4.submit_scenario("shard0", ticks)
    eng4.submit_scenario("shard1", ticks)
    eng4.drain()
    assert eng4.accounting()["closes"]
    group4 = next(iter(eng4.groups.values()))
    stream4 = s0.stream(ticks, round=0)
    _, acc4_solo = Interface(cfg_s).compile(
        default_connectivity(cfg_s, s0.connectivity_seed)).run(stream4)
    acc4 = eng4.tenant_stats("shard0")
    identical_shard = all(float(a) == float(np.asarray(b))
                          for a, b in zip(acc4_solo, acc4))
    assert identical_shard, \
        "sharded serve-path stats drifted from the flat solo session run"
    fleet4 = eng4.serve_report()[-1]
    served4 = eng4.ticks_served()
    rec_shard = {"scenario": "__serve_shard__", **key,
                 "ticks_served": served4, "chips": chips,
                 "new_tick_ms": fleet4["busy_s"] / max(served4, 1) * 1e3,
                 "groups": len(eng4.groups),
                 "jit_cache_entries": group4.jit_cache_entries(),
                 "serve_bit_identical": identical_shard}
    records.append(rec_shard)
    print(f"  shard group (chips={chips}): jit entries "
          f"{rec_shard['jit_cache_entries']}, identical={identical_shard}")
    return records


def chaos_sweep(rounds, cores, neurons, entries, ticks, report_path=None):
    """Serve engine under a seeded mixed fault plan (``--chaos``).

    Builds a small fleet (6 tenants, the last carrying a fabric-level
    `repro.ft.FaultModel`, so two groups share the engine), arms
    `FaultPlan.mixed` over ``rounds`` pump rounds through a
    `ChaosInjector` (no-op sleeps: the plan is about determinism, not
    wall time), and drives submit+pump to exhaustion.  Asserts the
    engine degrades gracefully and recovers every time: every chaos
    charge fires, the accounting identity submitted == served + shed +
    pending closes exactly, every lane ends healthy after the drain,
    and each group's masked batched jit holds ONE cache entry.  Emits a
    ``__chaos__``-tagged record (sweep keys + wall clock so
    check_regression.py can index it; candidate-only records report as
    "new", the fault path is never latency-gated) and, with
    ``--chaos-report PATH``, the JSONL serve report for
    ``python -m repro.obs.report`` / the CI artifact.
    """
    from repro.ft import ChaosInjector, FaultModel, FaultPlan, \
        RetriesExhaustedError
    from repro.serve import HealthPolicy, RetryPolicy, ServeEngine, \
        TenantSpec

    tenants = 6
    print(f"\n== chaos sweep ({rounds} rounds, {tenants} tenants, {cores} "
          f"cores x {neurons} neurons/core, {entries} CAM entries, "
          f"{ticks} ticks/round) ==")
    cfg = fabric.FabricConfig(cores=cores, neurons_per_core=neurons,
                              cam_entries_per_core=entries)
    names = traffic.scenario_names()
    specs = []
    for i in range(tenants):
        fault = FaultModel(drop_rate=0.05, seed=3) \
            if i == tenants - 1 else None
        specs.append(TenantSpec(f"chaos{i}", cfg,
                                scenario=names[i % len(names)], seed=i,
                                fault=fault))
    plan = FaultPlan.mixed([s.name for s in specs], rounds=rounds, seed=0)
    injector = ChaosInjector(plan, sleep=lambda s: None)
    sink = obs_metrics.JsonlSink(report_path) if report_path else None
    engine = ServeEngine(flush_ticks=ticks, flush_deadline_s=0.0,
                         chaos=injector,
                         retry=RetryPolicy(max_retries=3,
                                           backoff_base_s=0.0),
                         health=HealthPolicy(quarantine_after=2,
                                             quarantine_rounds=2),
                         sink=sink, sleep=lambda s: None)
    for spec in specs:
        engine.register(spec)

    hard_failures = 0
    for _ in range(rounds):
        for spec in specs:
            engine.submit_scenario(spec.name, ticks)
        try:
            engine.pump(force=True)
        except RetriesExhaustedError:
            hard_failures += 1          # restaged; a later pump serves it
    while True:                         # drain through any leftover charges
        try:
            engine.drain()
            break
        except RetriesExhaustedError:
            hard_failures += 1

    report = engine.emit_report()
    if sink is not None:
        sink.close()
        print(f"  wrote {report_path} ({len(report)} serve records)")
    fleet = report[-1]
    acct = engine.accounting()
    recovered = all(engine.lane_health(s.name) == "healthy" for s in specs)
    cache_entries = max(
        g.session._masked_cache["run_batched"]._cache_size()
        for g in engine.groups.values() if g.session._masked_cache)
    served = engine.ticks_served()
    rec = {"scenario": "__chaos__", "cores": cores,
           "neurons_per_core": neurons, "cam_entries_per_core": entries,
           "ticks": ticks, "rounds": rounds, "tenants": tenants,
           "groups": len(engine.groups), "ticks_served": served,
           "ticks_submitted": engine.ticks_submitted(),
           "hard_failures": hard_failures,
           "new_tick_ms": fleet["busy_s"] / max(served, 1) * 1e3,
           "tick_ms_p50": fleet.get("tick_ms_p50", 0.0),
           "tick_ms_p95": fleet.get("tick_ms_p95", 0.0),
           "tick_ms_p99": fleet.get("tick_ms_p99", 0.0),
           "faults": fleet.get("faults", {}),
           "plan_exhausted": injector.exhausted(),
           "accounting_closes": acct["closes"],
           "lanes_recovered": recovered,
           "jit_cache_entries": cache_entries}
    for k in ("recovery_ms_p50", "recovery_ms_p99"):
        if k in fleet:
            rec[k] = fleet[k]
    print(f"{'rounds':>6} {'served':>7} {'injected':>8} {'retries':>7} "
          f"{'hard':>4} {'closes':>6} {'recovered':>9} {'cache':>5}")
    faults = rec["faults"]
    print(f"{rounds:>6} {served:>7} {faults.get('injected', 0):>8} "
          f"{faults.get('retries', 0):>7} {hard_failures:>4} "
          f"{str(acct['closes']):>6} {str(recovered):>9} "
          f"{cache_entries:>5}")
    return [rec]


def chips_sweep(chips_list, cores, neurons, entries, ticks, repeats=3):
    """Same total fabric, 1..K chips: hierarchy costs + sharded session."""
    print(f"\n== chip hierarchy sweep ({cores} cores total, {neurons} "
          f"neurons/core, {entries} CAM entries, {ticks} ticks, best of "
          f"{repeats}) ==")
    print(f"{'chips':>5} {'noc_hops':>9} {'noc_latency':>11} {'chip_hops':>9} "
          f"{'chip_latency':>12} {'chip_energy':>11} {'session_ms':>10} "
          f"{'shard_ok':>8}")
    base = fabric.FabricConfig(cores=cores, neurons_per_core=neurons,
                               cam_entries_per_core=entries)
    params = fabric.random_connectivity(jax.random.PRNGKey(0), base)
    sp = jax.random.bernoulli(jax.random.PRNGKey(3), RATE,
                              (ticks, cores, neurons))
    records = []
    cur_ref = None
    for chips in chips_list:
        cfg = dataclasses.replace(base, chips=chips)
        session = Interface(cfg).compile(params)

        def session_run():
            out = session.run(sp)
            jax.block_until_ready(out)
            return out

        cur, acc = session_run()                               # compile
        t_run = min(_timed(session_run) for _ in range(repeats))
        if cur_ref is None:
            cur_ref = cur
        assert bool(jnp.all(cur == cur_ref)), \
            "currents must not depend on the chip partitioning"
        cur_s, _ = session.run(sp, shard="chips")
        shard_ok = bool(jnp.all(cur_s == cur))
        assert shard_ok, "sharded currents drifted from the unsharded path"
        rec = {"chips": chips, "cores": cores, "neurons_per_core": neurons,
               "cam_entries_per_core": entries, "ticks": ticks,
               "session_ms": t_run * 1e3,
               "sharded_bit_identical": shard_ok}
        for name in ("noc_hops", "noc_latency", "noc_energy", "chip_hops",
                     "chip_latency", "chip_energy"):
            rec[name] = float(getattr(acc, name))
        records.append(rec)
        print(f"{chips:>5} {rec['noc_hops']:>9.0f} {rec['noc_latency']:>11.1f} "
              f"{rec['chip_hops']:>9.0f} {rec['chip_latency']:>12.1f} "
              f"{rec['chip_energy']:>11.0f} {rec['session_ms']:>10.2f} "
              f"{str(shard_ok):>8}")
    return records


def _git_sha():
    """Current commit (worktree-dirty marked), or 'unknown' outside git."""
    import subprocess
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
        ).stdout.strip()
        if not sha:
            return "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
        ).stdout.strip()
        return sha + ("-dirty" if dirty else "")
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cores", default=",".join(map(str, DEFAULT_CORES)),
                    help="comma-separated core counts to sweep (default: "
                         "%(default)s)")
    ap.add_argument("--ticks", type=int, default=16,
                    help="timesteps for the API wall-clock sweep "
                         "(default: %(default)s)")
    ap.add_argument("--tick-cores", default="16",
                    help="core counts for the session-tick sweep "
                         "(default: %(default)s)")
    ap.add_argument("--tick-neurons", type=int, default=256,
                    help="neurons/core for the session-tick sweep "
                         "(default: %(default)s)")
    ap.add_argument("--tick-entries", type=int, default=128,
                    help="CAM entries/core for the session-tick sweep "
                         "(default: %(default)s)")
    ap.add_argument("--tick-ticks", type=int, default=8,
                    help="timesteps for the session-tick sweep "
                         "(default: %(default)s)")
    ap.add_argument("--tick-repeats", type=int, default=3,
                    help="best-of-N repeats for the session-tick sweep; "
                         "raise on noisy shared runners (default: "
                         "%(default)s)")
    ap.add_argument("--scenario", default=None, metavar="LIST",
                    help="comma-separated repro.traffic scenario names, or "
                         "'all' (off by default); reuses the session-tick "
                         "shape (--tick-neurons/--tick-entries/--tick-ticks)")
    ap.add_argument("--scenario-cores", type=int, default=16,
                    help="cores for the scenario sweep (default: "
                         "%(default)s)")
    ap.add_argument("--serve", nargs="?", const=8, default=None, type=int,
                    metavar="TENANTS",
                    help="run the multi-tenant serve sweep with TENANTS "
                         "tenants (default when flag given: %(const)s) on "
                         "one shared session; reuses the session-tick "
                         "shape and --scenario-cores")
    ap.add_argument("--pump-threads", type=int, default=1,
                    help="background pump threads for the serve sweep's "
                         "async phase (default: %(default)s)")
    ap.add_argument("--chaos", nargs="?", const=12, default=None, type=int,
                    metavar="ROUNDS",
                    help="run the chaos sweep: the serve engine under a "
                         "seeded mixed fault plan for ROUNDS pump rounds "
                         "(default when flag given: %(const)s); reuses the "
                         "session-tick shape and --scenario-cores")
    ap.add_argument("--chaos-report", default=None, metavar="PATH",
                    help="write the chaos run's JSONL serve report to PATH "
                         "(render with python -m repro.obs.report)")
    ap.add_argument("--chips", default=None, metavar="LIST",
                    help="comma-separated chip counts for the hierarchy "
                         "sweep (e.g. 1,2,4; off by default)")
    ap.add_argument("--chips-cores", type=int, default=16,
                    help="total cores for the chip sweep "
                         "(default: %(default)s)")
    ap.add_argument("--json", nargs="?", const="BENCH_interface.json",
                    default=None, metavar="PATH",
                    help="write the session-tick records to PATH "
                         "(default when flag given: %(const)s)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace (Perfetto) JSON of the "
                         "compile/transfer/run/block spans to PATH "
                         "(repro.obs.trace)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    core_sweep = tuple(int(c) for c in str(args.cores).split(",") if c)
    tick_cores = tuple(int(c) for c in str(args.tick_cores).split(",") if c)
    chips_list = tuple(int(c) for c in str(args.chips).split(",") if c) \
        if args.chips else ()

    tracer = obs_trace.Tracer("noc_bench") if args.trace else None
    with (tracer if tracer is not None else contextlib.nullcontext()):
        # wall clock first: a pristine process keeps the comparison honest
        timing = api_timing_sweep(core_sweep, args.ticks)
        tick_records = tick_sweep(tick_cores, args.tick_neurons,
                                  args.tick_entries, args.tick_ticks,
                                  repeats=args.tick_repeats)
        sparsity_records = sparsity_sweep(
            tick_cores[0], args.tick_neurons, args.tick_entries,
            args.tick_ticks, repeats=args.tick_repeats)
        chips_records = chips_sweep(chips_list, args.chips_cores, NEURONS,
                                    2 * NEURONS, args.tick_ticks,
                                    repeats=args.tick_repeats) \
            if chips_list else []
        scenario_names = ()
        if args.scenario:
            scenario_names = traffic.scenario_names() \
                if args.scenario == "all" \
                else tuple(s for s in str(args.scenario).split(",") if s)
        scenario_records = scenario_sweep(
            scenario_names, args.scenario_cores, args.tick_neurons,
            args.tick_entries, args.tick_ticks,
            repeats=args.tick_repeats) if scenario_names else []
        serve_records = serve_sweep(
            args.serve, args.scenario_cores, args.tick_neurons,
            args.tick_entries, args.tick_ticks,
            repeats=args.tick_repeats,
            pump_threads=args.pump_threads) if args.serve else []
        chaos_records = chaos_sweep(
            args.chaos, args.scenario_cores, args.tick_neurons,
            args.tick_entries, args.tick_ticks,
            report_path=args.chaos_report) if args.chaos else []
        scheme = scheme_sweep(core_sweep)
        placed = placement_sweep(core_sweep)
    if tracer is not None:
        tracer.save(args.trace)
        print(f"\nwrote {args.trace} ({len(tracer.events)} trace events)")

    if args.json:
        payload = {"benchmark": "interface_session_tick",
                   "schema_version": SCHEMA_VERSION,
                   "git_sha": _git_sha(),
                   # host identity: committed baselines are only gate-
                   # comparable on a matching platform (check_regression
                   # warns instead of gating on mismatch)
                   "platform": jax.devices()[0].platform,
                   "jax_version": jax.__version__,
                   "config": vars(args),
                   "rate": RATE,
                   "records": tick_records + sparsity_records
                   + scenario_records + serve_records + chaos_records}
        if chips_records:
            payload["chips_records"] = chips_records
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"\nwrote {args.json} ({len(payload['records'])} records, "
              f"sha {payload['git_sha'][:12]})")

    print("\n== acceptance checks ==")
    ok = True
    for cores in (c for c in (16, 64) if c in core_sweep):
        bcast = scheme[(cores, "broadcast")]
        mtree = scheme[(cores, "multicast_tree")]
        s_ok = float(mtree.cam_searches) < float(bcast.cam_searches)
        h_ok = float(mtree.noc_hops) < float(bcast.noc_hops)
        _, _, st_greedy = placed[(cores, "greedy")]
        _, _, st_random = placed[(cores, "random")]
        p_ok = (float(st_greedy.cam_searches) <= float(st_random.cam_searches)
                and float(st_greedy.noc_hops) <= float(st_random.noc_hops))
        print(f"  {cores:>2} cores: multicast<broadcast searches={s_ok} "
              f"hops={h_ok}; greedy<=random placement={p_ok}")
        ok &= s_ok and h_ok and p_ok
    if args.ticks >= 8:
        t_ok = all(speedup >= 1.0 for _, _, speedup in timing.values())
        print(f"  session not slower than per-tick loop on all sizes: {t_ok}")
        ok &= t_ok
    else:
        # a couple of ticks sit inside scheduler noise on shared CI runners;
        # report the timing but gate only the meaningful sweeps
        print(f"  (timing reported, not gated: --ticks {args.ticks} < 8)")
    gated = [r for r in tick_records
             if r["cores"] >= 16 and r["neurons_per_core"] >= 256]
    if gated:
        s_ok = all(r["speedup"] >= 5.0 for r in gated)
        print("  event-driven tick >= 5x dense oracle at "
              + ", ".join(f"{r['cores']}x{r['neurons_per_core']}"
                          f" ({r['speedup']:.1f}x)" for r in gated)
              + f": {s_ok}")
        ok &= s_ok
    else:
        print("  (tick speedup reported, not gated below 16 cores x 256 "
              "neurons/core)")
    sp_gated = [r for r in sparsity_records
                if r["scenario"] == "sparsity_sparse_poisson"
                and r["cores"] >= 16 and r["neurons_per_core"] >= 256]
    if sp_gated:
        s_ok = all(r["sparse_speedup"] >= 3.0 for r in sp_gated)
        print("  sparse tick >= 3x dense event path on sparse_poisson at "
              + ", ".join(f"{r['cores']}x{r['neurons_per_core']}"
                          f" ({r['sparse_speedup']:.2f}x)" for r in sp_gated)
              + f": {s_ok}")
        ok &= s_ok
    else:
        print("  (sparse speedup reported, not gated below 16 cores x 256 "
              "neurons/core)")
    if scenario_records:
        live = all(r["events_per_tick"] > 0 for r in scenario_records)
        print(f"  every scenario produced traffic "
              f"({', '.join(r['scenario'] for r in scenario_records)}): "
              f"{live}")
        ok &= live
    if serve_records:
        r = serve_records[0]
        s_ok = (r["tenants"] >= 8 and r["groups"] == 1
                and r["serve_bit_identical"] and r["events_per_sec"] > 0)
        print(f"  serve: {r['tenants']} tenants on {r['groups']} session(s), "
              f"{r['events_per_sec']:.0f} events/s, stats bit-identical to "
              f"solo: {s_ok}")
        ok &= s_ok
        v2 = {x["scenario"]: x for x in serve_records[1:]}
        a = v2.get("__serve_async__")
        v2_ok = all(x["serve_bit_identical"] for x in serve_records) \
            and (a is None or a["async_vs_sync"] > 0)
        print(f"  serve v2: async pump "
              f"{a['async_vs_sync'] if a else 0:.2f}x sync, "
              f"autoscale+shard phases bit-identical: {v2_ok}")
        ok &= v2_ok
    if chaos_records:
        r = chaos_records[0]
        c_ok = (r["plan_exhausted"] and r["accounting_closes"]
                and r["lanes_recovered"] and r["jit_cache_entries"] == 1)
        print(f"  chaos: {r['faults'].get('injected', 0)} faults injected "
              f"over {r['rounds']} rounds, plan exhausted="
              f"{r['plan_exhausted']}, accounting closes="
              f"{r['accounting_closes']}, lanes recovered="
              f"{r['lanes_recovered']}, jit cache entries="
              f"{r['jit_cache_entries']}: {c_ok}")
        ok &= c_ok
    if chips_records:
        c_ok = all(r["sharded_bit_identical"] for r in chips_records)
        paid = all(r["chip_hops"] > 0 for r in chips_records if r["chips"] > 1)
        free = all(r["chip_hops"] == 0 for r in chips_records
                   if r["chips"] == 1)
        print(f"  sharded sessions bit-identical at chips="
              f"{','.join(str(r['chips']) for r in chips_records)}: {c_ok}; "
              f"chip tier paid iff chips>1: {paid and free}")
        ok &= c_ok and paid and free
    if not ok:
        raise SystemExit("acceptance criteria FAILED")
    print("  all passed")


if __name__ == "__main__":
    main()
