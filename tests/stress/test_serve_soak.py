"""Sustained-load soak of the serving engine (``slow`` tier).

Drives a mixed-scenario tenant fleet through a few thousand ticks of
round-based load and asserts the properties that only show up under
sustained operation, not in one flush:

* **no queue-depth divergence**: the engine keeps up with the offered
  load round after round - queues return to empty after every drain and
  the queue depth before each drain never exceeds the per-round offered
  request count;
* **stable jit cache**: chunk shapes are fixed (lanes x flush_ticks), so
  the masked batched step compiles exactly once for the whole soak - a
  shape leak (recompile per round) would show up here long before it
  shows up as a latency cliff in production;
* **stable memory**: host-side bookkeeping (backlogs, queues, retained
  currents) does not grow with rounds served; python object growth per
  round stays bounded;
* **accounting closes**: per-tenant served ticks and the fleet tick
  counter agree with the offered load exactly, events keep flowing, and
  the final report is well-formed.
"""

import gc

import pytest

from repro.serve import ServeEngine, TenantSpec
from tests.conformance.paths import small_config

ROUNDS = 40
TICKS_PER_ROUND = 16  # x 5 tenants x 40 rounds = 3200 lane-ticks
SCENARIOS = ("sparse_poisson", "hotspot_core", "synchronized_burst", "mixture", "clustered")


@pytest.mark.slow
def test_serve_soak_sustained_mixed_load():
    cfg = small_config("binary_tree", "multicast_tree")
    engine = ServeEngine(flush_ticks=TICKS_PER_ROUND, flush_deadline_s=0.0)
    specs = [
        TenantSpec(f"t{i}", cfg, scenario=sc, seed=i) for i, sc in enumerate(SCENARIOS)
    ]
    for spec in specs:
        engine.register(spec)
    assert len(engine.groups) == 1
    group = next(iter(engine.groups.values()))

    # warm round: pays compilation, then measure cache/memory stability
    for spec in specs:
        engine.submit_scenario(spec.name, TICKS_PER_ROUND)
    engine.drain()
    batched_fn = group.session._masked_cache["run_batched"]
    assert batched_fn._cache_size() == 1

    gc.collect()
    objects_before = len(gc.get_objects())

    depth_max = 0
    for _ in range(ROUNDS - 1):
        for spec in specs:
            engine.submit_scenario(spec.name, TICKS_PER_ROUND)
        depth_max = max(depth_max, engine.queue_depth())
        served = engine.drain()
        assert served == len(specs) * TICKS_PER_ROUND
        # no divergence: drained queues and backlogs return to empty
        assert engine.queue_depth() == 0
        assert group.backlog_ticks() == 0

    # fixed chunk shapes: the whole soak ran on ONE compiled batched step
    assert batched_fn._cache_size() == 1, "chunk shape leak: masked step recompiled"

    gc.collect()
    growth = len(gc.get_objects()) - objects_before
    assert growth < 50_000, f"host object growth over {ROUNDS} rounds: {growth}"

    # accounting closes exactly
    total = ROUNDS * TICKS_PER_ROUND
    for spec in specs:
        assert engine.ticks_served(spec.name) == total
    assert engine.ticks_served() == len(specs) * total
    assert engine.registry.counter("serve.ticks").value == len(specs) * total
    assert depth_max <= len(specs), "queue depth diverged beyond one round's load"

    records = engine.emit_report()
    fleet = records[-1]
    assert fleet["ticks"] == len(specs) * total
    assert fleet["events"] > 0 and fleet["events_per_sec"] > 0
    assert fleet["tick_ms_p99"] >= fleet["tick_ms_p50"] > 0
