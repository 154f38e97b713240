"""Serving tier (`repro.serve`) + masked/ragged session batching.

The contract under test, bottom layer first:

* ``InterfaceSession.run_batched(spikes, mask=...)``: every masked lane's
  currents AND accumulated `StepStats` are BIT-IDENTICAL to a solo
  ``session.run`` over just its live ticks - sampled across the full
  5-arbiter x 3-NoC conformance grid, ragged lengths included, with an
  all-padding lane staying exactly zero.
* ``stats0`` threads the accumulator through chunked calls: a stream
  served in chunks accumulates bit-identically to one uninterrupted run.
* `IngestQueue` flushes on the size trigger, the deadline trigger
  (injectable clock), or ``force`` - and not before.
* `AdmissionController` bounds lanes/groups/request size with
  `AdmissionError`, before any device work.
* `ServeEngine` end-to-end: mixed-scenario tenants on one shared session
  serve bit-identically to their solo runs, report records carry the
  percentile + ``stats_per_tick`` fields the report CLI renders, and
  incompatible configs land on separate groups.
* Request latency is commit - submit and its wait first take - submit, on
  the engine's clock, through split and restaged requests; under an
  active tracer the pump thread lies in ``serve.*`` spans throughout.
* The LM reference loop still imports from `repro.serve.lm_engine`.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fabric
from repro.ft.chaos import (
    ChaosInjector,
    FaultEvent,
    FaultPlan,
    RetriesExhaustedError,
    TransientFaultError,
)
from repro.interface import Interface, InterfaceConfig, StepStats
from repro.noc import topology
from repro.obs import trace as obs_trace
from repro.serve import (
    AdmissionController,
    AdmissionError,
    AdmissionPolicy,
    AutoscalePolicy,
    CompositionError,
    IngestQueue,
    RateLimitedError,
    RetryPolicy,
    ServeEngine,
    ServeError,
    TenantSpec,
    TokenBucket,
    compat_key,
    default_connectivity,
)
from tests.conformance.paths import GRID, small_config

TICKS = 6


def _session(cfg, seed=0):
    params = fabric.random_connectivity(jax.random.PRNGKey(seed), cfg)
    return Interface(cfg).compile(params)


def _spikes(cfg, ticks=TICKS, seed=3, lead=()):
    shape = lead + (ticks, cfg.cores, cfg.neurons_per_core)
    return jax.random.bernoulli(jax.random.PRNGKey(seed), 0.25, shape)


def _assert_stats_equal(a: StepStats, b: StepStats, label: str) -> None:
    for field in StepStats._fields:
        va, vb = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        assert np.array_equal(va, vb), f"{label}: {field} {va} != {vb}"


# ---- masked / ragged batched stepping --------------------------------------


@pytest.mark.parametrize("arb_scheme,noc_scheme", GRID)
def test_masked_lanes_bit_identical_to_solo_across_grid(arb_scheme, noc_scheme):
    """Ragged lanes == solo runs, on every arbiter x NoC path."""
    cfg = small_config(arb_scheme, noc_scheme)
    session = _session(cfg)
    lengths = (TICKS, TICKS // 2, 0)  # full, ragged, all-padding
    spikes = _spikes(cfg, lead=(len(lengths),))
    mask = np.zeros((len(lengths), TICKS), bool)
    for lane, t in enumerate(lengths):
        mask[lane, :t] = True
    currents, acc = session.run_batched(spikes, mask=jnp.asarray(mask))
    for lane, t in enumerate(lengths):
        label = f"{arb_scheme}/{noc_scheme} lane{lane} t={t}"
        if t == 0:
            _assert_stats_equal(
                jax.tree.map(lambda x: x[lane], acc), StepStats.zeros(), label
            )
            assert not np.asarray(currents[lane]).any(), f"{label}: currents leaked"
            continue
        cur_solo, acc_solo = session.run(spikes[lane, :t])
        assert np.array_equal(
            np.asarray(currents[lane, :t]), np.asarray(cur_solo)
        ), f"{label}: currents differ"
        _assert_stats_equal(jax.tree.map(lambda x: x[lane], acc), acc_solo, label)


def test_masked_solo_run_matches_truncated():
    cfg = small_config("binary_tree", "multicast_tree")
    session = _session(cfg)
    spikes = _spikes(cfg)
    mask = jnp.arange(TICKS) < 4
    cur_m, acc_m = session.run(spikes, mask=mask)
    cur_t, acc_t = session.run(spikes[:4])
    assert np.array_equal(np.asarray(cur_m[:4]), np.asarray(cur_t))
    _assert_stats_equal(acc_m, acc_t, "masked solo vs truncated")


def test_stats0_carry_chunked_equals_one_shot():
    """Chunk-streamed serving accumulates bit-identically to one run."""
    cfg = small_config("greedy_tree", "unicast")
    session = _session(cfg)
    spikes = _spikes(cfg, ticks=8, lead=(2,))
    full_mask = jnp.ones((2, 8), bool)
    cur_full, acc_full = session.run_batched(spikes, mask=full_mask)
    acc = None
    chunks = []
    for lo in (0, 4):
        cur, acc = session.run_batched(
            spikes[:, lo : lo + 4], mask=full_mask[:, lo : lo + 4], stats0=acc
        )
        chunks.append(np.asarray(cur))
    assert np.array_equal(np.concatenate(chunks, axis=1), np.asarray(cur_full))
    _assert_stats_equal(acc, acc_full, "chunked stats0 carry")


def test_mask_validation():
    cfg = small_config("binary_tree", "broadcast")
    session = _session(cfg)
    spikes = _spikes(cfg, lead=(2,))
    good = jnp.ones((2, TICKS), bool)
    with pytest.raises(ValueError, match="mask"):
        session.run_batched(spikes, mask=jnp.ones((2, TICKS + 1), bool))
    with pytest.raises(ValueError, match="stats0"):
        session.run(spikes[0], stats0=StepStats.zeros())
    with pytest.raises(ValueError, match="shard"):
        session.run_batched(spikes, mask=good, shard="dies")
    with pytest.raises(CompositionError, match="telemetry"):
        session.run_batched(spikes, mask=good, telemetry="ticks")
    # mask + shard="chips" composes now (one-chip configs run flat)
    cur, _ = session.run_batched(spikes, mask=good, shard="chips")
    cur_flat, _ = session.run_batched(spikes, mask=good)
    assert np.array_equal(np.asarray(cur), np.asarray(cur_flat))


# ---- ingest queue ----------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _frames(n, cfg):
    return np.zeros((n, cfg.cores, cfg.neurons_per_core), bool)


def test_queue_size_trigger():
    cfg = small_config("binary_tree", "broadcast")
    q = IngestQueue(flush_frames=8, flush_deadline_s=60.0, clock=_FakeClock())
    q.submit("a", _frames(5, cfg))
    assert not q.ready() and q.poll() == []
    q.submit("b", _frames(3, cfg))  # 8 frames total: size trigger fires
    assert q.ready() and q.pending_frames() == 8
    out = q.poll()
    assert [r.tenant for r in out] == ["a", "b"]
    assert q.depth() == 0 and q.pending_frames() == 0


def test_queue_deadline_trigger_and_force():
    cfg = small_config("binary_tree", "broadcast")
    clock = _FakeClock()
    q = IngestQueue(flush_frames=100, flush_deadline_s=0.5, clock=clock)
    q.submit("a", _frames(2, cfg))
    clock.now = 0.4
    assert not q.ready()
    clock.now = 0.5  # oldest request hits its latency deadline
    assert q.ready() and len(q.poll()) == 1
    q.submit("b", _frames(1, cfg))
    assert len(q.poll(force=True)) == 1  # drain semantics ignore triggers
    with pytest.raises(ValueError, match="frames"):
        q.submit("c", np.zeros((0, cfg.cores, cfg.neurons_per_core), bool))


# ---- admission -------------------------------------------------------------


def test_admission_bounds():
    cfg = small_config("binary_tree", "broadcast")
    ctrl = AdmissionController(AdmissionPolicy(max_tenants_per_group=2, max_groups=1))
    spec = TenantSpec("t0", cfg)
    key = ctrl.admit(spec, {})
    assert key == compat_key(spec)
    with pytest.raises(AdmissionError, match="capacity"):
        ctrl.admit(spec, {key: 2})
    other = TenantSpec("t1", cfg, connectivity_seed=9)  # needs a new group
    with pytest.raises(AdmissionError, match="max_groups"):
        ctrl.admit(other, {key: 1})
    with pytest.raises(AdmissionError, match="max_frames_per_request"):
        ctrl.validate_request("t0", 5000)
    with pytest.raises(ValueError, match=">= 1"):
        AdmissionPolicy(max_groups=0)


def test_tenant_spec_validation_and_streams():
    cfg = small_config("binary_tree", "broadcast")
    with pytest.raises(ValueError, match="non-empty"):
        TenantSpec("", cfg)
    with pytest.raises(ValueError, match="unknown scenario parameter"):
        TenantSpec("t", cfg, scenario="sparse_poisson", scenario_params={"nope": 1})
    spec = TenantSpec("t", cfg, scenario="sparse_poisson", seed=5)
    a, b = spec.stream(4, round=0), spec.stream(4, round=0)
    assert np.array_equal(np.asarray(a), np.asarray(b)), "streams must be deterministic"
    c = spec.stream(4, round=1)
    assert not np.array_equal(np.asarray(a), np.asarray(c)), "rounds must draw fresh traffic"
    assert 0.0 < spec.expected_rate() < 1.0


# ---- serve engine ----------------------------------------------------------


def _engine(cfg, scenarios, **kw):
    kw.setdefault("flush_ticks", 4)
    kw.setdefault("flush_deadline_s", 0.0)
    engine = ServeEngine(**kw)
    specs = [
        TenantSpec(f"t{i}", cfg, scenario=sc, seed=i) for i, sc in enumerate(scenarios)
    ]
    for spec in specs:
        engine.register(spec)
    return engine, specs


def test_engine_serves_bit_identical_to_solo():
    cfg = small_config("binary_tree", "multicast_tree")
    engine, specs = _engine(
        cfg, ["sparse_poisson", "hotspot_core", "synchronized_burst"], keep_currents=True
    )
    assert len(engine.groups) == 1, "same (config, connectivity) must share a session"
    ticks = (7, 4, 9)  # ragged across tenants, none a flush multiple
    for spec, t in zip(specs, ticks):
        engine.submit_scenario(spec.name, t)
    assert engine.drain() == sum(ticks)

    session = _session(cfg)  # same seed-0 connectivity as the group
    for spec, t in zip(specs, ticks):
        cur_solo, acc_solo = session.run(spec.stream(t, round=0))
        assert np.array_equal(engine.currents(spec.name), np.asarray(cur_solo)), spec.name
        _assert_stats_equal(engine.tenant_stats(spec.name), acc_solo, spec.name)
        assert engine.ticks_served(spec.name) == t


def test_engine_report_records_and_metrics():
    cfg = small_config("binary_tree", "broadcast")
    engine, specs = _engine(cfg, ["sparse_poisson", "mixture"])
    for spec in specs:
        engine.submit_scenario(spec.name, 6)
    engine.drain()
    records = engine.serve_report()
    assert [r["tenant"] for r in records] == ["t0", "t1", "__fleet__"]
    latency = {"request_ms_p50", "request_ms_p95", "request_ms_p99", "wait_ms_p95"}
    for rec in records[:-1]:
        assert rec["ticks"] == 6
        assert latency | {"stats_per_tick"} <= set(rec)
        assert rec["request_ms_p99"] >= rec["request_ms_p50"] > 0
        assert rec["stats_per_tick"]["events"] > 0
    fleet = records[-1]
    assert fleet["tenants"] == 2 and fleet["ticks"] == 12
    assert fleet["events_per_sec"] > 0
    # fleet request percentiles come from Histogram.merge over the tenant hists
    assert latency <= set(fleet)
    assert fleet["request_ms_p99"] >= fleet["request_ms_p50"] > 0
    # the fleet's tick wall clock is the watchdog's per-step histogram
    steps = engine.registry.histograms["serve.step_ms"]
    assert fleet["tick_ms_p50"] == pytest.approx(steps.percentile(50) / engine.flush_ticks)
    assert fleet["tick_ms_p99"] >= fleet["tick_ms_p50"] > 0
    assert engine.registry.counter("serve.ticks").value == 12
    snapshot = engine.registry.snapshot()
    assert "tenant.t0.request_ms" in snapshot and "tenant.t0.wait_ms" in snapshot
    # nothing samples a per-lane tick time or the queue depth any more
    assert "tenant.t0.tick_ms" not in snapshot and "serve.queue_depth" not in snapshot


def test_engine_grouping_and_errors():
    cfg_a = small_config("binary_tree", "broadcast")
    cfg_b = small_config("binary_tree", "broadcast", cores=8)
    engine = ServeEngine(flush_ticks=4, policy=AdmissionPolicy(max_groups=2))
    engine.register(TenantSpec("a0", cfg_a))
    engine.register(TenantSpec("b0", cfg_b))  # incompatible shape: new group
    assert len(engine.groups) == 2
    with pytest.raises(ValueError, match="already registered"):
        engine.register(TenantSpec("a0", cfg_a))
    with pytest.raises(ValueError, match="conflict"):
        engine.register(
            TenantSpec("a1", cfg_a), params=default_connectivity(cfg_a, 0)
        )
    with pytest.raises(KeyError, match="unknown tenant"):
        engine.submit("ghost", np.zeros((1, cfg_a.cores, cfg_a.neurons_per_core), bool))
    with pytest.raises(ValueError, match="do not match"):
        engine.submit("a0", np.zeros((1, cfg_b.cores, cfg_b.neurons_per_core), bool))
    with pytest.raises(ValueError, match="keep_currents"):
        engine.currents("a0")


def test_engine_deadline_holds_partial_batches():
    """Under the deadline, a partial batch waits; force flushes it."""
    cfg = small_config("binary_tree", "broadcast")
    clock = _FakeClock()
    engine = ServeEngine(flush_ticks=8, flush_deadline_s=1.0, clock=clock)
    engine.register(TenantSpec("t0", cfg))
    engine.submit_scenario("t0", 3)  # 3 < 8 frames and inside the deadline
    assert engine.pump() == 0 and engine.queue_depth() == 1
    clock.now = 1.0
    assert engine.pump() == 3  # deadline trigger fires the partial flush
    engine.submit_scenario("t0", 2)
    clock.now = 1.5
    assert engine.drain() == 2  # force path ignores triggers entirely


def _clocked_steps(engine, clock, step_s):
    """Make every batched step of the engine's groups take ``step_s`` on
    the fake ``clock``."""
    for group in engine.groups.values():
        run = group.session.run_batched

        def timed(*args, _run=run, **kw):
            clock.now += step_s
            return _run(*args, **kw)

        group.session.run_batched = timed


def _latencies(engine, tenant):
    """(count, min, max) of the tenant's request_ms and wait_ms histograms."""
    hists = [engine.registry.histograms[f"tenant.{tenant}.{k}"] for k in ("request_ms", "wait_ms")]
    return [(h.count, h.min, h.max) for h in hists]


def test_request_latency_spans_submit_to_commit_across_chunks():
    cfg = small_config("binary_tree", "broadcast")
    clock = _FakeClock()
    engine, _ = _engine(cfg, ["sparse_poisson", "mixture"], clock=clock)
    _clocked_steps(engine, clock, 0.25)
    engine.submit_scenario("t0", 6)  # two chunks of flush_ticks=4
    clock.now = 0.5
    engine.submit_scenario("t1", 3)  # one chunk
    clock.now = 1.0
    assert engine.pump() == 9
    # both chunks were packed at 1.0; t1 commits with the first step (1.25),
    # t0 only with the second (1.5), which serves its last tick
    assert _latencies(engine, "t0") == [(1, 1500.0, 1500.0), (1, 1000.0, 1000.0)]
    assert _latencies(engine, "t1") == [(1, 750.0, 750.0), (1, 500.0, 500.0)]
    fleet = engine.serve_report()[-1]  # pooled: exact to a bucket's width
    assert fleet["request_ms_p99"] == pytest.approx(1500.0, rel=0.04)
    assert fleet["wait_ms_p95"] == pytest.approx(1000.0, rel=0.04)


def test_restaged_request_keeps_its_id_submit_and_first_take():
    cfg = small_config("binary_tree", "broadcast")
    clock = _FakeClock()
    plan = FaultPlan(events=(FaultEvent(round=2, kind="execute_fail", times=2),))
    engine, _ = _engine(
        cfg, ["sparse_poisson"], clock=clock, sleep=lambda s: None,
        chaos=ChaosInjector(plan, sleep=lambda s: None),
        retry=RetryPolicy(max_retries=1, backoff_base_s=0.0),
        policy=AdmissionPolicy(shed_deadline_s=2.5),
    )
    _clocked_steps(engine, clock, 0.25)
    engine.submit_scenario("t0", 2)  # request 0: served in round 1, by 0.25
    assert engine.pump() == 2
    engine.submit_scenario("t0", 4)  # request 1, submitted at 0.25
    clock.now = 1.0
    with pytest.raises(RetriesExhaustedError):
        engine.pump()  # round 2: packed at 1.0, then restaged with its id
    assert engine.accounting()["tenants"]["t0"]["pending"] == 4
    clock.now = 3.0  # 2.75 s after submit, 2.0 s after the restage
    tracer = obs_trace.Tracer()
    with tracer:
        assert engine.pump() == 4  # not shed: the deadline restarted
    assert engine.ticks_shed("t0") == 0
    request_ms, wait_ms = _latencies(engine, "t0")
    assert request_ms == (2, 250.0, 3000.0)  # request 1: 3.25 - 0.25
    assert wait_ms == (2, 0.0, 750.0)  # request 1: 1.0 - 0.25
    begin, end = [e for e in tracer.events if e["name"] == "serve.request"]
    assert begin["ph"] == "b" and end["ph"] == "e" and begin["id"] == end["id"] == 1
    assert begin["args"] == {"tenant": "t0", "ticks": 4}
    assert end["ts"] - begin["ts"] == pytest.approx(3000.0 * 1e3)


def _arrivals_during_steps(engine, arrivals):
    """Wrap the engine's batched step: while step k (from 1) runs, submit
    each ``(tenant, ticks)`` of ``arrivals(k)``.  Returns the list that
    collects each step's lanes with live ticks."""
    step, lanes = engine._step, []

    def stepping(group, spikes, mask):
        lanes.append(np.flatnonzero(np.asarray(mask).any(axis=1)).tolist())
        for name, ticks in arrivals(len(lanes)):
            engine.submit_scenario(name, ticks)
        return step(group, spikes, mask)

    engine._step = stepping
    return lanes


def test_midpump_arrival_rides_the_next_step_of_the_pump_in_flight():
    cfg = small_config("binary_tree", "broadcast")
    engine, specs = _engine(cfg, ["sparse_poisson", "hotspot_core"], keep_currents=True)
    engine.submit_scenario("t0", 12)  # three steps of flush_ticks=4
    lanes = _arrivals_during_steps(engine, lambda k: [("t1", 4)] if k == 1 else [])
    assert engine.pump(force=True) == 16
    # t1's lane was empty: its request joined step 2, before t0's last chunk
    assert lanes == [[0], [0, 1], [0]]
    assert engine.ticks_served("t1") == 4
    session = _session(cfg)
    for spec, t in zip(specs, (12, 4)):
        cur_solo, acc_solo = session.run(spec.stream(t, round=0))
        assert np.array_equal(engine.currents(spec.name), np.asarray(cur_solo)), spec.name
        _assert_stats_equal(engine.tenant_stats(spec.name), acc_solo, spec.name)


def test_pump_without_arrivals_steps_its_backlog_once_bit_identically():
    cfg = small_config("binary_tree", "multicast_tree")
    engine, specs = _engine(
        cfg, ["sparse_poisson", "hotspot_core", "synchronized_burst"], keep_currents=True
    )
    streams = {"t0": (5, 6), "t1": (4,), "t2": (7,)}  # t0's 11 ticks need 3 steps
    for name, lengths in streams.items():
        for t in lengths:
            engine.submit_scenario(name, t)
    assert engine.pump(force=True) == 22
    assert engine.registry.counter("serve.flushes").value == 3
    assert engine.registry.counter("serve.packed_requests").value == 4
    assert "serve.midpump_requests" not in engine.registry.counters
    session = _session(cfg)
    for spec in specs:
        stream = np.concatenate(
            [np.asarray(spec.stream(t, round=r)) for r, t in enumerate(streams[spec.name])]
        )
        cur_solo, acc_solo = session.run(stream)
        assert np.array_equal(engine.currents(spec.name), np.asarray(cur_solo)), spec.name
        _assert_stats_equal(engine.tenant_stats(spec.name), acc_solo, spec.name)


def test_a_steady_producer_cannot_hold_a_pump_past_its_backlog():
    cfg = small_config("binary_tree", "broadcast")
    engine, _ = _engine(cfg, ["sparse_poisson", "hotspot_core"])
    engine.submit_scenario("t0", 8)  # the round starts with two steps of backlog
    lanes = _arrivals_during_steps(engine, lambda k: [("t0", 4), ("t1", 4)])
    assert engine.pump(force=True) == 12
    assert len(lanes) == 2 and lanes[1] == [0, 1]
    assert engine.accounting()["closes"]
    del engine._step  # the producer stops
    engine.drain()
    acct = engine.accounting()
    assert acct["closes"] and all(v["pending"] == 0 for v in acct["tenants"].values())


def test_midpump_counter_counts_the_requests_that_joined_in_flight():
    cfg = small_config("binary_tree", "broadcast")
    engine, _ = _engine(cfg, ["sparse_poisson", "hotspot_core", "mixture"])
    engine.submit_scenario("t0", 12)  # three steps
    plan = {1: [("t1", 4), ("t2", 2)], 2: [("t1", 3)], 3: [("t2", 1)]}
    _arrivals_during_steps(engine, lambda k: plan.get(k, []))
    assert engine.pump(force=True) == 12 + 4 + 2 + 3
    counters = engine.registry.counters
    # the arrival during the last step waits for the next round's prologue
    assert counters["serve.midpump_requests"].value == 3
    assert counters["serve.packed_requests"].value == 4
    assert engine.drain() == 1
    assert counters["serve.midpump_requests"].value == 3
    fleet = engine.serve_report()[-1]
    assert fleet["packed_requests"] == 5 and fleet["midpump_requests"] == 3
    assert fleet["midpump_share"] == pytest.approx(0.6)


SERVE_SPANS = {
    "serve.pump", "serve.pump.wait", "serve.lock_wait", "serve.stage", "serve.take_chunk",
    "serve.record", "serve.step", "serve.device_transfer",
}


def _serve_a_few(engine, names, rounds=4, ticks=5):
    for _ in range(rounds):
        for name in names:
            engine.submit_scenario(name, ticks)
        _await_drained(engine, names)
        time.sleep(0.02)  # let the pump idle between bursts


def test_traced_pump_thread_lies_in_serve_spans():
    cfg = small_config("binary_tree", "broadcast")
    engine, specs = _engine(cfg, ["sparse_poisson", "hotspot_core"])
    names = [s.name for s in specs]
    engine.submit_scenario("t0", 4)
    engine.drain()  # compile outside the traced stretch
    tracer = obs_trace.Tracer()
    with tracer:
        engine.start(poll_interval_s=0.002)
        pump_tid = engine._pump_threads[0].ident
        _serve_a_few(engine, names)
        engine.stop()
    spans = [e for e in tracer.events if e["ph"] == "X" and e["tid"] == pump_tid]
    assert SERVE_SPANS <= {e["name"] for e in spans}
    first = min(e["ts"] for e in spans)
    last = max(e["ts"] + e["dur"] for e in spans)
    outer = sorted(
        (e["ts"], e["ts"] + e["dur"]) for e in spans if e["name"] in ("serve.pump", "serve.pump.wait")
    )
    covered = sum(end - start for start, end in outer)
    assert all(a[1] <= b[0] for a, b in zip(outer, outer[1:])), "pump and wait spans overlap"
    assert covered >= 0.95 * (last - first)
    requests = [e for e in tracer.events if e["name"] == "serve.request"]
    assert len(requests) == 2 * 4 * len(names)  # a begin and an end each
    assert len({e["id"] for e in requests}) == 4 * len(names)


def test_untraced_engine_records_no_spans_and_samples_nothing_per_pump():
    cfg = small_config("binary_tree", "broadcast")
    engine, specs = _engine(cfg, ["sparse_poisson", "hotspot_core"])
    names = [s.name for s in specs]
    idle = obs_trace.Tracer()  # made, never activated
    engine.start(poll_interval_s=0.002)
    _serve_a_few(engine, names)
    engine.stop()
    assert idle.events == []
    per_request = {f"tenant.{n}.{k}" for n in names for k in ("request_ms", "wait_ms")}
    assert set(engine.registry.histograms) == {"serve.step_ms"} | per_request
    steps = engine.registry.counter("serve.flushes").value
    assert engine.registry.histograms["serve.step_ms"].count == steps
    for name in per_request:
        assert engine.registry.histograms[name].count == 4


def test_lm_engine_relocated():
    from repro.serve import lm_engine

    assert hasattr(lm_engine, "ServeEngine") and hasattr(lm_engine, "make_decode_step")


# ---- serving tier v2: pump / rate limit / autoscale / sharding --------------


def _await_drained(engine, names, timeout_s=120.0):
    deadline = time.monotonic() + timeout_s
    while True:
        acct = engine.accounting()
        if all(acct["tenants"][n]["pending"] == 0 for n in names):
            return
        assert time.monotonic() < deadline, f"pump never drained: {acct}"
        time.sleep(0.002)


def test_background_pump_serves_bit_identical_to_solo():
    cfg = small_config("binary_tree", "broadcast")
    engine, specs = _engine(cfg, ["sparse_poisson", "hotspot_core"], keep_currents=True)
    streams = {s.name: np.asarray(s.stream(9, round=0)) for s in specs}
    engine.start(poll_interval_s=0.001)
    assert engine.running
    for name, frames in streams.items():
        engine.submit(name, frames)
    _await_drained(engine, streams)
    engine.stop(drain=True)
    assert not engine.running and engine.pump_errors() == []
    assert engine.accounting()["closes"]
    session = _session(cfg)
    for spec in specs:
        cur_solo, acc_solo = session.run(streams[spec.name])
        assert np.array_equal(engine.currents(spec.name), np.asarray(cur_solo)), spec.name
        _assert_stats_equal(engine.tenant_stats(spec.name), acc_solo, spec.name)
    # the engine is restartable: the context manager runs a second burst
    with engine:
        engine.submit_scenario("t0", 5)
        _await_drained(engine, ["t0"])
    assert engine.ticks_served("t0") == 14


def test_pump_fatal_error_surfaces_on_submit(monkeypatch):
    cfg = small_config("binary_tree", "broadcast")
    engine, _ = _engine(cfg, ["sparse_poisson"])

    def boom(force=False):
        raise RuntimeError("pump exploded")

    monkeypatch.setattr(engine, "pump", boom)
    engine.start(poll_interval_s=0.001)
    deadline = time.monotonic() + 30
    while engine.running:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    with pytest.raises(ServeError, match="pump exploded"):
        engine.submit_scenario("t0", 2)
    assert engine.registry.counter("serve.pump.fatal").value == 1
    engine.stop()  # fatal already surfaced; stop is a clean no-op join


def test_pump_survives_retries_exhausted(monkeypatch):
    cfg = small_config("binary_tree", "broadcast")
    engine, _ = _engine(cfg, ["sparse_poisson"])
    real_pump, tripped = engine.pump, []

    def flaky(force=False):
        if not tripped:
            tripped.append(1)
            raise RetriesExhaustedError("transfer still failing")
        return real_pump(force=force)

    monkeypatch.setattr(engine, "pump", flaky)
    engine.start(poll_interval_s=0.001)
    engine.submit_scenario("t0", 6)
    _await_drained(engine, ["t0"])
    engine.stop(drain=True)
    errors = engine.pump_errors()
    assert len(errors) == 1 and isinstance(errors[0], RetriesExhaustedError)
    assert engine.ticks_served("t0") == 6 and engine.accounting()["closes"]


def test_rate_limit_typed_rejection_and_refill():
    cfg = small_config("binary_tree", "broadcast")
    clock = _FakeClock()
    engine = ServeEngine(
        flush_ticks=4,
        flush_deadline_s=0.0,
        clock=clock,
        policy=AdmissionPolicy(rate_limit_per_s=8.0, rate_limit_burst=8.0),
    )
    engine.register(TenantSpec("t0", cfg))
    engine.submit("t0", _frames(8, cfg))  # drains the full burst
    with pytest.raises(RateLimitedError, match="rate-limited"):
        engine.submit("t0", _frames(1, cfg))
    assert engine.registry.counter("serve.rate_limited").value == 1
    assert engine.registry.counter("serve.rate_limited_ticks").value == 1
    # rejected ticks never entered the ledger
    assert engine.ticks_submitted("t0") == 8
    clock.now += 0.5  # refills 4 tokens
    engine.submit("t0", _frames(4, cfg))
    with pytest.raises(RateLimitedError, match="never be admitted"):
        engine.submit("t0", _frames(9, cfg))  # larger than the burst
    assert engine.drain() == 12
    assert engine.accounting()["closes"]
    fleet = engine.serve_report()[-1]
    assert fleet["faults"]["rate_limited"] == 2


def test_token_bucket_semantics():
    clock = _FakeClock()
    bucket = TokenBucket(rate=10.0, capacity=5.0, clock=clock)
    assert bucket.take(5) and not bucket.take(1)  # starts full; all-or-nothing
    clock.now += 0.25
    assert bucket.tokens() == pytest.approx(2.5)
    assert not bucket.take(3) and bucket.take(2.5)
    clock.now += 100.0
    assert bucket.tokens() == pytest.approx(5.0)  # capped at capacity
    with pytest.raises(ValueError, match="rate"):
        TokenBucket(rate=0.0, capacity=5.0)
    with pytest.raises(ValueError, match="burst"):
        AdmissionPolicy(rate_limit_burst=4.0)  # burst without a rate


def test_quarantined_backlog_sheds_past_deadline():
    """Regression: staged backlog frames never aged against the shed
    deadline - a quarantined lane's work could wait forever instead of
    shedding, violating what shed_deadline_s promises."""
    cfg = small_config("binary_tree", "broadcast")
    clock = _FakeClock()
    engine = ServeEngine(
        flush_ticks=4,
        flush_deadline_s=0.0,
        clock=clock,
        policy=AdmissionPolicy(shed_deadline_s=1.0),
    )
    engine.register(TenantSpec("t0", cfg))
    engine.submit_scenario("t0", 6)
    for _ in range(engine.health.policy.quarantine_after):
        engine.health.record_failure("t0")
    assert engine.lane_health("t0") == "quarantined"
    assert engine.pump(force=True) == 0  # staged but skipped, age 0: kept
    group = engine._tenant_group["t0"]
    assert group.backlog_ticks_of("t0") == 6
    clock.now = 5.0
    assert engine.pump(force=True) == 0  # aged out: shed, not served
    assert group.backlog_ticks_of("t0") == 0
    assert engine.ticks_shed("t0") == 6
    acct = engine.accounting()
    assert acct["closes"] and acct["tenants"]["t0"]["pending"] == 0
    assert any("backlog" in str(e) for e in engine.shed_errors())


def test_retry_recovery_clock_starts_at_first_attempt():
    """Regression: serve.recovery_ms used to start after the first failed
    attempt *returned*, so the failed attempt's own wall time - most of a
    real outage - was silently excluded."""
    clock = _FakeClock()
    engine = ServeEngine(flush_ticks=4, clock=clock, sleep=lambda s: None)
    tripped = []

    def flaky():
        if not tripped:
            tripped.append(1)
            clock.now += 2.0  # the failing attempt itself takes 2s
            raise TransientFaultError("transient")
        clock.now += 1.0
        return "ok"

    assert engine._with_retries("execute", flaky) == "ok"
    hist = engine.registry.histograms["serve.recovery_ms"]
    assert hist.count == 1
    assert hist.total == pytest.approx(3000.0)  # 2s failed attempt + 1s retry


def test_autoscale_policy_targets():
    exact = AutoscalePolicy()
    assert exact.target(3, 8) == 3 and exact.target(0, 0) == 1
    geo = AutoscalePolicy(grow_factor=2.0, shrink_at=0.5)
    assert geo.target(3, 2) == 4 and geo.target(5, 4) == 8
    assert geo.target(3, 8) == 4  # 3 > 4 * 0.5: hysteresis holds at 4
    assert geo.target(2, 8) == 2  # 2 <= 4 * 0.5: shrinks through to the floor
    floor = AutoscalePolicy(min_lanes=4)
    assert floor.target(1, 0) == 4
    with pytest.raises(ValueError, match="grow_factor"):
        AutoscalePolicy(grow_factor=0.5)
    with pytest.raises(ValueError, match="shrink_at"):
        AutoscalePolicy(shrink_at=0.0)


def test_autoscale_grow_shrink_preserves_solo_bit_identity():
    cfg = small_config("binary_tree", "multicast_tree")
    engine = ServeEngine(flush_ticks=4, flush_deadline_s=0.0, keep_currents=True)
    engine.register(TenantSpec("t0", cfg, scenario="sparse_poisson", seed=0))
    engine.submit_scenario("t0", 6)
    assert engine.drain() == 6
    engine.register(TenantSpec("t1", cfg, scenario="hotspot_core", seed=1))
    group = engine._tenant_group["t0"]
    assert group.capacity == 2 and group.capacities_seen == {1, 2}
    engine.submit_scenario("t0", 5)
    engine.submit_scenario("t1", 7)
    assert engine.drain() == 12
    assert engine.accounting()["closes"]
    engine.submit_scenario("t1", 3)
    with pytest.raises(ServeError, match="pending"):
        engine.deregister("t1")  # a lane with queued work cannot retire
    assert engine.drain() == 3
    spec0 = group.specs["t0"]
    engine.deregister("t1")
    assert group.capacity == 1 and "t1" not in group.lanes
    engine.submit_scenario("t0", 4)
    assert engine.drain() == 4
    # t0's chunks crossed capacities 1 -> 2 -> 1; its cumulative stream
    # must still equal one uninterrupted solo run, stats included
    session = _session(cfg)
    full = np.concatenate(
        [np.asarray(spec0.stream(t, round=r)) for r, t in enumerate((6, 5, 4))]
    )
    cur, acc = session.run(full)
    assert np.array_equal(engine.currents("t0"), np.asarray(cur))
    _assert_stats_equal(engine.tenant_stats("t0"), acc, "t0 across resizes")
    acct = engine.accounting()
    assert acct["closes"] and acct["tenants"]["t1"]["pending"] == 0  # retired row
    assert engine.registry.counter("serve.autoscale.grow").value == 2
    assert engine.registry.counter("serve.autoscale.shrink").value == 1
    assert engine.serve_report()[-1]["lane_capacity"] == 1


def _chip_cfg(chips=2, cores=8, n=16, entries=32):
    return InterfaceConfig(cores=cores, neurons_per_core=n,
                           cam_entries_per_core=entries, scheme="hier_tree",
                           noc=topology.NocConfig("multicast_tree"), chips=chips)


def test_sharded_group_bit_identical_and_separate_from_flat():
    cfg = _chip_cfg()
    engine = ServeEngine(flush_ticks=4, flush_deadline_s=0.0, keep_currents=True)
    engine.register(TenantSpec("s0", cfg, shard="chips", seed=0))
    engine.register(TenantSpec("s1", cfg, shard="chips", scenario="hotspot_core", seed=1))
    engine.register(TenantSpec("f0", cfg, seed=0))
    # sharded and flat tenants of the SAME config land in different groups
    assert len(engine.groups) == 2
    group = engine._tenant_group["s0"]
    assert group.shard == "chips" and engine._tenant_group["f0"] is not group
    for name, t in (("s0", 7), ("s1", 5), ("f0", 7)):
        engine.submit_scenario(name, t)
    assert engine.drain() == 19
    # each sharded lane is bit-identical to the flat unsharded oracle
    session = _session(cfg)
    for name, t in (("s0", 7), ("s1", 5)):
        spec = group.specs[name]
        cur, acc = session.run(spec.stream(t, round=0))
        assert np.array_equal(engine.currents(name), np.asarray(cur)), name
        _assert_stats_equal(engine.tenant_stats(name), acc, name)
    assert group.jit_cache_entries() == 1
    assert engine.accounting()["closes"]
    # rejected composition is a typed error at spec construction
    with pytest.raises(CompositionError, match="one-chip"):
        TenantSpec("bad", small_config("binary_tree", "broadcast"), shard="chips")
    with pytest.raises(ValueError, match="unknown shard"):
        TenantSpec("bad", cfg, shard="dies")
    # the package-level ServeEngine is the fabric streaming engine now
    assert hasattr(ServeEngine, "register") and hasattr(ServeEngine, "drain")
