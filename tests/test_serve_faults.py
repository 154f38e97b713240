"""Graceful degradation of the serving engine (`repro.serve` + `repro.ft`).

The hardened-engine contract, layer by layer:

* `submit` validates frames host-side: NaN, wrong dtype, wrong rank, and
  wrong fabric shape raise typed `FrameValidationError` (also a
  `ValueError`, so legacy handlers keep working) before any device work;
* `QueueOverflowError` bounds pending work per group at submit time and
  clears once the engine drains - backpressure, not data loss;
* requests older than ``shed_deadline_s`` are shed at flush time as
  typed `DeadlineExceededError`s, and shed ticks keep the accounting
  identity submitted == served + shed + pending closed;
* transient transfer/execute faults retry under the bounded-backoff
  `RetryPolicy` and the served results stay BIT-IDENTICAL to an
  undisturbed engine (commit-after-success: replays cannot
  double-count);
* when retries exhaust, unserved chunks restage onto the backlog before
  `RetriesExhaustedError` propagates - the ledger still closes, and a
  later pump serves the work;
* repeated lane faults walk healthy -> degraded -> quarantined; a
  quarantined lane is masked out of the shared batched step WITHOUT
  recompiling, probes back after its cooldown, and recovers - while the
  other lanes keep serving throughout;
* a tenant carrying a fabric-level `FaultModel` lands in its own group
  (the compat key includes the fault), so clean tenants' results are
  untouched by a faulted neighbor.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft import (
    ChaosInjector,
    FaultEvent,
    FaultModel,
    FaultPlan,
    RetriesExhaustedError,
    TransientFaultError,
)
from repro.interface import Interface
from repro.serve import (
    AdmissionError,
    AdmissionPolicy,
    DeadlineExceededError,
    FrameValidationError,
    QueueOverflowError,
    RetryPolicy,
    ServeEngine,
    ServeError,
    TenantSpec,
    default_connectivity,
)
from tests.conformance.paths import small_config

TICKS = 8


class _FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _engine(**kw):
    kw.setdefault("flush_ticks", TICKS)
    kw.setdefault("flush_deadline_s", 0.0)
    kw.setdefault("sleep", lambda s: None)
    return ServeEngine(**kw)


def _frames(cfg, ticks=TICKS, fill=False):
    return np.full((ticks, cfg.cores, cfg.neurons_per_core), fill, bool)


# ---- typed error hierarchy --------------------------------------------------


def test_error_hierarchy():
    assert issubclass(AdmissionError, ServeError)
    assert issubclass(QueueOverflowError, AdmissionError)
    assert issubclass(DeadlineExceededError, AdmissionError)
    assert issubclass(FrameValidationError, ServeError)
    assert issubclass(FrameValidationError, ValueError)


# ---- frame validation at submit ---------------------------------------------


def test_submit_rejects_malformed_frames():
    cfg = small_config("binary_tree", "broadcast")
    engine = _engine()
    engine.register(TenantSpec("t0", cfg))
    good = _frames(cfg).astype(np.float32)
    nan = good.copy()
    nan[0, 0, 0] = np.nan
    with pytest.raises(FrameValidationError, match="non-finite"):
        engine.submit("t0", nan)
    with pytest.raises(FrameValidationError, match="dtype"):
        engine.submit("t0", good.astype(np.complex64))
    with pytest.raises(FrameValidationError, match="ticks >= 1"):
        engine.submit("t0", good[0])  # rank 2
    with pytest.raises(FrameValidationError, match="ticks >= 1"):
        engine.submit("t0", good[:0])  # empty stream
    with pytest.raises(FrameValidationError, match="do not match the group"):
        engine.submit("t0", np.zeros((TICKS, cfg.cores + 1, cfg.neurons_per_core)))
    assert engine.ticks_submitted("t0") == 0, "rejected frames must not be counted"
    # finite floats are accepted and cast to bool
    engine.submit("t0", good)
    assert engine.drain() == TICKS


def test_queue_overflow_backpressure_clears_after_drain():
    cfg = small_config("binary_tree", "broadcast")
    engine = _engine(policy=AdmissionPolicy(max_pending_frames=2 * TICKS))
    engine.register(TenantSpec("t0", cfg))
    engine.submit("t0", _frames(cfg))
    engine.submit("t0", _frames(cfg))
    with pytest.raises(QueueOverflowError, match="max_pending_frames"):
        engine.submit("t0", _frames(cfg))
    acct = engine.accounting()
    assert acct["closes"] and acct["tenants"]["t0"]["pending"] == 2 * TICKS
    engine.drain()
    engine.submit("t0", _frames(cfg))  # capacity restored
    assert engine.drain() == TICKS
    assert engine.accounting()["closes"]


# ---- deadline shedding ------------------------------------------------------


def test_deadline_shedding_is_typed_and_accounted():
    cfg = small_config("binary_tree", "broadcast")
    clock = _FakeClock()
    engine = _engine(
        policy=AdmissionPolicy(shed_deadline_s=1.0),
        clock=clock,
        keep_currents=True,
    )
    engine.register(TenantSpec("t0", cfg))
    engine.submit("t0", _frames(cfg, fill=True))
    clock.now = 2.0  # the queued request ages past the shed deadline
    engine.submit("t0", _frames(cfg, fill=True))
    assert engine.drain() == TICKS, "only the fresh request is served"
    assert engine.ticks_shed("t0") == TICKS
    errors = engine.shed_errors()
    assert len(errors) == 1 and isinstance(errors[0], DeadlineExceededError)
    assert "t0" in str(errors[0])
    acct = engine.accounting()
    assert acct["closes"]
    assert acct["tenants"]["t0"] == {
        "submitted": 2 * TICKS,
        "served": TICKS,
        "shed": TICKS,
        "pending": 0,
    }
    assert engine.registry.counter("serve.shed_ticks").value == TICKS
    rec = engine.serve_report()[0]
    assert rec["shed_ticks"] == TICKS and rec["submitted"] == 2 * TICKS


# ---- transient-fault retries ------------------------------------------------


def _mirrored_engines(cfg, specs, **chaos_kw):
    """One chaotic engine and one undisturbed twin over the same specs."""
    chaotic = _engine(keep_currents=True, **chaos_kw)
    calm = _engine(keep_currents=True)
    for spec in specs:
        chaotic.register(spec)
        calm.register(spec)
    return chaotic, calm


def test_retried_faults_stay_bit_identical_to_calm_engine():
    cfg = small_config("binary_tree", "multicast_tree")
    specs = [
        TenantSpec("t0", cfg, scenario="sparse_poisson", seed=0),
        TenantSpec("t1", cfg, scenario="hotspot_core", seed=1),
    ]
    plan = FaultPlan(
        events=(
            FaultEvent(round=1, kind="transfer_fail", times=2),
            FaultEvent(round=2, kind="execute_fail", times=2),
            FaultEvent(round=2, kind="slow_device", times=1, delay_s=0.0),
        )
    )
    chaotic, calm = _mirrored_engines(
        cfg,
        specs,
        chaos=ChaosInjector(plan, sleep=lambda s: None),
        retry=RetryPolicy(max_retries=3, backoff_base_s=0.0),
    )
    for round_ in range(3):
        for engine in (chaotic, calm):
            for spec in specs:
                engine.submit_scenario(spec.name, TICKS)
            engine.pump(force=True)
    assert chaotic.chaos.exhausted()
    assert chaotic.registry.counter("serve.retries").value == 4
    assert chaotic.registry.counter("serve.retry_recoveries").value == 2
    for spec in specs:
        assert np.array_equal(chaotic.currents(spec.name), calm.currents(spec.name)), (
            f"{spec.name}: retried currents drifted from the calm engine"
        )
        a, b = chaotic.tenant_stats(spec.name), calm.tenant_stats(spec.name)
        for field, va in a._asdict().items():
            assert float(np.asarray(va)) == float(np.asarray(getattr(b, field)))
    assert chaotic.accounting()["closes"]


def test_retry_after_dispatch_reads_a_live_accumulator():
    """An attempt that fails after its batched step was dispatched is
    retried with the same committed accumulator; the failed attempt must
    not have consumed it (no buffer donation), so the retry stays
    bit-identical to an undisturbed engine."""
    cfg = small_config("binary_tree", "multicast_tree")
    specs = [TenantSpec("t0", cfg, scenario="sparse_poisson", seed=0)]
    chaotic, calm = _mirrored_engines(
        cfg, specs, retry=RetryPolicy(max_retries=2, backoff_base_s=0.0)
    )
    session = next(iter(chaotic.groups.values())).session
    dispatch = session.run_batched
    calls = []

    def fails_once_after_dispatch(*args, **kw):
        out = dispatch(*args, **kw)
        calls.append(out)
        if len(calls) == 1:
            raise TransientFaultError("lost the device after dispatch")
        return out

    session.run_batched = fails_once_after_dispatch
    for _ in range(2):
        for engine in (chaotic, calm):
            engine.submit_scenario("t0", TICKS)
            engine.pump(force=True)
    assert len(calls) == 3, "one failed attempt, then one step per round"
    assert chaotic.registry.counter("serve.retry_recoveries").value == 1
    assert np.array_equal(chaotic.currents("t0"), calm.currents("t0"))
    a, b = chaotic.tenant_stats("t0"), calm.tenant_stats("t0")
    for field, va in a._asdict().items():
        assert float(np.asarray(va)) == float(np.asarray(getattr(b, field))), field
    assert chaotic.accounting()["closes"]


def test_retries_exhausted_restages_then_recovers():
    cfg = small_config("binary_tree", "broadcast")
    plan = FaultPlan(events=(FaultEvent(round=1, kind="transfer_fail", times=6),))
    engine = _engine(
        chaos=ChaosInjector(plan, sleep=lambda s: None),
        retry=RetryPolicy(max_retries=1, backoff_base_s=0.0),
    )
    engine.register(TenantSpec("t0", cfg))
    engine.submit("t0", _frames(cfg, fill=True))
    hard = 0
    while True:  # 6 charges / 2 attempts per pump: fails thrice, then heals
        try:
            engine.drain()
            break
        except RetriesExhaustedError:
            hard += 1
            acct = engine.accounting()
            assert acct["closes"], "ledger must close at every failure point"
            assert acct["tenants"]["t0"]["pending"] == TICKS, "work restaged"
    assert hard == 3
    assert engine.chaos.exhausted()
    assert engine.ticks_served("t0") == TICKS
    assert engine.registry.counter("serve.retries_exhausted").value == 3
    assert engine.accounting()["closes"]


# ---- lane health machine ----------------------------------------------------


def test_quarantine_masks_lane_without_recompile_then_recovers():
    cfg = small_config("binary_tree", "multicast_tree")
    specs = [
        TenantSpec("t0", cfg, scenario="sparse_poisson", seed=0),
        TenantSpec("t1", cfg, scenario="hotspot_core", seed=1),
        TenantSpec("t2", cfg, scenario="mixture", seed=2),
    ]
    plan = FaultPlan(events=(FaultEvent(round=1, kind="lane_fault", tenant="t1", times=2),))
    from repro.serve import HealthPolicy

    engine = _engine(
        chaos=ChaosInjector(plan, sleep=lambda s: None),
        health=HealthPolicy(quarantine_after=2, quarantine_rounds=2, recover_after=1),
    )
    for spec in specs:
        engine.register(spec)
    assert len(engine.groups) == 1
    group = next(iter(engine.groups.values()))

    states = []
    for _ in range(6):
        for spec in specs:
            engine.submit_scenario(spec.name, TICKS)
        engine.pump(force=True)
        states.append(engine.lane_health("t1"))
        # healthy lanes never stall behind the sick one
        assert engine.ticks_served("t0") == engine.ticks_submitted("t0")
    # round 1: first fault degrades; round 2: second fault quarantines
    # (masked the same pump); round 3: cooldown (still masked, backlog
    # retained); round 4: cooldown expires at the pump's advance - the
    # lane probes, serves cleanly, and recovers; rounds 5-6: healthy
    assert states == ["degraded", "quarantined", "quarantined", "healthy", "healthy", "healthy"]
    assert engine.registry.counter("serve.quarantines").value == 1
    assert engine.registry.counter("serve.probes").value == 1
    assert engine.registry.counter("serve.recoveries").value == 1
    engine.drain()  # quarantine-era backlog finally served
    assert engine.ticks_served("t1") == engine.ticks_submitted("t1")
    assert engine.accounting()["closes"]
    batched = group.session._masked_cache["run_batched"]
    assert batched._cache_size() == 1, "quarantine masking must not recompile"
    fleet = engine.serve_report()[-1]
    assert fleet["faults"]["quarantines"] == 1
    assert fleet["faults"]["injected"] >= 2
    assert "recovery_ms_p50" in fleet


def test_lane_fault_on_unknown_tenant_is_counted_not_fatal():
    cfg = small_config("binary_tree", "broadcast")
    plan = FaultPlan(events=(FaultEvent(round=1, kind="lane_fault", tenant="ghost"),))
    engine = _engine(chaos=ChaosInjector(plan, sleep=lambda s: None))
    engine.register(TenantSpec("t0", cfg))
    engine.submit("t0", _frames(cfg))
    assert engine.drain() == TICKS
    assert engine.registry.counter("serve.faults.unknown_lane").value == 1
    assert engine.lane_health("t0") == "healthy"
    with pytest.raises(KeyError, match="unknown tenant"):
        engine.lane_health("ghost")


# ---- fabric faults inside the serving tier ----------------------------------


def test_fabric_faulted_tenant_gets_own_group_and_clean_stay_identical():
    cfg = small_config("binary_tree", "multicast_tree")
    fault = FaultModel(drop_rate=0.3, seed=7)
    specs = [
        TenantSpec("clean0", cfg, scenario="sparse_poisson", seed=0),
        TenantSpec("clean1", cfg, scenario="hotspot_core", seed=1),
        TenantSpec("lossy", cfg, scenario="sparse_poisson", seed=0, fault=fault),
    ]
    engine = _engine(keep_currents=True)
    for spec in specs:
        engine.register(spec)
    assert len(engine.groups) == 2, "the fault must be part of the compat key"
    for spec in specs:
        engine.submit_scenario(spec.name, TICKS)
        engine.submit_scenario(spec.name, TICKS)
    engine.drain()
    # clean tenants: bit-identical to their solo sessions, untouched by
    # the lossy neighbor; the lossy tenant matches its own faulted solo
    params = default_connectivity(cfg, 0)
    for name, solo_fault in (("clean0", None), ("lossy", fault)):
        spec = next(s for s in specs if s.name == name)
        stream = jnp.concatenate([spec.stream(TICKS, round=r) for r in range(2)])
        solo = Interface(cfg).compile(params, fault=solo_fault)
        kw = {"fault_tick0": 0} if solo_fault is not None else {}
        cur, _ = solo.run(stream, **kw)
        assert np.array_equal(engine.currents(name), np.asarray(cur)), name
    # the drop actually bit: lossy serves fewer events than its clean twin
    lossy = float(np.asarray(engine.tenant_stats("lossy").events))
    clean = float(np.asarray(engine.tenant_stats("clean0").events))
    assert lossy < clean
    rec = next(r for r in engine.serve_report() if r.get("tenant") == "lossy")
    assert rec["fault"]["drop_rate"] == pytest.approx(0.3)
