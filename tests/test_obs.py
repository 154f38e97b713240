"""Observability subsystem (`repro.obs`): telemetry, tracing, metrics, report.

The contract under test, in the order the layers stack:

* ``telemetry="off"`` is exactly today's path - currents AND accumulated
  stats bit-identical across the conformance grid and execution paths
  (event / pallas / multichip); richer modes never change them either.
* ``"ticks"`` per-tick series sums back to the accumulated `StepStats`
  (exactly for integer-valued counts, to float tolerance for energies).
* ``"cores"`` per-core breakdowns sum (max, for latency) to the per-tick
  totals, and attribute inter-chip hops only when chips > 1.
* `repro.obs.trace` spans record nested Chrome-trace events, are exact
  no-ops when no tracer is active, wrap session compile/run, and are
  stamped on the clock of the profiler's host events.
* `repro.obs.metrics` percentiles track numpy within the documented
  bucket error; the JSONL sink feeds ``python -m repro.obs.report``.
* `StepStats.mean`/``summary(ticks=0)`` raises instead of silently
  reporting inf/nan.
"""

import dataclasses
import glob
import json
import math
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fabric
from repro.interface import Interface, StepStats
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import telemetry as obs_telemetry
from repro.obs import trace as obs_trace
from hypothesis import given, settings, strategies as st
from tests.conformance.paths import ARBITER_SCHEMES, EXACT_FIELDS, GRID, NOC_SCHEMES, small_config

REL = 1e-6
TICKS = 5


def _session(cfg, seed=0):
    params = fabric.random_connectivity(jax.random.PRNGKey(seed), cfg)
    return Interface(cfg).compile(params)


def _spikes(cfg, ticks=TICKS, seed=3, lead=()):
    shape = lead + (ticks, cfg.cores, cfg.neurons_per_core)
    return jax.random.bernoulli(jax.random.PRNGKey(seed), 0.25, shape)


def _assert_stats_equal(a: StepStats, b: StepStats, label: str) -> None:
    for field in StepStats._fields:
        va, vb = getattr(a, field), getattr(b, field)
        assert bool(jnp.all(va == vb)), f"{label}: {field} differs"


def _assert_sums_back(acc: StepStats, series: StepStats, label: str) -> None:
    """Summing the tick axis reproduces the accumulated record (also batched)."""
    for field in StepStats._fields:
        total = np.asarray(getattr(acc, field))
        summed = np.asarray(jnp.sum(getattr(series, field), axis=-1))
        if field in EXACT_FIELDS:
            assert np.array_equal(summed, total), f"{label}: {field} {summed} != {total}"
        else:
            np.testing.assert_allclose(summed, total, rtol=REL, err_msg=f"{label}: {field}")


# ---- telemetry: "off" identical, series sums back --------------------------


@pytest.mark.parametrize("arb_scheme,noc_scheme", GRID)
def test_telemetry_preserves_off_path_across_grid(arb_scheme, noc_scheme):
    """Currents and accumulated stats are bit-identical with telemetry on."""
    cfg = small_config(arb_scheme, noc_scheme)
    session = _session(cfg)
    spikes = _spikes(cfg)
    cur_off, acc_off = session.run(spikes)
    cur_t, acc_t, telem = session.run(spikes, telemetry="ticks")
    assert bool(jnp.all(cur_off == cur_t)), f"{arb_scheme}/{noc_scheme}: currents differ"
    _assert_stats_equal(acc_off, acc_t, f"{arb_scheme}/{noc_scheme}")
    _assert_sums_back(acc_off, telem.per_tick, f"{arb_scheme}/{noc_scheme}")
    assert telem.ticks == TICKS


@pytest.mark.parametrize("variant", ["pallas", "chips2"], ids=["impl=pallas", "chips=2"])
def test_telemetry_preserves_off_path_on_alt_paths(variant):
    cfg = small_config(ARBITER_SCHEMES[0], NOC_SCHEMES[1])
    if variant == "pallas":
        cfg = dataclasses.replace(cfg, impl="pallas")
    else:
        cfg = dataclasses.replace(cfg, chips=2)
    session = _session(cfg)
    spikes = _spikes(cfg)
    cur_off, acc_off = session.run(spikes)
    for mode in ("ticks", "cores"):
        cur_t, acc_t, _ = session.run(spikes, telemetry=mode)
        assert bool(jnp.all(cur_off == cur_t)), f"{variant}/{mode}: currents differ"
        _assert_stats_equal(acc_off, acc_t, f"{variant}/{mode}")


def test_tick_series_percentiles_and_records():
    cfg = small_config(ARBITER_SCHEMES[0], NOC_SCHEMES[0])
    session = _session(cfg)
    _, _, telem = session.run(_spikes(cfg), telemetry="ticks")
    series = np.asarray(telem.series("events"))
    pcts = telem.percentiles("events")
    assert pcts["p50"] == pytest.approx(float(np.percentile(series, 50)))
    assert pcts["p99"] == pytest.approx(float(np.percentile(series, 99)))
    records = telem.to_records()
    assert len(records) == TICKS
    assert records[0]["events"] == float(series[0])
    assert set(records[0]) == set(StepStats._fields)


# ---- telemetry: per-core attribution ---------------------------------------


@pytest.mark.parametrize("arb_scheme", ARBITER_SCHEMES)
def test_core_breakdowns_sum_to_tick_totals(arb_scheme):
    cfg = small_config(arb_scheme, "unicast")
    session = _session(cfg)
    _, _, telem = session.run(_spikes(cfg), telemetry="cores")
    per_tick, per_core = telem.per_tick, telem.per_core
    assert per_core.events.shape == (TICKS, cfg.cores)
    assert bool(jnp.all(jnp.sum(per_core.events, axis=-1) == per_tick.events))
    assert bool(jnp.all(jnp.sum(per_core.noc_hops, axis=-1) == per_tick.noc_hops))
    assert bool(jnp.all(jnp.max(per_core.encode_latency, axis=-1) == per_tick.encode_latency))
    np.testing.assert_allclose(
        np.asarray(jnp.sum(per_core.encode_energy, axis=-1)),
        np.asarray(per_tick.encode_energy),
        rtol=REL,
    )
    totals = telem.core_totals()
    assert totals.events.shape == (cfg.cores,)
    assert float(jnp.sum(totals.events)) == float(jnp.sum(per_tick.events))


def test_chip_hops_attributed_only_on_multichip():
    flat = small_config(ARBITER_SCHEMES[0], "unicast")
    chips = dataclasses.replace(flat, chips=2)
    _, _, telem_flat = _session(flat).run(_spikes(flat), telemetry="cores")
    _, acc, telem_chips = _session(chips).run(_spikes(chips), telemetry="cores")
    assert float(jnp.sum(telem_flat.per_core.chip_hops)) == 0.0
    chip_sums = jnp.sum(telem_chips.per_core.chip_hops, axis=-1)
    assert bool(jnp.all(chip_sums == telem_chips.per_tick.chip_hops))
    assert float(acc.chip_hops) > 0, "2-chip random fabric should cross chips"
    assert float(jnp.sum(telem_chips.per_core.chip_hops)) == float(acc.chip_hops)


def test_run_batched_telemetry_shapes_and_sums():
    cfg = small_config(ARBITER_SCHEMES[1], NOC_SCHEMES[2])
    session = _session(cfg)
    spikes = _spikes(cfg, lead=(3,))
    cur, acc, telem = session.run_batched(spikes, telemetry="ticks")
    assert cur.shape == spikes.shape[:2] + (cfg.cores, cfg.neurons_per_core)
    assert telem.per_tick.events.shape == (3, TICKS)
    assert acc.events.shape == (3,)
    _assert_sums_back(acc, telem.per_tick, "batched")
    _, _, core_telem = session.run_batched(spikes, telemetry="cores")
    assert core_telem.per_core.events.shape == (3, TICKS, cfg.cores)
    core_sums = jnp.sum(core_telem.per_core.events, axis=-1)
    assert bool(jnp.all(core_sums == core_telem.per_tick.events))


# ---- telemetry: validation -------------------------------------------------


def test_unknown_telemetry_mode_raises():
    cfg = small_config(ARBITER_SCHEMES[0], NOC_SCHEMES[0])
    session = _session(cfg)
    with pytest.raises(ValueError, match="unknown telemetry mode"):
        session.run(_spikes(cfg), telemetry="bogus")
    with pytest.raises(ValueError, match="unknown telemetry mode"):
        obs_telemetry.validate_mode("per_neuron")


def test_telemetry_rejects_sharded_runs():
    cfg = dataclasses.replace(small_config(ARBITER_SCHEMES[0], "unicast"), chips=2)
    session = _session(cfg)
    with pytest.raises(ValueError, match="shard"):
        session.run(_spikes(cfg), shard="chips", telemetry="ticks")


def test_stepstats_mean_rejects_degenerate_ticks():
    acc = StepStats.zeros()
    for bad in (0, -3, 0.0, float("nan")):
        with pytest.raises(ValueError, match="positive tick count"):
            acc.mean(bad)
    with pytest.raises(ValueError, match="positive tick count"):
        acc.summary(ticks=0)
    assert acc.summary(ticks=4)["events"] == 0.0
    assert acc.summary()["events"] == 0.0  # totals need no tick count


# ---- trace -----------------------------------------------------------------


def test_tracer_records_nested_spans(tmp_path):
    tracer = obs_trace.Tracer("test-proc")
    with tracer:
        with obs_trace.span("outer", cores=4):
            with obs_trace.span("inner"):
                pass
        tracer.instant("marker", tick=7)
    names = [e["name"] for e in tracer.events]
    assert names == ["inner", "outer", "marker"]  # completion order
    by_name = {e["name"]: e for e in tracer.events}
    assert by_name["outer"]["args"] == {"cores": 4, "depth": 0}
    assert by_name["inner"]["args"] == {"depth": 1}
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    payload = json.loads(path.read_text())
    assert payload["traceEvents"][0]["ph"] == "M"
    assert payload["traceEvents"][0]["args"]["name"] == "test-proc"
    assert {e["name"] for e in payload["traceEvents"][1:]} == {"outer", "inner", "marker"}
    assert all(e["ph"] in ("X", "i") for e in payload["traceEvents"][1:])


def test_tracer_keeps_each_threads_span_depth():
    """Pump and producer threads record into one tracer: every event lands
    and each thread's nesting depth stays its own."""
    tracer = obs_trace.Tracer()

    def work():
        for _ in range(200):
            with obs_trace.span("outer"):
                with obs_trace.span("inner"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(16)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with tracer:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert len(tracer.events) == 16 * 200 * 2
    assert all(e["args"]["depth"] == (e["name"] == "inner") for e in tracer.events)


def test_span_is_noop_without_active_tracer():
    assert obs_trace.active_tracer() is None
    with obs_trace.span("nobody-listening") as t:
        assert t is None


def test_tracer_deactivates_on_exit():
    tracer = obs_trace.Tracer()
    with tracer:
        assert obs_trace.active_tracer() is tracer
    assert obs_trace.active_tracer() is None
    with obs_trace.span("after"):
        pass
    assert tracer.events == []


def test_tracer_clock_is_the_profilers_host_clock(tmp_path):
    """A span's start in the tracer's JSON and its annotation on the
    profile's host plane (``profile_start_time`` + ``start_ns``) agree."""
    from jax.profiler import ProfileData

    tracer = obs_trace.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer, obs_trace.span("probe.align"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    origin = dict(planes["Task Environment"].stats)["profile_start_time"]
    (start_ns,) = [
        ev.start_ns
        for line in planes["/host:CPU"].lines
        for ev in line.events
        if ev.name == "probe.align"
    ]
    payload = tracer.to_chrome_trace()
    (ours,) = [e for e in payload["traceEvents"] if e["name"] == "probe.align"]
    assert abs(ours["ts"] * 1e3 - (origin + start_ns)) < 1e6  # within 1 ms
    assert payload["otherData"]["clock"] == obs_trace.CLOCK


def test_session_compile_and_run_emit_spans():
    cfg = small_config(ARBITER_SCHEMES[0], NOC_SCHEMES[0])
    tracer = obs_trace.Tracer()
    with tracer:
        session = _session(cfg)
        session.run(_spikes(cfg))
        session.run(_spikes(cfg), telemetry="ticks")
    names = [e["name"] for e in tracer.events]
    assert names.count("interface.compile") == 1
    assert names.count("interface.run") == 2
    compile_ev = next(e for e in tracer.events if e["name"] == "interface.compile")
    assert compile_ev["args"]["cores"] == cfg.cores
    telem_ev = [e for e in tracer.events if e["args"].get("telemetry") == "ticks"]
    assert len(telem_ev) == 1


# ---- metrics ---------------------------------------------------------------


def test_exact_percentiles_match_numpy():
    values = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    got = obs_metrics.percentiles(values, qs=(0, 50, 95, 100))
    for q in (0, 50, 95, 100):
        assert got[f"p{q:g}"] == pytest.approx(float(np.percentile(values, q)))
    with pytest.raises(ValueError, match="empty"):
        obs_metrics.percentiles([])
    with pytest.raises(ValueError, match="outside"):
        obs_metrics.percentiles([1.0], qs=(101,))


def test_histogram_percentiles_within_bucket_error():
    rng = np.random.default_rng(0)
    sample = rng.lognormal(mean=0.0, sigma=1.0, size=4000)
    hist = obs_metrics.Histogram("t")
    for v in sample:
        hist.add(v)
    # documented bound: one geometric bucket, ~10**(1/64) - 1 < 4% headroom
    for q in (50, 95, 99):
        exact = float(np.percentile(sample, q))
        assert hist.percentile(q) == pytest.approx(exact, rel=0.04)
    assert hist.count == len(sample)
    assert hist.min == pytest.approx(sample.min())
    assert hist.max == pytest.approx(sample.max())
    assert hist.mean == pytest.approx(sample.mean(), rel=1e-9)
    summary = hist.summary()
    assert set(summary) == {"count", "mean", "min", "max", "p50", "p95", "p99"}


def test_histogram_edge_cases():
    hist = obs_metrics.Histogram("edge")
    with pytest.raises(ValueError, match="empty"):
        hist.percentile(50)
    with pytest.raises(ValueError, match="empty"):
        hist.mean
    hist.add(0.0)  # at/below lo clamps into the lowest bucket, never raises
    hist.add(1e12)  # above hi clamps into the highest bucket
    assert hist.count == 2
    assert hist.min <= hist.percentile(0) <= hist.percentile(100) <= hist.max
    with pytest.raises(ValueError, match="outside"):
        hist.percentile(-1)
    with pytest.raises(ValueError, match="lo"):
        obs_metrics.Histogram("bad", lo=1.0, hi=0.5)


def test_histogram_nonfinite_counted_without_poisoning():
    """Regression: NaN crashed `_bin` (math.log10 ValueError) and Inf
    raised OverflowError - one bad measured duration killed the serve
    path.  Non-finite adds are now counted aside and excluded from every
    statistic."""
    hist = obs_metrics.Histogram("nf")
    hist.add(2.0)
    hist.add(float("nan"))
    hist.add(float("inf"))
    hist.add(float("-inf"))
    assert hist.count == 1 and hist.nonfinite == 3
    assert hist.min == 2.0 and hist.max == 2.0 and hist.mean == 2.0
    summary = hist.summary()
    assert summary["nonfinite"] == 3 and summary["count"] == 1
    assert math.isfinite(summary["p99"])
    other = obs_metrics.Histogram("nf2")
    other.add(float("nan"))
    other.add(3.0)
    merged = hist.merge(other)
    assert merged.nonfinite == 4 and merged.count == 2 and merged.max == 3.0
    clean = obs_metrics.Histogram("clean")
    clean.add(1.0)
    assert "nonfinite" not in clean.summary()


@settings(max_examples=25, deadline=None)
@given(
    st.lists(st.floats(1e-4, 1e4), min_size=1, max_size=64),
    st.lists(st.floats(1e-4, 1e4), min_size=0, max_size=64),
)
def test_histogram_merge_matches_pooled_sample(a, b):
    """merge(h1, h2) == the histogram fed both sample streams.

    Bucket counts, count, min, max (and therefore every percentile, which
    is a pure function of those) must match the pooled histogram exactly;
    totals to float tolerance (summation order legitimately differs).
    The serving tier relies on this to roll per-tenant latency histograms
    into fleet percentiles without retaining samples.
    """
    h1, h2, pooled = (obs_metrics.Histogram(n) for n in ("a", "b", "pooled"))
    for v in a:
        h1.add(v)
        pooled.add(v)
    for v in b:
        h2.add(v)
        pooled.add(v)
    merged = h1.merge(h2)
    assert merged._counts == pooled._counts
    assert merged.count == pooled.count == len(a) + len(b)
    assert merged.min == pooled.min and merged.max == pooled.max
    for q in (0, 50, 95, 99, 100):
        assert merged.percentile(q) == pooled.percentile(q)
    assert merged.mean == pytest.approx(pooled.mean, rel=1e-12)
    # originals are untouched
    assert h1.count == len(a) and h2.count == len(b)


def test_histogram_merge_rejects_mismatched_bucketing():
    base = obs_metrics.Histogram("base")
    for other in (
        obs_metrics.Histogram("lo", lo=1e-3),
        obs_metrics.Histogram("hi", hi=1e3),
        obs_metrics.Histogram("bins", bins_per_decade=32),
    ):
        with pytest.raises(ValueError, match="bucketing"):
            base.merge(other)


def test_counter_registry_and_snapshot():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("ticks").inc()
    reg.counter("ticks").inc(4)
    assert reg.counter("ticks") is reg.counters["ticks"]
    h = reg.histogram("lat_ms")
    assert reg.histogram("lat_ms") is h
    h.add(2.0)
    snap = reg.snapshot()
    assert snap["ticks"] == 5.0
    assert snap["lat_ms"]["count"] == 1
    empty = obs_metrics.MetricsRegistry()
    empty.histogram("unused")
    assert empty.snapshot() == {}  # empty histograms stay out of snapshots


def test_jsonl_sink_roundtrips_through_report_loader(tmp_path):
    path = tmp_path / "metrics.jsonl"
    with obs_metrics.JsonlSink(str(path)) as sink:
        sink.write({"scenario": "sparse_poisson", "new_tick_ms": 0.5})
        sink.write({"scenario": "hotspot_core", "new_tick_ms": 0.9})
    records = obs_report.load_records(str(path))
    assert [r["scenario"] for r in records] == ["sparse_poisson", "hotspot_core"]


# ---- report CLI ------------------------------------------------------------


def _bench_payload():
    stats = {
        "events": 84.5,
        "encode_latency": 18.4,
        "encode_energy": 16.0,
        "cam_searches": 41.0,
        "cam_energy": 23193.4,
        "cam_time_ns": 103.9,
        "noc_hops": 91.4,
        "noc_latency": 12.7,
        "noc_energy": 3198.1,
        "chip_hops": 0.0,
        "chip_latency": 0.0,
        "chip_energy": 0.0,
    }
    record = {
        "cores": 16,
        "neurons_per_core": 256,
        "cam_entries_per_core": 128,
        "ticks": 8,
        "scenario": "sparse_poisson",
        "new_tick_ms": 0.712,
        "tick_ms_p50": 0.82,
        "tick_ms_p95": 0.99,
        "tick_ms_p99": 1.0,
        "stats_per_tick": stats,
    }
    return {
        "benchmark": "interface_session_tick",
        "schema_version": 2,
        "platform": "cpu",
        "jax_version": "0.0-test",
        "git_sha": "cafe" * 10,
        "records": [record],
    }


def test_report_renders_tier_breakdown(tmp_path, capsys):
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(_bench_payload()))
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    for tier in ("arbiter", "cam", "noc", "chip"):
        assert tier in out
    assert "sparse_poisson" in out
    assert "platform cpu" in out
    assert "p99 1.000 ms" in out
    # CAM dominates this record's summed latency: the share column says so
    rows = obs_report.tier_rows(_bench_payload()["records"][0]["stats_per_tick"])
    shares = {tier: share for tier, _, _, _, _, share in rows}
    assert max(shares, key=shares.get) == "cam"
    assert sum(shares.values()) == pytest.approx(1.0)


def test_report_renders_serve_request_latency(tmp_path, capsys):
    path = tmp_path / "serve.jsonl"
    with obs_metrics.JsonlSink(str(path)) as sink:
        sink.write({"tenant": "t0", "ticks": 32, "request_ms_p50": 80.0,
                    "request_ms_p95": 120.5, "request_ms_p99": 150.0, "wait_ms_p95": 70.25,
                    "stats_per_tick": _bench_payload()["records"][0]["stats_per_tick"]})
        sink.write({"tenant": "__fleet__", "ticks": 64, "request_ms_p50": 81.0,
                    "request_ms_p95": 121.0, "request_ms_p99": 151.0, "wait_ms_p95": 71.0})
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "request latency: p50 80.000 ms  p95 120.500 ms  p99 150.000 ms" in out
    assert "(wait p95 70.250 ms)" in out
    assert "__fleet__" in out and "p99 151.000 ms" in out


def test_report_scenario_filter(tmp_path, capsys):
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(_bench_payload()))
    assert obs_report.main([str(path), "--scenario", "sparse_poisson"]) == 0
    assert "sparse_poisson" in capsys.readouterr().out
    assert obs_report.main([str(path), "--scenario", "not_a_scenario"]) == 0
    assert "no reportable records" in capsys.readouterr().out


def test_report_rejects_malformed_input(tmp_path, capsys):
    bad = tmp_path / "bad.txt"
    bad.write_text("definitely { not json\nnor jsonl ]")
    assert obs_report.main([str(bad)]) == 1
    assert "error:" in capsys.readouterr().out
    assert obs_report.main([str(tmp_path / "missing.json")]) == 1
