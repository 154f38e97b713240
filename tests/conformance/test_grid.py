"""Differential conformance grid: scenarios x arbiters x NoC x paths.

Fast run: every registered scenario exercises all six execution paths on
a deterministically sampled pair of (arbiter, NoC) grid cells, plus a
`hypothesis`-sampled oracle-vs-event sweep over the 5x3 cell
grid.  The full grid (every cell, every scenario) runs under ``-m slow``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import traffic
from repro.core import fabric
from hypothesis import given, settings, strategies as st
from tests.conformance import paths

TICKS = 3
SEED = 17
SCENARIOS = traffic.scenario_names()


def _sampled_cells(index: int, count: int = 2):
    """Deterministic per-scenario grid cells; together they cover most of
    the 15-cell grid across the scenario list (full coverage under slow)."""
    return [paths.GRID[(count * index + 7 * k) % len(paths.GRID)] for k in range(count)]


def _setup(arb_scheme, noc_scheme, scenario, ticks=TICKS):
    cfg = paths.small_config(arb_scheme, noc_scheme)
    params = fabric.random_connectivity(jax.random.PRNGKey(SEED), cfg)
    spikes = traffic.generate(scenario, SEED + 1, ticks, cfg)
    return cfg, params, spikes


# The heavyweight scenarios (10-35s each: dense or clustered streams hit
# the sparse paths' worst case) conform under ``-m slow``; the fast lane
# keeps the cheap ones for per-commit path coverage.
_SLOW_SCENARIOS = {"clustered", "mixture", "dvs_trace", "hotspot_core",
                   "synchronized_burst"}


@pytest.mark.parametrize(
    "scenario",
    [pytest.param(s, marks=(pytest.mark.slow,) if s in _SLOW_SCENARIOS else ())
     for s in SCENARIOS],
)
def test_scenario_conforms_across_all_paths(scenario):
    """Acceptance: currents bit-identical across oracle / event / pallas /
    pallas_sparse / chips>1 / sharded-vmap for every registered scenario."""
    index = SCENARIOS.index(scenario)
    for arb_scheme, noc_scheme in _sampled_cells(index):
        cfg, params, spikes = _setup(arb_scheme, noc_scheme, scenario)
        results = paths.run_paths(cfg, params, spikes)
        paths.assert_conformant(results, label=f"{scenario}/{arb_scheme}/{noc_scheme}")


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**16))
def test_sampled_grid_oracle_vs_event(sample):
    """Sampled 5x3 grid cells: oracle and event paths agree on every
    StepStats field (the cheap pair, so the sampler can range widely) -
    `assert_conformant` covers the transport fields too, since both
    paths share the flat partitioning."""
    arb_scheme, noc_scheme = paths.GRID[sample % len(paths.GRID)]
    scenario = SCENARIOS[sample % len(SCENARIOS)]
    cfg, params, spikes = _setup(arb_scheme, noc_scheme, scenario, ticks=2)
    results = paths.run_paths(cfg, params, spikes, names=("oracle", "event"))
    paths.assert_conformant(results, label=f"{scenario}/{arb_scheme}/{noc_scheme}")


@pytest.mark.slow
@pytest.mark.parametrize("noc_scheme", paths.NOC_SCHEMES)
def test_full_grid(noc_scheme):
    """The full conformance grid: every scenario through every arbiter
    for this NoC scheme, all six paths.  Sessions are compiled once per
    grid cell and reused across scenarios (spikes are data, not trace)."""
    from repro.interface import Interface

    for arb_scheme in paths.ARBITER_SCHEMES:
        cfg = paths.small_config(arb_scheme, noc_scheme)
        params = fabric.random_connectivity(jax.random.PRNGKey(SEED), cfg)
        session = Interface(cfg).compile(params)
        session_p = Interface(dataclasses.replace(cfg, impl="pallas")).compile(params)
        session_s = Interface(dataclasses.replace(cfg, impl="pallas_sparse")).compile(params)
        session_c = Interface(dataclasses.replace(cfg, chips=2)).compile(params)
        for scenario in SCENARIOS:
            spikes = traffic.generate(scenario, SEED + 1, TICKS, cfg)
            results = {
                "oracle": paths.run_oracle(cfg, params, spikes),
                "event": session.run(spikes),
                "pallas": session_p.run(spikes),
                "pallas_sparse": session_s.run(spikes),
                "chips2": session_c.run(spikes),
                "chips2_sharded": session_c.run(spikes, shard="chips"),
            }
            paths.assert_conformant(results, label=f"{scenario}/{arb_scheme}/{noc_scheme}")


def test_traffic_matches_expected_rate():
    """Scenario rate metadata is honest: empirical rate within 5 sigma."""
    cores, n, ticks = 4, 16, 256
    for scenario in SCENARIOS:
        spikes = traffic.generate(scenario, 3, ticks, (cores, n))
        rate = traffic.expected_rate(scenario, cores, n)
        emp = float(jnp.mean(spikes))
        # mixture/burst frames are correlated within a tick; widen by the
        # per-tick worst case instead of assuming independent samples
        sigma = max((rate * (1.0 - rate) / (ticks * cores * n)) ** 0.5, 0.5 / ticks**0.5 * 0.1)
        assert abs(emp - rate) < 5.0 * sigma + 0.02, (scenario, emp, rate)


def test_generators_are_jit_able():
    for scenario in SCENARIOS:
        spec = traffic.get_scenario(scenario)
        fn = jax.jit(lambda key, s=spec: s.generate(key, 4, 4, 16, **s.defaults))
        out = fn(jax.random.PRNGKey(0))
        assert out.shape == (4, 4, 16) and out.dtype == jnp.bool_


def test_scenario_registry_validation():
    with pytest.raises(KeyError, match="sparse_poisson"):
        traffic.get_scenario("no_such_scenario")
    with pytest.raises(ValueError, match="valid"):
        traffic.generate("sparse_poisson", 0, 2, (4, 16), bogus=1)
    with pytest.raises(ValueError, match="leaf"):
        traffic.generate("mixture", 0, 2, (4, 16), components=(("mixture", 1.0),))
    with pytest.raises(ValueError, match="does not match"):
        traffic.register_scenario(
            "misnamed", dataclasses.replace(traffic.get_scenario("sparse_poisson"))
        )
    spec = traffic.get_scenario("sparse_poisson")
    with pytest.raises(ValueError, match="already registered"):
        traffic.register_scenario("sparse_poisson", spec)
