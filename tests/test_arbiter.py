"""Arbitration architectures: closed forms, DES, paper tables, properties."""

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ppa
from repro.core.arbiter import (Arbiter, ArbiterConfig, SCHEMES,
                                batched_tick_latency, burst_latency_units,
                                encode_energy_units, sparse_latency_units,
                                area_units)

KEY = jax.random.PRNGKey(0)


def _des_frame_latency(arb: Arbiter, frame) -> float:
    """Reference: completion time of a frame via the event-loop simulator."""
    req = jnp.where(frame, 0.0, jnp.inf).astype(jnp.float32)
    grants = arb.simulate(req)
    return float(jnp.where(jnp.any(frame),
                           jnp.max(jnp.where(jnp.isfinite(grants), grants,
                                             0.0)), 0.0))


# ---- paper Table I/II/III closed forms -------------------------------------

@pytest.mark.parametrize("n,expected", [(64, 10), (256, 14)])
def test_table1_binary_sparse(n, expected):
    assert sparse_latency_units("binary_tree", n) == expected


@pytest.mark.parametrize("n,expected", [(64, 6), (256, 8)])
def test_table1_hat_sparse(n, expected):
    assert sparse_latency_units("hier_tree", n) == expected


@pytest.mark.parametrize("n,expected", [(64, 32.5), (256, 128.5)])
def test_table1_token_ring_sparse(n, expected):
    assert sparse_latency_units("token_ring", n) == expected


@pytest.mark.parametrize("n,expected", [(64, 71), (256, 275)])
def test_table2_hat_burst(n, expected):
    assert burst_latency_units("hier_tree", n) == pytest.approx(expected)


@pytest.mark.parametrize("n,expected", [(64, 9), (256, 12)])
def test_table3_hat_area(n, expected):
    assert area_units("hier_tree", n) == pytest.approx(expected)


def test_measured_ns_reproduced_at_design_points():
    """The affine calibration reproduces every published ns/area value."""
    for scheme, (m64, m256) in ppa.MEASURED_SPARSE_NS.items():
        assert ppa.sparse_latency_ns(scheme, 64) == pytest.approx(m64)
        assert ppa.sparse_latency_ns(scheme, 256) == pytest.approx(m256)
    for scheme, (m64, m256) in ppa.MEASURED_BURST_NS.items():
        assert ppa.burst_latency_ns(scheme, 64) == pytest.approx(m64)
    for scheme, (m64, m256) in ppa.MEASURED_AREA_NORM.items():
        assert ppa.area_normalized(scheme, 256) == pytest.approx(m256)


def test_headline_claim_sparse_latency_reduction():
    """'up to 78.3% lower latency': HAT 2.0ns vs HTR 9.2ns at N=256."""
    hat = ppa.sparse_latency_ns("hier_tree", 256)
    htr = ppa.sparse_latency_ns("hier_ring", 256)
    assert 1 - hat / htr == pytest.approx(0.783, abs=0.005)


# ---- discrete-event simulation ---------------------------------------------

@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("n", [64, 256])
def test_des_sparse_matches_theory(scheme, n):
    arb = Arbiter(ArbiterConfig(scheme=scheme, n=n))
    sim = float(arb.sparse_event_latency(KEY, num_trials=n))
    theory = sparse_latency_units(scheme, n)
    # ring schemes: random-position sampling noise; trees: exact
    tol = 0.12 if "ring" in scheme else 1e-6
    assert sim == pytest.approx(theory, rel=tol)


@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("n", [64, 256])
def test_des_burst_matches_theory(scheme, n):
    arb = Arbiter(ArbiterConfig(scheme=scheme, n=n))
    sim = float(arb.burst_latency())
    theory = burst_latency_units(scheme, n)
    assert sim == pytest.approx(theory, rel=0.08)


def test_hat_wins_sparse_and_competitive_burst():
    """The paper's central comparison at N=256."""
    sparse = {s: sparse_latency_units(s, 256) for s in SCHEMES}
    assert min(sparse, key=sparse.get) == "hier_tree"
    burst = {s: burst_latency_units(s, 256) for s in SCHEMES}
    assert burst["hier_tree"] < 1.1 * burst["token_ring"]
    area = {s: area_units(s, 256) for s in SCHEMES}
    assert min(area, key=area.get) == "hier_tree"


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 63), st.integers(0, 63))
def test_des_deterministic(a, b):
    """Same request set -> identical grants (no analog nondeterminism)."""
    arb = Arbiter(ArbiterConfig(scheme="hier_tree", n=64))
    req = jnp.full((64,), jnp.inf).at[a].set(0.0).at[b].set(0.0)
    g1, g2 = arb.simulate(req), arb.simulate(req)
    assert bool(jnp.all(g1 == g2))


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 63), min_size=1, max_size=20, unique=True))
def test_all_requests_served_exactly_once(reqs):
    for scheme in ("hier_tree", "token_ring", "binary_tree"):
        arb = Arbiter(ArbiterConfig(scheme=scheme, n=64))
        req = jnp.full((64,), jnp.inf)
        for r in reqs:
            req = req.at[r].set(0.0)
        grants = arb.simulate(req)
        served = jnp.isfinite(grants)
        assert bool(jnp.all(served[jnp.array(reqs)])), scheme
        inactive = jnp.delete(served, jnp.array(reqs))
        assert not bool(jnp.any(inactive)), scheme


# ---- vectorized tick-latency policies vs. the simulator ---------------------

@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("n", [2, 4, 16, 64, 256])
def test_tick_latency_matches_des_sparse_and_burst(scheme, n):
    """The per-tick policy is bit-exact with the event loop on the frames
    the paper characterizes: isolated sparse events and a full burst."""
    cfg = ArbiterConfig(scheme=scheme, n=n)
    arb = Arbiter(cfg)
    frames = [jnp.zeros((n,), bool).at[p].set(True)
              for p in sorted({0, 1, n // 2, n - 1})]
    frames += [jnp.ones((n,), bool), jnp.zeros((n,), bool)]
    fast = batched_tick_latency(cfg, jnp.stack(frames))
    for i, frame in enumerate(frames):
        assert float(fast[i]) == _des_frame_latency(arb, frame), (scheme, i)


@pytest.mark.parametrize("scheme", SCHEMES)
def test_tick_latency_falls_back_to_des_on_non_square_n(scheme):
    """n=8: sqrt(8) is not integral, so hier_ring's closed form does not
    apply and the dispatcher must fall back to the simulator."""
    cfg = ArbiterConfig(scheme=scheme, n=8)
    arb = Arbiter(cfg)
    frames = [jnp.zeros((8,), bool).at[p].set(True) for p in range(8)]
    frames.append(jnp.ones((8,), bool))
    fast = batched_tick_latency(cfg, jnp.stack(frames))
    for i, frame in enumerate(frames):
        assert float(fast[i]) == _des_frame_latency(arb, frame), (scheme, i)


@settings(max_examples=12, deadline=None)
@given(st.lists(st.integers(0, 63), min_size=0, max_size=48, unique=True))
def test_tick_latency_matches_des_random_frames(reqs):
    frame = jnp.zeros((64,), bool)
    for r in reqs:
        frame = frame.at[r].set(True)
    for scheme in SCHEMES:
        cfg = ArbiterConfig(scheme=scheme, n=64)
        fast = batched_tick_latency(cfg, frame[None, :])
        assert float(fast[0]) == _des_frame_latency(Arbiter(cfg), frame), scheme


def test_hat_encode_energy_below_flat():
    """HAT re-encodes higher levels only on cluster switch (paper §III-B)."""
    seq = jnp.arange(64)  # address-ordered drain
    hat = float(encode_energy_units("hier_tree", 64, seq))
    flat = float(encode_energy_units("binary_tree", 64, seq))
    assert hat < flat  # 6 lines always vs ~2.6 expected
    assert hat == pytest.approx(2 + 2 / 4 + 2 / 16, rel=0.2)
