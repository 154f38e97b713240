"""CAM model: functional search semantics + behavioural PPA calibration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cam, ppa

KEY = jax.random.PRNGKey(0)


# ---- functional semantics ---------------------------------------------------

@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 32), st.integers(2, 12), st.integers(0, 2 ** 31 - 1))
def test_search_matches_bruteforce(entries, bits, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    tags = jax.random.bernoulli(k1, 0.5, (entries, bits)).astype(jnp.int32)
    valid = jax.random.bernoulli(k2, 0.8, (entries,))
    query = jax.random.bernoulli(k3, 0.5, (bits,)).astype(jnp.int32)
    got = cam.search(tags, valid, query)
    want = np.array([bool(v) and bool((np.array(t) == np.array(query)).all())
                     for t, v in zip(tags, valid)])
    assert np.array_equal(np.array(got), want)


def test_first_match_and_write():
    arr = cam.CamArray(cam.CamConfig(entries=8, bits=4))
    arr = arr.write(3, [1, 0, 1, 1]).write(5, [1, 0, 1, 1])
    assert int(arr.first_match(jnp.array([1, 0, 1, 1]))) == 3
    assert int(arr.first_match(jnp.array([0, 0, 0, 0]))) == 8  # no match
    m = arr.search(jnp.array([1, 0, 1, 1]))
    assert int(m.sum()) == 2  # multi-match fan-out (synapse semantics)


def test_mismatch_bit_counts():
    tags = jnp.array([[0, 0, 0], [1, 1, 1], [1, 0, 0]])
    q = jnp.array([1, 0, 0])
    counts = cam.mismatch_bit_counts(tags, q)
    assert counts.tolist() == [1, 2, 0]


# ---- paper-calibrated PPA ----------------------------------------------------

@pytest.mark.parametrize("entries", [16, 512])
def test_cycle_time_improvement_matches_paper(entries):
    assert cam.cycle_improvement(entries) == pytest.approx(
        ppa.CAM_CYCLE_IMPROVEMENT[entries], abs=1e-3)


def test_cscd_monotonic_mechanism_stack():
    """Each mechanism must strictly reduce cycle time (Fig. 10 ordering)."""
    e = 512
    t_conv = cam.cycle_time_ns(cam.CamConfig(e, cscd=False, feedback=False,
                                             speculative=False))
    t_cscd = cam.cycle_time_ns(cam.CamConfig(e, feedback=False,
                                             speculative=False))
    t_fb = cam.cycle_time_ns(cam.CamConfig(e, speculative=False))
    t_full = cam.cycle_time_ns(cam.CamConfig(e))
    assert t_conv > t_cscd > t_fb > t_full


def test_energy_savings_match_paper_endpoints():
    assert cam.energy_saving("all_match") == pytest.approx(0.358, abs=2e-3)
    assert cam.energy_saving("all_mismatch") == pytest.approx(0.402, abs=2e-3)


def test_energy_random_documented_gap():
    """Reproduction finding (DESIGN.md/cam.py): the paper's 46.7% random-
    search saving is not consistent with its own endpoint numbers under a
    linear energy model; the calibrated model lands at ~40%."""
    s = cam.energy_saving("random")
    assert 0.38 < s < 0.42
    assert s < ppa.CAM_ENERGY_SAVING["random"]


@pytest.mark.parametrize("entries", [16, 512])
def test_area_matches_paper(entries):
    base, prop = ppa.CAM_AREA_UM2[entries]
    assert cam.area_um2(cam.CamConfig(entries, cscd=False, feedback=False,
                                      speculative=False)) == pytest.approx(base, rel=1e-3)
    assert cam.area_um2(cam.CamConfig(entries)) == pytest.approx(prop, rel=1e-3)


def test_area_overhead_shrinks_with_scale():
    """+8.9% at 16 entries -> +5.2% at 512 (paper §IV-D 'Area')."""
    def ovh(e):
        b = cam.area_um2(cam.CamConfig(e, cscd=False, feedback=False,
                                       speculative=False))
        p = cam.area_um2(cam.CamConfig(e))
        return p / b - 1
    assert ovh(16) == pytest.approx(0.089, abs=0.005)
    assert ovh(512) == pytest.approx(0.052, abs=0.005)
    assert ovh(512) < ovh(16)


def test_spec_sense_probability_formula():
    """Paper §IV-B: last 3 of 10 bits -> 87.6%."""
    assert ppa.spec_sense_close_probability(10, 3) == pytest.approx(0.876,
                                                                    abs=5e-4)


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 16), st.integers(1, 8), st.integers(0, 2 ** 31 - 1))
def test_spec_sense_probability_monte_carlo(bits, sense, seed):
    """Empirical frequency matches the EXACT conditional closed form.

    (The paper's published expression approximates it: equal to within
    2^-N, i.e. indistinguishable at the paper's N=10 design point but
    visibly different at toy widths - a documented repro finding.)"""
    if sense >= bits:
        sense = bits - 1
    rng = np.random.default_rng(seed)
    stored = rng.integers(0, 2, (4000, bits))
    query = rng.integers(0, 2, (4000, bits))
    mism = (stored != query)
    is_mismatch = mism.any(axis=1)
    closed = mism[:, -sense:].any(axis=1)
    if is_mismatch.sum() == 0:
        return
    emp = (closed & is_mismatch).sum() / is_mismatch.sum()
    pred = ppa.spec_sense_close_probability_exact(bits, sense)
    assert emp == pytest.approx(pred, abs=0.05)
    # paper formula agrees with the exact one at the paper's design point
    assert ppa.spec_sense_close_probability(10, 3) == pytest.approx(
        ppa.spec_sense_close_probability_exact(10, 3), abs=1e-3)
