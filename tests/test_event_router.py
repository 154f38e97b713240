"""HAT-style MoE event router: capacity semantics + combine correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import event_router as er

KEY = jax.random.PRNGKey(0)


def test_no_drop_combine_is_weighted_identity():
    logits = jax.random.normal(KEY, (32, 8))
    r = er.hat_route(logits, k=2, capacity=64)
    assert bool(r.kept.all())
    x = jax.random.normal(KEY, (32, 16))
    y = er.combine(er.dispatch(x, r), r, 32)
    assert jnp.allclose(y, x, atol=1e-5)


def test_capacity_drops_are_fifo_by_token():
    """Earlier tokens win slots - the AER arbitration order."""
    t, e = 16, 2
    logits = jnp.stack([jnp.ones((t,)) * 5.0, jnp.zeros((t,))], axis=1)
    r = er.hat_route(logits, k=1, capacity=4)  # all want expert 0
    kept_tokens = np.nonzero(np.array(r.kept[:, 0]))[0]
    assert list(kept_tokens) == [0, 1, 2, 3]


def test_load_counts():
    logits = jax.random.normal(KEY, (64, 8))
    r = er.hat_route(logits, k=2, capacity=64)
    assert int(r.load.sum()) == 64 * 2
    ids = np.array(r.expert_ids).reshape(-1)
    want = np.bincount(ids, minlength=8)
    assert np.array_equal(np.array(r.load), want)


def test_buffer_rows_consistent_with_event_slot():
    logits = jax.random.normal(KEY, (32, 4))
    r = er.hat_route(logits, k=2, capacity=8)
    buf = np.array(r.buffer_rows)
    ids = np.array(r.expert_ids)
    slots = np.array(r.event_slot)
    kept = np.array(r.kept)
    for tkn in range(32):
        for j in range(2):
            if kept[tkn, j]:
                assert buf[ids[tkn, j], slots[tkn, j]] == tkn


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(st.integers(1, 64), st.integers(2, 16), st.integers(1, 4),
       st.integers(0, 2 ** 31 - 1))
def test_positions_never_exceed_capacity(t, e, k, seed):
    k = min(k, e)
    logits = jax.random.normal(jax.random.PRNGKey(seed), (t, e))
    cap = max(1, (t * k) // e)
    r = er.hat_route(logits, k=k, capacity=cap)
    slots = np.array(r.event_slot)
    kept = np.array(r.kept)
    assert (slots[kept] < cap).all()
    assert (slots[kept] >= 0).all()
    # per-expert kept count <= capacity
    buf = np.array(r.buffer_rows)
    assert ((buf >= 0).sum(axis=1) <= cap).all()


def test_hierarchical_scan_matches_flat():
    logits = jax.random.normal(KEY, (64, 16))
    r1 = er.hat_route(logits, k=2, capacity=16, use_hierarchical_scan=False)
    r2 = er.hat_route(logits, k=2, capacity=16, use_hierarchical_scan=True)
    assert bool((r1.event_slot == r2.event_slot).all())
    assert bool((r1.buffer_rows == r2.buffer_rows).all())
