"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.cam_search import ops as cam_ops, ref as cam_ref
from repro.kernels.hat_encode import ops as hat_ops
from repro.kernels.lif_step import ops as lif_ops
from repro.kernels.moe_dispatch import ops as moe_ops

KEY = jax.random.PRNGKey(0)


# ---- cam_search --------------------------------------------------------------

@pytest.mark.parametrize("b,e,bits", [(8, 16, 11), (128, 128, 11),
                                      (256, 64, 33), (64, 512, 44)])
def test_cam_search_sweep(b, e, bits):
    k1, k2, k3 = jax.random.split(KEY, 3)
    tags = jax.random.bernoulli(k1, 0.5, (e, bits)).astype(jnp.int32)
    # force some matches by copying tags into queries
    qbits = jax.random.bernoulli(k2, 0.5, (b, bits)).astype(jnp.int32)
    qbits = qbits.at[: min(b, e)].set(tags[: min(b, e)])
    valid = jax.random.bernoulli(k3, 0.9, (e,))
    t_p, q_p = cam_ref.pack_bits(tags), cam_ref.pack_bits(qbits)
    want = cam_ops.cam_search(q_p, t_p, valid, impl="xla")
    got = cam_ops.cam_search(q_p, t_p, valid, impl="pallas", interpret=True)
    assert bool((want == got).all())
    assert int(want.sum()) > 0  # the sweep actually exercises matches


def test_cam_first_match_and_speculative():
    tags = jax.random.bernoulli(KEY, 0.5, (64, 11)).astype(jnp.int32)
    t_p = cam_ref.pack_bits(tags)
    q_p = t_p[:16]
    valid = jnp.ones((64,), bool)
    fm = cam_ops.cam_first_match(q_p, t_p, valid, impl="pallas",
                                 interpret=True)
    assert bool((fm[:16] <= jnp.arange(16)).all())
    spec = cam_ops.cam_search_speculative(q_p, t_p, valid)
    full = cam_ops.cam_search(q_p, t_p, valid)
    assert bool((spec == full).all())


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(0, 2 ** 31 - 1))
def test_cam_pack_bits_roundtrip_words(words, seed):
    bits = words * 32
    x = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5, (5, bits))
    packed = cam_ref.pack_bits(x.astype(jnp.int32))
    assert packed.shape == (5, words)
    # unpack manually and compare
    unpacked = ((packed[..., :, None].astype(jnp.uint32)
                 >> jnp.arange(32, dtype=jnp.uint32)) & 1)
    unpacked = unpacked.reshape(5, bits)
    assert bool((unpacked == x.astype(jnp.uint32)).all())


# ---- hat_encode ---------------------------------------------------------------

@pytest.mark.parametrize("n,row", [(256, 256), (1024, 256), (4096, 128),
                                   (65536, 256)])
@pytest.mark.parametrize("rate", [0.0, 0.05, 1.0])
def test_hat_encode_sweep(n, row, rate):
    spk = jax.random.bernoulli(KEY, rate, (n,))
    rx, cx, ccx = hat_ops.hat_encode(spk, row=row, impl="xla")
    rp, cp, ccp = hat_ops.hat_encode(spk, row=row, impl="pallas",
                                     interpret=True)
    assert bool((rx == rp).all()) and int(cx) == int(cp)
    assert bool((ccx == ccp).all())


def test_hat_encode_stream_is_sorted_actives():
    spk = jax.random.bernoulli(KEY, 0.1, (1024,))
    stream, cnt = hat_ops.encode_stream(spk, impl="pallas", interpret=True)
    active = np.nonzero(np.array(spk))[0]
    assert int(cnt) == len(active)
    assert np.array_equal(np.array(stream[: len(active)]), active)
    assert bool((stream[len(active):] == 1024).all())


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.0, 1.0))
def test_hat_encode_property(seed, rate):
    spk = jax.random.bernoulli(jax.random.PRNGKey(seed), rate, (512,))
    ranks, count, ccounts = hat_ops.hat_encode(spk, row=128, impl="pallas",
                                               interpret=True)
    n_active = int(spk.sum())
    assert int(count) == n_active
    assert int(ccounts.sum()) == n_active
    r = np.array(ranks)
    # active ranks are a permutation of 0..count-1, ascending in address
    act = r[r >= 0]
    assert sorted(act) == list(range(n_active))
    assert list(act) == sorted(act)


# ---- moe_dispatch ---------------------------------------------------------------

@pytest.mark.parametrize("m,e", [(256, 16), (2048, 160), (512, 64),
                                 (4096, 128)])
def test_moe_dispatch_sweep(m, e):
    ids = jax.random.randint(KEY, (m,), 0, e)
    px, lx = moe_ops.dispatch_positions(ids, num_experts=e, impl="xla")
    pp, lp = moe_ops.dispatch_positions(ids, num_experts=e, impl="pallas",
                                        interpret=True)
    assert bool((px == pp).all()) and bool((lx == lp).all())


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 32))
def test_moe_dispatch_property(seed, e):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (256,), 0, e)
    pos, load = moe_ops.dispatch_positions(ids, num_experts=e,
                                           impl="pallas", interpret=True)
    ids_n, pos_n = np.array(ids), np.array(pos)
    # (expert, position) pairs are unique and dense per expert
    for ex in range(e):
        p = np.sort(pos_n[ids_n == ex])
        assert list(p) == list(range(len(p)))
    assert int(load.sum()) == 256


# ---- lif_step --------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 512), (16, 1024), (8, 4096), (32, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lif_step_sweep(shape, dtype):
    v = jax.random.normal(KEY, shape).astype(dtype)
    i = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    vx, sx = lif_ops.lif_step(v, i, decay=0.9, threshold=1.0, impl="xla")
    vp, sp = lif_ops.lif_step(v, i, decay=0.9, threshold=1.0, impl="pallas",
                              interpret=True)
    np.testing.assert_allclose(np.array(vx, np.float32),
                               np.array(vp, np.float32), rtol=1e-2, atol=1e-2)
    assert bool((sx == sp).all())


def test_lif_step_semantics():
    v = jnp.array([[0.5, 2.0, -1.0, 0.95]])
    i = jnp.zeros((1, 4))
    vn, s = lif_ops.lif_step(v, i, decay=1.0, threshold=1.0)
    assert s.tolist() == [[0.0, 1.0, 0.0, 0.0]]
    np.testing.assert_allclose(np.array(vn), [[0.5, 0.0, -1.0, 0.95]],
                               rtol=1e-6)
