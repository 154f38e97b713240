"""The sparse event tick: compaction, policies, fallback, kernel.

`tests/conformance` already holds the whole ``impl="pallas_sparse"``
session bit-identical to the dense oracle across the grid; this file
covers the pieces in isolation - the sort-free event compaction, the
sparse arbiter/encode policies against their dense counterparts, the
event-indexed accounting, the overflow-to-dense `lax.cond`, and the
dispatch-layer validation errors.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arbiter as arb
from repro.core import fabric
from repro.interface import Interface, pipeline
from repro.interface.config import InterfaceConfig
from repro.interface.registry import get_arbiter
from repro.kernels.sparse_tick import ops as sparse_ops

KEY = jax.random.PRNGKey(0)
SPARSE_SCHEMES = ("binary_tree", "greedy_tree", "token_ring", "hier_ring",
                  "hier_tree")

# Same contract as tests/conformance: per-tick stats are bit-identical,
# but across differently-jitted scans XLA may fuse the accumulate chain
# differently (FMA), so accumulated counts are exact and energies agree
# to the conformance tolerance.
EXACT_FIELDS = ("events", "cam_searches", "noc_hops", "chip_hops")


def _assert_stats_close(a, b):
    for f in a._fields:
        va, vb = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        if f in EXACT_FIELDS:
            np.testing.assert_array_equal(va, vb, err_msg=f)
        else:
            np.testing.assert_allclose(va, vb, rtol=1e-6, err_msg=f)


def _frame(key, cores=4, n=64, p=0.1):
    return jax.random.bernoulli(key, p, (cores, n))


# ---- compaction --------------------------------------------------------------

def test_compact_events_matches_nonzero():
    spikes = _frame(KEY, p=0.2)
    buf, counts = sparse_ops.compact_events(spikes, capacity=32)
    for c in range(spikes.shape[0]):
        want = np.flatnonzero(np.asarray(spikes[c]))
        got = np.asarray(buf[c])
        assert int(counts[c]) == want.size
        np.testing.assert_array_equal(got[: want.size], want)
        assert (got[want.size:] == spikes.shape[1]).all()  # pad value is n


def test_compact_events_edge_counts():
    n = 16
    empty = jnp.zeros((1, n), bool)
    buf, counts = sparse_ops.compact_events(empty, capacity=4)
    assert int(counts[0]) == 0 and bool((buf == n).all())

    # exactly-capacity frame still carries one trailing pad slot
    exact = jnp.zeros((1, n), bool).at[0, :4].set(True)
    buf, counts = sparse_ops.compact_events(exact, capacity=4)
    assert buf.shape == (1, 5)
    assert int(counts[0]) == 4 and int(buf[0, -1]) == n

    # overflow: counts exceed capacity, buffer is truncated
    full = jnp.ones((1, n), bool)
    buf, counts = sparse_ops.compact_events(full, capacity=4)
    assert int(counts[0]) == n and bool((buf[0] == jnp.arange(5)).all())


def test_event_indices_weights_and_bases():
    spikes = jnp.array([[0, 1, 0, 1], [1, 0, 0, 0]], bool)
    buf, _ = sparse_ops.compact_events(spikes, capacity=2)
    ev_idx, ev_w = sparse_ops.event_indices(buf, 4)
    np.testing.assert_array_equal(np.asarray(ev_w), [1, 1, 1, 0])
    np.testing.assert_array_equal(np.asarray(ev_idx), [1, 3, 4, 0])


def test_resolve_capacity():
    assert sparse_ops.resolve_capacity(None, 256) == 32
    assert sparse_ops.resolve_capacity(None, 16) == sparse_ops.MIN_CAPACITY
    assert sparse_ops.resolve_capacity(100, 16) == 15   # clamped to n - 1
    assert sparse_ops.resolve_capacity(3, 256) == 3
    with pytest.raises(ValueError, match="positive"):
        sparse_ops.resolve_capacity(0, 256)


# ---- sparse policies vs dense policies ---------------------------------------

@pytest.mark.parametrize("scheme", SPARSE_SCHEMES)
def test_sparse_policies_match_dense(scheme):
    n = 64
    cfg = arb.ArbiterConfig(scheme, n)
    ctx = arb.make_context(cfg)
    entry = get_arbiter(scheme)
    lat_fn = entry.sparse_tick_latency(ctx)
    enc_fn = entry.sparse_encode_energy(ctx)
    assert lat_fn is not None and enc_fn is not None
    for seed, p in ((1, 0.02), (2, 0.1), (3, 0.4)):
        spikes = _frame(jax.random.PRNGKey(seed), cores=8, n=n, p=p)
        buf, counts = sparse_ops.compact_events(spikes, capacity=n - 1)
        dense_lat = arb.batched_tick_latency(cfg, spikes)
        lat = lat_fn(buf, counts[:, None])
        assert lat.shape == (8, 1), lat.shape     # policies stay 2-D
        assert bool((lat[:, 0] == dense_lat).all()), (scheme, p)
        dense_enc = jax.vmap(lambda s: arb.encode_energy_units(
            scheme, n, pipeline._hat_order(s, n)[0]))(spikes)
        enc = enc_fn(buf, counts[:, None])
        assert enc.shape == (8, 1), enc.shape
        assert bool((enc[:, 0] == dense_enc).all()), (scheme, p)


def test_unsupported_schemes_return_none():
    # greedy_tree at n=2 has no backlog closed form; hier_ring needs a
    # square address space - both refuse rather than approximate
    ctx = arb.make_context(arb.ArbiterConfig("greedy_tree", 2))
    assert get_arbiter("greedy_tree").sparse_tick_latency(ctx) is None
    ctx = arb.make_context(arb.ArbiterConfig("hier_ring", 8))
    assert get_arbiter("hier_ring").sparse_tick_latency(ctx) is None


# ---- arbiter kernel: ref vs kernel -------------------------------------------

def _tick_operands(cores=4, n=32, p=0.15, scheme="hier_tree"):
    cfg = InterfaceConfig(cores=cores, neurons_per_core=n,
                          cam_entries_per_core=2 * n, scheme=scheme)
    spikes = _frame(jax.random.PRNGKey(5), cores, n, p)
    lat_fn, enc_fn, _, capacity = pipeline.resolve_sparse_plan(cfg)
    buf, counts = sparse_ops.compact_events(spikes, capacity)
    return (buf, counts), dict(latency_fn=lat_fn, encode_fn=enc_fn)


@pytest.mark.parametrize("cores", [4, 16, 24])
def test_kernel_matches_ref(cores):
    # 16 and 24 cores take the multi-program grid (blocks of 8 cores)
    operands, kw = _tick_operands(cores=cores)
    want = sparse_ops.sparse_tick(*operands, impl="xla", **kw)
    got = sparse_ops.sparse_tick(*operands, impl="pallas", interpret=True,
                                 **kw)
    for w, g in zip(want, got):
        assert w.shape == g.shape == (cores,) and bool((w == g).all())


def test_sparse_tick_validation():
    operands, kw = _tick_operands()
    with pytest.raises(ValueError, match="impl"):
        sparse_ops.sparse_tick(*operands, impl="cuda", **kw)
    buf, counts = operands
    with pytest.raises(ValueError, match="one count per core"):
        sparse_ops.sparse_tick(buf[:-1], counts, **kw)
    with pytest.raises(ValueError, match="one count per core"):
        sparse_ops.sparse_tick(buf.reshape(-1), counts, **kw)


# ---- overflow fallback and config plumbing -----------------------------------

def test_overflow_falls_back_to_dense():
    cfg = InterfaceConfig(cores=4, neurons_per_core=16,
                          cam_entries_per_core=32, sparse_capacity=2)
    params = fabric.random_connectivity(KEY, cfg)
    dense = Interface(cfg).compile(params)
    sparse = Interface(dataclasses.replace(
        cfg, impl="pallas_sparse")).compile(params)
    # ticks alternate under and over the 2-event budget: the lax.cond
    # takes both branches inside one scan, results identical throughout
    spikes = jnp.stack([
        jnp.zeros((4, 16), bool).at[0, 3].set(True),
        jnp.ones((4, 16), bool),
        jnp.zeros((4, 16), bool),
        jax.random.bernoulli(jax.random.PRNGKey(9), 0.5, (4, 16)),
    ])
    cd, sd = dense.run(spikes)
    cs, ss = sparse.run(spikes)
    assert bool((cd == cs).all())
    _assert_stats_close(sd, ss)


def test_empty_frame_zero_stats():
    cfg = InterfaceConfig(cores=4, neurons_per_core=16,
                          cam_entries_per_core=32, impl="pallas_sparse")
    params = fabric.random_connectivity(KEY, cfg)
    currents, stats = Interface(cfg).compile(params).run(
        jnp.zeros((2, 4, 16), bool))
    assert not currents.any()
    for f in stats._fields:
        assert float(getattr(stats, f)) == 0.0, f


def test_config_validation():
    with pytest.raises(ValueError, match="impl"):
        InterfaceConfig(impl="pallas_dense")
    with pytest.raises(ValueError, match="sparse_capacity"):
        InterfaceConfig(sparse_capacity=0)
    with pytest.raises(ValueError, match="sparse_capacity"):
        fabric.FabricConfig(sparse_capacity=-1)
    # legacy round-trip preserves the knob
    cfg = InterfaceConfig(sparse_capacity=7, impl="pallas_sparse")
    assert InterfaceConfig.from_fabric(cfg.fabric()).sparse_capacity == 7


def test_session_refuses_unsupported_scheme():
    cfg = InterfaceConfig(cores=4, neurons_per_core=8,
                          cam_entries_per_core=16, scheme="hier_ring",
                          impl="pallas_sparse")
    params = fabric.random_connectivity(KEY, cfg)
    with pytest.raises(ValueError, match="hier_ring"):
        Interface(cfg).compile(params)


def test_masked_batched_composition():
    cfg = InterfaceConfig(cores=4, neurons_per_core=16,
                          cam_entries_per_core=32, impl="pallas_sparse")
    params = fabric.random_connectivity(KEY, cfg)
    session = Interface(cfg).compile(params)
    dense = Interface(dataclasses.replace(cfg, impl="xla")).compile(params)
    batch = jax.random.bernoulli(jax.random.PRNGKey(11), 0.15, (2, 5, 4, 16))
    mask = jnp.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], bool)
    cs, ss = session.run_batched(batch, mask=mask)
    cd, sd = dense.run_batched(batch, mask=mask)
    assert bool((cs == cd).all())
    _assert_stats_close(sd, ss)


def test_hat_pad_boundary_at_exact_capacity():
    # a frame holding exactly `capacity` events exercises the trailing
    # pad slot the HAT encode-energy boundary toggle depends on
    n, cap = 16, 4
    cfg = arb.ArbiterConfig("hier_tree", n)
    ctx = arb.make_context(cfg)
    enc_fn = get_arbiter("hier_tree").sparse_encode_energy(ctx)
    spikes = jnp.zeros((1, n), bool).at[0, jnp.array([1, 5, 9, 13])].set(True)
    buf, counts = sparse_ops.compact_events(spikes, cap)
    assert int(counts[0]) == cap
    dense = arb.encode_energy_units(
        "hier_tree", n, pipeline._hat_order(spikes[0], n)[0])
    assert float(enc_fn(buf, counts[:, None])[0, 0]) == float(dense)


def test_default_capacity_heuristic():
    assert sparse_ops.default_capacity(256) == 32
    assert sparse_ops.default_capacity(64) == sparse_ops.MIN_CAPACITY
    assert math.log2(sparse_ops.CAPACITY_DIVISOR).is_integer()
