"""NoC subsystem: topology, multicast trees, link loads, placement, fabric."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cam as cam_mod
from repro.core import fabric
from repro.noc import multicast, placement, router, topology
from hypothesis import given, settings, strategies as st

KEY = jax.random.PRNGKey(0)


def _cfg(cores=4, n=16, entries=32, scheme="multicast_tree"):
    return fabric.FabricConfig(cores=cores, neurons_per_core=n,
                               cam_entries_per_core=entries,
                               noc=topology.NocConfig(scheme))


# ---- topology ---------------------------------------------------------------

def test_mesh_dims_cover_cores():
    for cores in (1, 2, 3, 4, 5, 16, 48, 64):
        w, h = topology.mesh_dims(cores)
        assert w * h >= cores and w >= h


def test_hop_matrix_is_manhattan():
    hm = np.asarray(topology.hop_matrix(4))           # 2x2 mesh
    assert np.array_equal(hm, [[0, 1, 1, 2], [1, 0, 2, 1],
                               [1, 2, 0, 1], [2, 1, 1, 0]])
    hm16 = np.asarray(topology.hop_matrix(16))
    assert np.array_equal(hm16, hm16.T)
    assert np.all(np.diag(hm16) == 0)
    assert hm16.max() == 6                            # corner to corner, 4x4


def test_bad_scheme_rejected():
    with pytest.raises(ValueError):
        topology.NocConfig("warp_drive")


# ---- multicast trees --------------------------------------------------------

def test_single_destination_multicast_equals_unicast():
    """One destination -> the tree is the XY path: hops AND link loads match."""
    cores = 16
    s = 64
    src = jax.random.randint(KEY, (s,), 0, cores)
    dest = jax.random.randint(jax.random.PRNGKey(1), (s,), 0, cores)
    mask = jax.nn.one_hot(dest, cores, dtype=jnp.bool_)
    uni = multicast.unicast_hops(mask, src, cores)
    tree = multicast.multicast_tree_hops(mask, src, cores)
    assert bool(jnp.all(uni == tree))
    lu = router.link_loads(mask, src, cores, "unicast")
    lm = router.link_loads(mask, src, cores, "multicast_tree")
    assert bool(jnp.all(lu == lm))


def test_multicast_never_exceeds_unicast():
    cores = 16
    mask = jax.random.bernoulli(KEY, 0.3, (128, cores))
    src = jax.random.randint(jax.random.PRNGKey(2), (128,), 0, cores)
    uni = multicast.unicast_hops(mask, src, cores)
    tree = multicast.multicast_tree_hops(mask, src, cores)
    assert bool(jnp.all(tree <= uni))
    # per physical link too: the tree counts each link at most once
    lu = router.link_loads(mask, src, cores, "unicast")
    lm = router.link_loads(mask, src, cores, "multicast_tree")
    assert bool(jnp.all(lm <= lu))
    assert bool(jnp.all(lm <= 1.0))


def test_link_loads_sum_to_hop_counts():
    """Per-link tables and closed-form hop counts are the same model."""
    cores = 16
    mask = jax.random.bernoulli(KEY, 0.4, (64, cores))
    src = jax.random.randint(jax.random.PRNGKey(3), (64,), 0, cores)
    for scheme, hop_fn in [
        ("unicast", lambda: multicast.unicast_hops(mask, src, cores)),
        ("multicast_tree",
         lambda: multicast.multicast_tree_hops(mask, src, cores)),
        ("broadcast", lambda: multicast.broadcast_tree_hops(src, cores)),
    ]:
        loads = router.link_loads(mask, src, cores, scheme)
        assert loads.shape[1] == topology.num_links(cores)
        assert bool(jnp.all(jnp.sum(loads, axis=1) == hop_fn()))


def test_subscription_matrix_bruteforce():
    cfg = _cfg()
    params = fabric.random_connectivity(KEY, cfg)
    subs = np.asarray(multicast.subscription_matrix(
        params.tags, params.valid, cfg.cores, cfg.neurons_per_core,
        cfg.tag_bits))
    tags = np.asarray(params.tags)
    valid = np.asarray(params.valid)
    w = 1 << np.arange(cfg.tag_bits - 1, -1, -1)
    srcs = (tags * w).sum(-1)                         # (cores, entries)
    total = cfg.cores * cfg.neurons_per_core
    want = np.zeros((cfg.cores, total), bool)
    for c in range(cfg.cores):
        for e in range(cfg.cam.entries):
            if valid[c, e]:
                want[c, srcs[c, e]] = True
    assert np.array_equal(subs, want)


# ---- fabric rewrite ---------------------------------------------------------

def test_currents_bit_identical_across_schemes():
    """Delivery scheme changes accounting only - never the computation."""
    cfg = _cfg()
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(4), 0.25,
                                  (cfg.cores, cfg.neurons_per_core))
    outs = {}
    for scheme in ("broadcast", "unicast", "multicast_tree"):
        c = dataclasses.replace(cfg, noc=topology.NocConfig(scheme))
        outs[scheme], _ = fabric.step(params, spikes, c)
    assert bool(jnp.all(outs["broadcast"] == outs["unicast"]))
    assert bool(jnp.all(outs["broadcast"] == outs["multicast_tree"]))


def test_broadcast_stats_match_seed_accounting():
    """`scheme="broadcast"` reproduces the seed flood model exactly."""
    cfg = _cfg(scheme="broadcast")
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(5), 0.25,
                                  (cfg.cores, cfg.neurons_per_core))
    _, st = fabric.step(params, spikes, cfg)
    events = float(jnp.sum(spikes))
    assert float(st.events) == events
    assert float(st.cam_searches) == events * cfg.cores
    # recompute the seed energy formula from first principles
    w = 1 << np.arange(cfg.tag_bits - 1, -1, -1)
    srcs = (np.asarray(params.tags) * w).sum(-1)
    spiking = set(np.flatnonzero(np.asarray(spikes).reshape(-1)))
    hits = sum(int(srcs[c, e] in spiking)
               for c in range(cfg.cores)
               for e in np.flatnonzero(np.asarray(params.valid)[c]))
    searches = events * cfg.cores
    match = hits / searches
    mismatch = float(np.asarray(params.valid).sum(1).mean()) - match
    want = searches * float(cam_mod._energy_jnp(cfg.cam, match, mismatch))
    assert float(st.cam_energy) == pytest.approx(want, rel=1e-5)
    assert float(st.cam_time_ns) == pytest.approx(
        searches * cam_mod.cycle_time_ns(cfg.cam), rel=1e-6)


def test_mesh_accounting_never_exceeds_broadcast():
    cfg = _cfg(cores=16)
    params = fabric.random_connectivity(KEY, cfg, fan_in=0.5)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(6), 0.2,
                                  (cfg.cores, cfg.neurons_per_core))
    _, st_b = fabric.step(params, spikes, dataclasses.replace(
        cfg, noc=topology.NocConfig("broadcast")))
    _, st_m = fabric.step(params, spikes, cfg)
    assert float(st_m.cam_searches) < float(st_b.cam_searches)
    assert float(st_m.noc_hops) < float(st_b.noc_hops)
    assert float(st_m.cam_energy) < float(st_b.cam_energy)
    assert float(st_m.noc_energy) < float(st_b.noc_energy)


def test_prebuilt_tables_match_inline():
    cfg = _cfg()
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(7), 0.3,
                                  (cfg.cores, cfg.neurons_per_core))
    tables = fabric.noc_tables(params, cfg)
    cur_a, st_a = fabric.step(params, spikes, cfg)
    cur_b, st_b = fabric.step(params, spikes, cfg, tables=tables)
    assert bool(jnp.all(cur_a == cur_b))
    for a, b in zip(st_a, st_b):
        assert bool(jnp.all(a == b))


def test_snn_accounting_reports_noc_stats():
    from repro.models import snn
    cfg = snn.SNNConfig(fabric=_cfg(cores=2, entries=32), d_in=8, d_out=4,
                        t_steps=4)
    params, topo = snn.init_snn(KEY, cfg)
    x = jnp.ones((2, cfg.t_steps, cfg.d_in)) * 3.0
    _, _, stats = snn.snn_forward(params, topo, x, cfg, account=True)
    assert stats is not None
    for field in ("noc_hops", "noc_latency", "noc_energy"):
        assert float(getattr(stats, field)) > 0.0


# ---- placement --------------------------------------------------------------

def test_optimized_placement_not_worse_than_random():
    """On fixed connectivity, greedy never loses to random/identity."""
    cores, n = 16, 16
    cfg = _cfg(cores=cores, n=n, entries=4 * n)
    params = placement.clustered_connectivity(0, cfg, cluster_size=n, fan_in=4)
    a = placement.fanout_adjacency(params, cfg)
    total = cores * n
    greedy = placement.greedy_overlap_placement(a, cores, n)
    c_greedy = placement.traffic_cost(a, greedy, cores, n)
    for seed in (1, 2, 3):
        rand = placement.random_placement(seed, total)
        assert c_greedy <= placement.traffic_cost(a, rand, cores, n)
        assert (placement.cam_search_count(a, greedy, cores, n)
                <= placement.cam_search_count(a, rand, cores, n))
    assert c_greedy <= placement.traffic_cost(
        a, placement.identity_placement(total), cores, n)


def test_greedy_recovers_hidden_clusters():
    """Cluster-per-core workloads collapse to zero inter-core traffic."""
    cores, n = 4, 16
    cfg = _cfg(cores=cores, n=n, entries=4 * n)
    params = placement.clustered_connectivity(3, cfg, cluster_size=n, fan_in=4)
    a = placement.fanout_adjacency(params, cfg)
    greedy = placement.greedy_overlap_placement(a, cores, n)
    assert placement.traffic_cost(a, greedy, cores, n) == 0.0


def test_placement_is_a_permutation():
    cores, n = 4, 8
    cfg = _cfg(cores=cores, n=n, entries=2 * n)
    params = fabric.random_connectivity(KEY, cfg)
    a = placement.fanout_adjacency(params, cfg)
    perm = placement.greedy_overlap_placement(a, cores, n)
    assert sorted(perm.tolist()) == list(range(cores * n))


def test_apply_placement_preserves_currents():
    """Re-placing neurons permutes the current vector, nothing else."""
    cores, n = 4, 8
    cfg = _cfg(cores=cores, n=n, entries=2 * n)
    params = fabric.random_connectivity(KEY, cfg, fan_in=0.7)
    total = cores * n
    spikes = jax.random.bernoulli(jax.random.PRNGKey(8), 0.3, (cores, n))
    cur0, _ = fabric.step(params, spikes, cfg)

    perm = placement.random_placement(11, total)
    p2, cfg2 = placement.apply_placement(params, cfg, perm)
    flat = np.asarray(spikes).reshape(-1)
    sp2 = np.zeros(total, dtype=bool)
    sp2[perm] = flat
    cur2, _ = fabric.step(p2, jnp.asarray(sp2.reshape(cores, n)), cfg2)
    want = np.zeros(total, np.float32)
    want[perm] = np.asarray(cur0).reshape(-1)
    assert np.allclose(np.asarray(cur2).reshape(-1), want, atol=1e-5)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**16))
def test_greedy_placement_is_valid_permutation_property(seed):
    """Optimizer output is a bijection onto [0, total) for any wiring."""
    cores, n = 4, 8
    cfg = _cfg(cores=cores, n=n, entries=2 * n)
    params = fabric.random_connectivity(jax.random.PRNGKey(seed), cfg)
    a = placement.fanout_adjacency(params, cfg)
    perm = placement.greedy_overlap_placement(a, cores, n)
    assert np.array_equal(np.sort(perm), np.arange(cores * n))


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**16))
def test_optimized_cost_not_worse_than_identity_property(seed):
    cores, n = 4, 8
    cfg = _cfg(cores=cores, n=n, entries=4 * n)
    params = placement.clustered_connectivity(seed, cfg, cluster_size=n,
                                              fan_in=3)
    a = placement.fanout_adjacency(params, cfg)
    greedy = placement.greedy_overlap_placement(a, cores, n)
    ident = placement.identity_placement(cores * n)
    assert (placement.traffic_cost(a, greedy, cores, n)
            <= placement.traffic_cost(a, ident, cores, n))


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**16))
def test_traffic_cost_invariant_under_relabeling(seed):
    """Costs depend on where neurons are placed, never on their labels:
    relabeling the input wiring (cluster ids included) and transporting
    the placement through the relabeling leaves every objective fixed."""
    cores, n = 4, 8
    total = cores * n
    rng = np.random.RandomState(seed)
    cfg = _cfg(cores=cores, n=n, entries=4 * n)
    params = placement.clustered_connectivity(seed, cfg, cluster_size=n,
                                              fan_in=3)
    a = placement.fanout_adjacency(params, cfg)
    perm = placement.random_placement(seed + 1, total)

    q = rng.permutation(total)               # old label -> new label
    inv = np.argsort(q)
    a_rel = a[inv][:, inv]                   # a_rel[q[s], q[d]] == a[s, d]
    perm_rel = np.empty(total, dtype=np.int64)
    perm_rel[q] = perm                       # same physical placement
    assert placement.traffic_cost(a_rel, perm_rel, cores, n) == \
        placement.traffic_cost(a, perm, cores, n)
    assert placement.cam_search_count(a_rel, perm_rel, cores, n) == \
        placement.cam_search_count(a, perm, cores, n)


def test_identity_placement_preserves_entry_content():
    cores, n = 2, 8
    cfg = _cfg(cores=cores, n=n, entries=2 * n)
    params = fabric.random_connectivity(KEY, cfg, fan_in=1.0)  # all valid
    p2, cfg2 = placement.apply_placement(
        params, cfg, placement.identity_placement(cores * n))
    assert cfg2.cam.entries == cfg.cam.entries
    assert bool(jnp.all(p2.tags == params.tags))
    assert bool(jnp.all(p2.valid == params.valid))
    assert bool(jnp.all(p2.targets == params.targets))
    assert bool(jnp.all(p2.weights == params.weights))
