"""The unified `repro.interface` API: registries, sessions, invariants.

Covers the PR acceptance criteria:
  * `InterfaceSession.run` currents are bit-identical to the deprecated
    `fabric.step` for all three NoC schemes (property-style over random
    connectivity/spike draws via `hypothesis`),
  * all scheme lookups go through the registries (unknown names fail with
    the registered list; new schemes plug in without touching the fabric),
  * `fabric.step` survives as a deprecated shim,
  * config validation catches cam-entries mismatches and stale NoC tables.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core import cam as cam_mod
from repro.core import fabric
from repro.interface import (
    Interface,
    InterfaceConfig,
    StepStats,
    build_routing_index,
    build_tables,
    ppa_report,
    registry,
)
from repro.interface import pipeline as interface_pipeline
from repro.noc import topology
from hypothesis import given, settings, strategies as st

KEY = jax.random.PRNGKey(0)
SCHEMES = ("broadcast", "unicast", "multicast_tree")


def _cfg(cores=4, n=16, entries=32, scheme="multicast_tree"):
    return fabric.FabricConfig(cores=cores, neurons_per_core=n,
                               cam_entries_per_core=entries,
                               noc=topology.NocConfig(scheme))


def _old_step(params, spikes, cfg, tables=None):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return fabric.step(params, spikes, cfg, tables)


# ---- cross-scheme / cross-API invariants ------------------------------------


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**16), st.floats(0.05, 0.6))
def test_session_bit_identical_to_fabric_step(seed, rate):
    """session.run == old fabric.step, tick for tick, for every scheme."""
    for scheme in SCHEMES:
        cfg = _cfg(scheme=scheme)
        params = fabric.random_connectivity(jax.random.PRNGKey(seed), cfg)
        t = 3
        spikes = jax.random.bernoulli(jax.random.PRNGKey(seed + 1), rate,
                                      (t, cfg.cores, cfg.neurons_per_core))
        session = Interface(cfg).compile(params)
        currents, acc = session.run(spikes)

        tables = fabric.noc_tables(params, cfg)
        ref_stats = StepStats.zeros()
        for i in range(t):
            cur_i, st_i = _old_step(params, spikes[i], cfg, tables)
            assert bool(jnp.all(currents[i] == cur_i)), \
                f"tick {i} currents differ from fabric.step under {scheme!r}"
            ref_stats = ref_stats.accumulate(st_i)
        for name in StepStats._fields:
            assert float(getattr(acc, name)) == pytest.approx(
                float(getattr(ref_stats, name)), rel=1e-5), (scheme, name)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**16), st.floats(0.05, 0.6))
def test_currents_bit_identical_across_schemes(seed, rate):
    """Transport scheme changes accounting only - never the currents."""
    base = _cfg()
    params = fabric.random_connectivity(jax.random.PRNGKey(seed), base)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(seed + 1), rate,
                                  (2, base.cores, base.neurons_per_core))
    outs = {}
    for scheme in SCHEMES:
        cfg = dataclasses.replace(base, noc=topology.NocConfig(scheme))
        outs[scheme], _ = Interface(cfg).compile(params).run(spikes)
    assert bool(jnp.all(outs["broadcast"] == outs["unicast"]))
    assert bool(jnp.all(outs["broadcast"] == outs["multicast_tree"]))


ARBITER_SCHEMES = ("binary_tree", "greedy_tree", "token_ring", "hier_ring",
                   "hier_tree")


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 2**16), st.floats(0.05, 0.6))
def test_event_driven_tick_matches_dense_oracle(seed, rate):
    """Gather/scatter tick == dense-sweep + DES oracle, every StepStats
    field bit-for-bit, across all registered arbiter x NoC schemes and
    non-power-uniform spike patterns (one bursting core, one silent)."""
    for arb_scheme in ARBITER_SCHEMES:
        for noc_scheme in SCHEMES:
            cfg = _cfg(scheme=noc_scheme)
            cfg = dataclasses.replace(cfg, scheme=arb_scheme)
            params = fabric.random_connectivity(jax.random.PRNGKey(seed), cfg)
            spikes = jax.random.bernoulli(
                jax.random.PRNGKey(seed + 1), rate,
                (cfg.cores, cfg.neurons_per_core))
            spikes = spikes.at[0].set(True).at[-1].set(False)  # non-uniform
            cur, st = interface_pipeline.interface_tick(params, spikes, cfg)
            ref_cur, ref_st = interface_pipeline.interface_tick(
                params, spikes, cfg, oracle=True)
            key = (arb_scheme, noc_scheme)
            assert bool(jnp.all(cur == ref_cur)), key
            assert float(st.events) == float(ref_st.events), key
            assert float(st.cam_searches) == float(ref_st.cam_searches), key
            for name in StepStats._fields:
                assert float(getattr(st, name)) == float(
                    getattr(ref_st, name)), key + (name,)


def test_session_reuses_precompiled_routing_index():
    cfg = _cfg()
    params = fabric.random_connectivity(KEY, cfg)
    session = Interface(cfg).compile(params)
    ref = build_routing_index(params, session.config)
    assert bool(jnp.all(session.routing.src_idx == ref.src_idx))
    assert bool(jnp.all(session.routing.active == ref.active))
    # out-of-range tags are masked out, in-range indices reproduce the tags
    total = cfg.cores * cfg.neurons_per_core
    assert int(jnp.max(session.routing.src_idx)) < total


def test_impl_pallas_session_matches_xla():
    """The cam_search/hat_encode kernel route (interpret mode on CPU) is
    bit-identical to the XLA gather path, stats included."""
    cfg = InterfaceConfig(cores=4, neurons_per_core=16,
                          cam_entries_per_core=32)
    cfg_p = dataclasses.replace(cfg, impl="pallas")
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(5), 0.3,
                                  (3, cfg.cores, cfg.neurons_per_core))
    cur_x, acc_x = Interface(cfg).compile(params).run(spikes)
    cur_p, acc_p = Interface(cfg_p).compile(params).run(spikes)
    assert bool(jnp.all(cur_x == cur_p))
    for name in StepStats._fields:
        assert float(getattr(acc_x, name)) == float(getattr(acc_p, name)), name


def test_impl_pallas_hat_kernel_path_matches_xla():
    """n=256 engages the hat_encode Pallas kernel (row=256) under vmap."""
    cfg = InterfaceConfig(cores=4, neurons_per_core=256,
                          cam_entries_per_core=64)
    cfg_p = dataclasses.replace(cfg, impl="pallas")
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(6), 0.2,
                                  (cfg.cores, cfg.neurons_per_core))
    cur_x, st_x = Interface(cfg).compile(params).step(spikes)
    cur_p, st_p = Interface(cfg_p).compile(params).step(spikes)
    assert bool(jnp.all(cur_x == cur_p))
    assert float(st_x.encode_energy) == float(st_p.encode_energy)


@pytest.mark.parametrize("make", [fabric.FabricConfig, InterfaceConfig])
def test_config_rejects_unknown_impl(make):
    with pytest.raises(ValueError, match="impl"):
        make(impl="cuda")


def test_run_batched_matches_run():
    cfg = _cfg()
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3,
                                  (2, 3, cfg.cores, cfg.neurons_per_core))
    session = Interface(cfg).compile(params)
    cur_b, acc_b = session.run_batched(spikes)
    assert cur_b.shape == spikes.shape[:2] + (cfg.cores, cfg.neurons_per_core)
    assert acc_b.events.shape == (2,)
    for b in range(2):
        cur, acc = session.run(spikes[b])
        assert bool(jnp.all(cur_b[b] == cur))
        assert float(acc_b.events[b]) == float(acc.events)


def test_step_stats_streaming_accumulation():
    z = StepStats.zeros()
    assert all(float(v) == 0.0 for v in z)
    one = StepStats(*[jnp.float32(i + 1) for i in range(len(StepStats._fields))])
    acc = z.accumulate(one).accumulate(one)
    assert float(acc.events) == 2.0 and float(acc.noc_energy) == 18.0
    means = acc.summary(ticks=2)
    assert means["events"] == 1.0 and means["noc_energy"] == 9.0
    totals = acc.summary()
    assert totals["cam_searches"] == 8.0


# ---- deprecated shim --------------------------------------------------------


def test_fabric_step_emits_deprecation_warning():
    cfg = _cfg()
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jnp.zeros((cfg.cores, cfg.neurons_per_core), bool)
    with pytest.warns(DeprecationWarning, match="repro.interface"):
        fabric.step(params, spikes, cfg)


def test_mismatched_tables_raise_value_error():
    """Stale tables fail loudly (formerly an `assert`, gone under -O)."""
    cfg = _cfg(scheme="multicast_tree")
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jnp.zeros((cfg.cores, cfg.neurons_per_core), bool)
    stale = build_tables(params, dataclasses.replace(
        cfg, noc=topology.NocConfig("unicast")))
    with pytest.raises(ValueError) as ei:
        _old_step(params, spikes, cfg, tables=stale)
    assert "unicast" in str(ei.value) and "multicast_tree" in str(ei.value)


# ---- config validation ------------------------------------------------------


@pytest.mark.parametrize("make", [fabric.FabricConfig, InterfaceConfig])
def test_cam_entries_mismatch_rejected(make):
    with pytest.raises(ValueError, match="cam_entries_per_core"):
        make(cam_entries_per_core=64, cam=cam_mod.CamConfig(entries=32))


@pytest.mark.parametrize("make", [fabric.FabricConfig, InterfaceConfig])
def test_cam_entries_agreement_accepted(make):
    cfg = make(cam_entries_per_core=64, cam=cam_mod.CamConfig(entries=64))
    assert cfg.cam.entries == 64 and cfg.cam_entries_per_core == 64
    assert make().cam.entries == 512          # default unchanged
    assert make(cam_entries_per_core=128).cam.entries == 128


def test_interface_config_rejects_unknown_schemes():
    with pytest.raises(ValueError, match="registered"):
        InterfaceConfig(scheme="quantum_arbiter")
    with pytest.raises(ValueError, match="registered"):
        InterfaceConfig(noc=topology.NocConfig("wormhole"))


# ---- registries -------------------------------------------------------------


def test_registries_list_builtins():
    assert set(registry.ARBITERS.names()) >= {
        "binary_tree", "greedy_tree", "token_ring", "hier_ring", "hier_tree"}
    assert set(registry.NOC_SCHEMES.names()) >= set(SCHEMES)
    assert set(registry.CAM_VARIANTS.names()) >= {
        "conventional", "cscd", "cscd+fb", "cscd+ss", "cscd+fb+ss"}


def test_duplicate_registration_rejected():
    entry = registry.NOC_SCHEMES.get("unicast")
    with pytest.raises(ValueError, match="already registered"):
        registry.register_noc_scheme("unicast", entry)
    registry.register_noc_scheme("unicast", entry, overwrite=True)  # explicit


def test_unknown_lookup_names_registered_schemes():
    with pytest.raises(KeyError, match="multicast_tree"):
        registry.get_noc_scheme("no_such_scheme")


def test_new_noc_scheme_plugs_in_without_fabric_edits():
    """A registered scheme flows through NocConfig -> session -> stats."""
    from repro.noc import router as noc_router

    unicast = registry.get_noc_scheme("unicast")
    entry = dataclasses.replace(unicast, name="unicast_copy")
    registry.register_noc_scheme("unicast_copy", entry)
    try:
        cfg = _cfg(scheme="unicast_copy")
        params = fabric.random_connectivity(KEY, cfg)
        spikes = jax.random.bernoulli(jax.random.PRNGKey(2), 0.3,
                                      (1, cfg.cores, cfg.neurons_per_core))
        cur, acc = Interface(cfg).compile(params).run(spikes)
        ref, ref_st = Interface(_cfg(scheme="unicast")).compile(params).run(spikes)
        assert bool(jnp.all(cur == ref))
        assert float(acc.noc_hops) == float(ref_st.noc_hops)
        tables = noc_router.build_tables(
            params.tags, params.valid, cores=cfg.cores,
            neurons_per_core=cfg.neurons_per_core, tag_bits=cfg.tag_bits,
            scheme="unicast_copy")
        assert tables.scheme == "unicast_copy"
    finally:
        registry.NOC_SCHEMES.unregister("unicast_copy")


def test_new_arbiter_plugs_in_and_reports_gracefully():
    """A runtime-registered arbiter simulates, runs, and reports (None
    closed forms) without edits to the simulator, fabric, or report."""
    from repro.core import arbiter as arb

    base = registry.get_arbiter("binary_tree")
    registry.register_arbiter(
        "binary_tree_copy", dataclasses.replace(base, name="binary_tree_copy"))
    try:
        cfg = dataclasses.replace(_cfg(), scheme="binary_tree_copy")
        params = fabric.random_connectivity(KEY, cfg)
        spikes = jax.random.bernoulli(jax.random.PRNGKey(3), 0.3,
                                      (1, cfg.cores, cfg.neurons_per_core))
        cur, _ = Interface(cfg).compile(params).run(spikes)
        ref, _ = Interface(dataclasses.replace(cfg, scheme="binary_tree")
                           ).compile(params).run(spikes)
        assert bool(jnp.all(cur == ref))
        rep = ppa_report(cfg)
        assert rep["arbiter"]["sparse_latency_units"] is None
        assert rep["cam"]["cycle_time_ns"] > 0
        grants = arb.Arbiter(arb.ArbiterConfig("binary_tree_copy", 16)
                             ).simulate(jnp.zeros(16))
        assert bool(jnp.all(jnp.isfinite(grants)))
    finally:
        registry.ARBITERS.unregister("binary_tree_copy")


def test_arbiter_overwrite_does_not_serve_stale_traces():
    """The jit cache is keyed on the entry, not the scheme name."""
    from repro.core import arbiter as arb

    cfg = arb.ArbiterConfig("binary_tree", 16)
    before = arb.Arbiter(cfg).simulate(jnp.zeros(16))
    original = registry.get_arbiter("binary_tree")
    slow = dataclasses.replace(
        original,
        grant_delay=lambda ctx, sel, backlog, th, tl, pa, ga:
            jnp.float32(1000.0))
    registry.register_arbiter("binary_tree", slow, overwrite=True)
    try:
        after = arb.Arbiter(cfg).simulate(jnp.zeros(16))
        assert float(jnp.min(after)) >= 1000.0, "stale trace served"
    finally:
        registry.register_arbiter("binary_tree", original, overwrite=True)
    restored = arb.Arbiter(cfg).simulate(jnp.zeros(16))
    assert bool(jnp.all(restored == before))


def test_custom_cam_variant_via_variant_name():
    base = registry.get_cam_variant("cscd+fb+ss")
    registry.register_cam_variant(
        "slow_cam", dataclasses.replace(base, name="slow_cam",
                                        settle_frac=0.95))
    try:
        fast = cam_mod.CamConfig(entries=64)
        slow = cam_mod.CamConfig(entries=64, variant_name="slow_cam")
        assert cam_mod.cycle_time_ns(slow) > cam_mod.cycle_time_ns(fast)
        # energy model follows the registered entry's flags, not the literal
        assert cam_mod.search_energy(slow, 1.0, 63.0) == pytest.approx(
            cam_mod.search_energy(fast, 1.0, 63.0))
    finally:
        registry.CAM_VARIANTS.unregister("slow_cam")


def test_no_string_scheme_dispatch_in_hot_paths():
    """Acceptance guard: fabric/router/pipeline contain no scheme string-ifs."""
    import inspect
    import re

    from repro.interface import pipeline as pipeline_mod
    from repro.noc import router as noc_router

    pattern = re.compile(
        r"if\s+[^\n]*scheme\s*(==|!=|\bin\b)[^\n]*"
        r"(\"|')(broadcast|unicast|multicast_tree|hier_tree|binary_tree)")
    for mod in (fabric, noc_router, pipeline_mod):
        src = inspect.getsource(mod)
        assert not pattern.search(src), f"string scheme dispatch in {mod.__name__}"


# ---- ppa report -------------------------------------------------------------


def test_ppa_report_unifies_area_latency_energy():
    cfg = _cfg()
    rep = ppa_report(cfg)
    assert rep["config"]["arbiter"] == "hier_tree"
    assert rep["arbiter"]["sparse_latency_units"] == pytest.approx(4.0)  # log2(16)
    assert rep["cam"]["cycle_time_ns"] > 0
    assert rep["cam"]["area_um2"] != rep["cam"]["area_um2_conventional"]
    assert rep["noc"]["links"] == topology.num_links(cfg.cores)
    # the legacy per-core area keys survive inside the unified report
    legacy = fabric.interface_area_um2(cfg)
    assert rep["arbiter"]["area_units"] == legacy["arbiter_units"]
    assert rep["cam"]["area_um2"] == legacy["cam_um2"]
