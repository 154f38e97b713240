"""Multi-chip fabric: chip tier, hierarchy tables, and sharded sessions.

Covers the PR acceptance criteria:
  * a ``chips=1`` config is bit-identical to the pre-existing flat-core
    path across all five arbiter schemes and all three NoC schemes
    (property-style via `hypothesis`),
  * currents are invariant under chip partitioning (the chip tier changes
    transport accounting, never the CAM-match semantics),
  * ``run(shard="chips")`` on a chips=4 x cores_per_chip=4 config is
    bit-identical to the unsharded oracle (vmap fallback in-process; the
    real `shard_map` mesh path runs on 8 fake devices in a slow
    subprocess test),
  * chips/cores/cores_per_chip reconciliation and stale-tables validation.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core import fabric, ppa
from repro.interface import Interface, InterfaceConfig, StepStats, ppa_report
from repro.interface import pipeline as interface_pipeline
from repro.noc import hierarchy, topology
from hypothesis import given, settings, strategies as st

KEY = jax.random.PRNGKey(0)
NOC_SCHEMES = ("broadcast", "unicast", "multicast_tree")
ARBITER_SCHEMES = ("binary_tree", "greedy_tree", "token_ring", "hier_ring",
                   "hier_tree")


def _cfg(chips=1, cores=8, n=16, entries=32, arbiter="hier_tree",
         noc="multicast_tree"):
    return InterfaceConfig(cores=cores, neurons_per_core=n,
                           cam_entries_per_core=entries, scheme=arbiter,
                           noc=topology.NocConfig(noc), chips=chips)


# ---- chips=1 == pre-existing flat-core path ---------------------------------


@pytest.mark.slow
@settings(max_examples=2, deadline=None)
@given(st.integers(0, 2**16), st.floats(0.05, 0.6))
def test_chips1_bit_identical_to_flat_path(seed, rate):
    """chips=1 sessions reproduce the flat fabric.step path, tick for
    tick, across all five arbiter schemes and all three NoC schemes."""
    for arbiter in ARBITER_SCHEMES:
        for noc in NOC_SCHEMES:
            cfg = _cfg(chips=1, cores=4, arbiter=arbiter, noc=noc)
            params = fabric.random_connectivity(jax.random.PRNGKey(seed), cfg)
            spikes = jax.random.bernoulli(
                jax.random.PRNGKey(seed + 1), rate,
                (2, cfg.cores, cfg.neurons_per_core))
            currents, acc = Interface(cfg).compile(params).run(spikes)

            tables = fabric.noc_tables(params, cfg)
            ref = StepStats.zeros()
            for i in range(2):
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    cur_i, st_i = fabric.step(params, spikes[i], cfg, tables)
                assert bool(jnp.all(currents[i] == cur_i)), (arbiter, noc, i)
                ref = ref.accumulate(st_i)
            for name in StepStats._fields:
                assert float(getattr(acc, name)) == pytest.approx(
                    float(getattr(ref, name)), rel=1e-6), (arbiter, noc, name)
            # a flat fabric has no chip tier to pay for
            assert float(acc.chip_hops) == 0.0
            assert float(acc.chip_latency) == 0.0
            assert float(acc.chip_energy) == 0.0


# ---- chip partitioning ------------------------------------------------------


def test_currents_invariant_under_chip_partitioning():
    """Splitting 16 cores into 1/2/4 chips never changes the currents:
    the chip tier re-routes delivery, not the CAM-match semantics."""
    flat = _cfg(chips=1, cores=16)
    params = fabric.random_connectivity(KEY, flat)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3,
                                  (3, 16, flat.neurons_per_core))
    ref, ref_acc = Interface(flat).compile(params).run(spikes)
    for chips in (2, 4):
        cfg = _cfg(chips=chips, cores=16)
        cur, acc = Interface(cfg).compile(params).run(spikes)
        assert bool(jnp.all(cur == ref)), chips
        # CAM accounting is delivery-independent too
        assert float(acc.events) == float(ref_acc.events)
        assert float(acc.cam_searches) == float(ref_acc.cam_searches)
        # cross-chip subscriptions exist at this density: the tier is paid
        assert float(acc.chip_hops) > 0.0
        assert float(acc.chip_energy) == pytest.approx(
            float(acc.chip_hops) * ppa.CHIP_HOP_ENERGY)


def test_event_driven_tick_matches_oracle_with_chips():
    """The dense-sweep + DES oracle and the event-driven path agree on
    every StepStats field (chip tier included) on a multi-chip fabric."""
    cfg = _cfg(chips=4, cores=16)
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(2), 0.3,
                                  (cfg.cores, cfg.neurons_per_core))
    cur, st = interface_pipeline.interface_tick(params, spikes, cfg)
    ref_cur, ref_st = interface_pipeline.interface_tick(params, spikes, cfg,
                                                        oracle=True)
    assert bool(jnp.all(cur == ref_cur))
    for name in StepStats._fields:
        assert float(getattr(st, name)) == float(getattr(ref_st, name)), name


# ---- sharded execution ------------------------------------------------------


def test_sharded_run_matches_unsharded_oracle():
    """Acceptance: chips=4 x cores_per_chip=4, run(shard="chips") currents
    bit-identical to the unsharded oracle (vmap fallback on one device)."""
    cfg = InterfaceConfig(chips=4, cores_per_chip=4, neurons_per_core=16,
                          cam_entries_per_core=32)
    assert cfg.cores == 16
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(3), 0.3,
                                  (4, cfg.cores, cfg.neurons_per_core))
    session = Interface(cfg).compile(params)
    cur, acc = session.run(spikes)
    cur_s, acc_s = session.run(spikes, shard="chips")
    assert bool(jnp.all(cur == cur_s))
    # oracle reference too, not just the event-driven unsharded path
    cur_o, _ = interface_pipeline.interface_tick(params, spikes[0], cfg,
                                                 oracle=True)
    assert bool(jnp.all(cur_s[0] == cur_o))
    for name in StepStats._fields:
        assert float(getattr(acc_s, name)) == pytest.approx(
            float(getattr(acc, name)), rel=1e-5), name


def test_sharded_run_batched_matches():
    cfg = InterfaceConfig(chips=2, cores_per_chip=4, neurons_per_core=16,
                          cam_entries_per_core=32)
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(4), 0.3,
                                  (2, 3, cfg.cores, cfg.neurons_per_core))
    session = Interface(cfg).compile(params)
    cur, acc = session.run_batched(spikes)
    cur_s, acc_s = session.run_batched(spikes, shard="chips")
    assert bool(jnp.all(cur == cur_s))
    assert acc_s.events.shape == (2,)
    assert bool(jnp.all(acc.events == acc_s.events))


def test_sharded_pallas_session_matches_xla():
    """shard="chips" always takes the XLA gather match; a pallas-impl
    session stays bit-identical under sharding."""
    cfg = InterfaceConfig(chips=2, cores_per_chip=2, neurons_per_core=16,
                          cam_entries_per_core=32, impl="pallas")
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(5), 0.3,
                                  (2, cfg.cores, cfg.neurons_per_core))
    session = Interface(cfg).compile(params)
    cur, _ = session.run(spikes)
    cur_s, _ = session.run(spikes, shard="chips")
    assert bool(jnp.all(cur == cur_s))


def test_shard_on_flat_config_falls_back():
    cfg = _cfg(chips=1, cores=4)
    params = fabric.random_connectivity(KEY, cfg)
    spikes = jax.random.bernoulli(jax.random.PRNGKey(6), 0.3,
                                  (2, cfg.cores, cfg.neurons_per_core))
    session = Interface(cfg).compile(params)
    cur, _ = session.run(spikes)
    cur_s, _ = session.run(spikes, shard="chips")
    assert bool(jnp.all(cur == cur_s))
    with pytest.raises(ValueError, match="shard"):
        session.run(spikes, shard="cores")


@pytest.mark.slow
def test_shard_map_mesh_path_matches_on_fake_devices():
    """The real shard_map route (8 fake CPU devices, one per chip) keeps
    currents bit-identical; stats agree to float tolerance."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = src
    body = textwrap.dedent("""
        import jax, jax.numpy as jnp
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core import fabric
        from repro.interface import Interface, InterfaceConfig, StepStats
        cfg = InterfaceConfig(chips=4, cores_per_chip=4, neurons_per_core=16,
                              cam_entries_per_core=32)
        params = fabric.random_connectivity(jax.random.PRNGKey(0), cfg)
        sp = jax.random.bernoulli(jax.random.PRNGKey(1), 0.25, (3, 16, 16))
        s = Interface(cfg).compile(params)
        cur, acc = s.run(sp)
        cur_s, acc_s = s.run(sp, shard="chips")
        assert bool(jnp.all(cur == cur_s)), "sharded currents drifted"
        for f in StepStats._fields:
            a, b = float(getattr(acc, f)), float(getattr(acc_s, f))
            assert abs(a - b) <= 1e-4 * max(1.0, abs(a)), (f, a, b)
        spb = jax.random.bernoulli(jax.random.PRNGKey(2), 0.25, (2, 3, 16, 16))
        cb, _ = s.run_batched(spb)
        cbs, _ = s.run_batched(spb, shard="chips")
        assert bool(jnp.all(cb == cbs))
        print("MESH_OK")
    """)
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "MESH_OK" in r.stdout


@pytest.mark.slow
def test_sharded_serve_group_on_fake_mesh_matches_solo():
    """A shard="chips" serving group on a real mesh (4 fake CPU devices):
    its accumulators live beside the mesh-placed per-chip constants, and
    served currents equal the unsharded solo runs."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = src
    body = textwrap.dedent("""
        import jax, numpy as np
        assert len(jax.devices()) == 4, jax.devices()
        from repro.interface import Interface, InterfaceConfig
        from repro.serve import ServeEngine, TenantSpec, default_connectivity
        cfg = InterfaceConfig(chips=4, cores_per_chip=2, neurons_per_core=16,
                              cam_entries_per_core=32)
        engine = ServeEngine(flush_ticks=4, flush_deadline_s=0.0,
                             keep_currents=True)
        specs = [TenantSpec(f"s{i}", cfg, seed=i, shard="chips")
                 for i in range(2)]
        for spec in specs:
            engine.register(spec)
            engine.submit_scenario(spec.name, 6)
        engine.drain()
        group = next(iter(engine.groups.values()))
        assert group.session.chip_mesh is not None, "took the vmap fallback"
        assert group.jit_cache_entries() == 1
        solo = Interface(cfg).compile(default_connectivity(cfg, 0))
        for spec in specs:
            cur, _ = solo.run(spec.stream(6, round=0))
            assert np.array_equal(engine.currents(spec.name), np.asarray(cur))
        assert engine.accounting()["closes"]
        print("SERVE_MESH_OK")
    """)
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SERVE_MESH_OK" in r.stdout


# ---- several simulated chips per device -------------------------------------

BLOCKS_RUN = """
import json
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from repro.core import fabric
from repro.interface import Interface, InterfaceConfig, StepStats
from repro.serve import ServeEngine, TenantSpec, default_connectivity

def cfg_of(chips):
    return InterfaceConfig(chips=chips, cores_per_chip=2, neurons_per_core=16,
                           cam_entries_per_core=32)

def stats_gap(a, b):
    return max(float(jnp.max(jnp.abs(getattr(a, f) - getattr(b, f))
                             / jnp.maximum(1.0, jnp.abs(getattr(a, f)))))
               for f in StepStats._fields)

out = {}
for chips in (16, 8, 6):
    cfg = cfg_of(chips)
    s = Interface(cfg).compile(
        fabric.random_connectivity(jax.random.PRNGKey(chips), cfg))
    sp = jax.random.bernoulli(jax.random.PRNGKey(1), 0.25,
                              (2, 3, cfg.cores, 16))
    cur, acc = s.run(sp[0])
    cur_s, acc_s = s.run(sp[0], shard="chips")
    cb, ab = s.run_batched(sp)
    cbs, abs_ = s.run_batched(sp, shard="chips")
    out[chips] = {"mesh": s.chip_mesh.devices.size,
                  "run_currents": bool(jnp.all(cur == cur_s)),
                  "batched_currents": bool(jnp.all(cb == cbs)),
                  "stats_gap": max(stats_gap(acc, acc_s),
                                   stats_gap(ab, abs_)),
                  "replicated": len(s.state_sharding("chips").device_set)}

cfg = cfg_of(16)
engine = ServeEngine(flush_ticks=4, flush_deadline_s=0.0, keep_currents=True)
specs = [TenantSpec(f"s{i}", cfg, seed=i, shard="chips") for i in range(2)]
for spec in specs:
    engine.register(spec)
    engine.submit_scenario(spec.name, 6)
engine.drain()
group = next(iter(engine.groups.values()))
solo = Interface(cfg).compile(default_connectivity(cfg, 0))
out["serve"] = {
    "mesh": group.session.chip_mesh.devices.size,
    "matches_solo": all(
        np.array_equal(engine.currents(spec.name),
                       np.asarray(solo.run(spec.stream(6, round=0))[0]))
        for spec in specs),
    "closes": bool(engine.accounting()["closes"])}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def blocks_run():
    """Sharded sessions with more simulated chips than the 4 fake CPU
    devices of a child process; the JSON of what each one did."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", BLOCKS_RUN], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("chips", [16, 8])
def test_several_chips_per_device_match_the_unsharded_session(blocks_run, chips):
    """16 and 8 simulated chips on 4 devices: a mesh of all 4, each device
    holding chips / 4 of them; currents bit-identical to the unsharded
    session, stats to float tolerance."""
    got = blocks_run[str(chips)]
    assert got["mesh"] == 4 and got["replicated"] == 4
    assert got["run_currents"] and got["batched_currents"]
    assert got["stats_gap"] <= 1e-5, got


def test_mesh_is_the_largest_divisor_of_the_chips_that_fits(blocks_run):
    """6 chips on 4 devices take a mesh of 3, two chips a device."""
    got = blocks_run["6"]
    assert got["mesh"] == 3 and got["replicated"] == 3
    assert got["run_currents"] and got["batched_currents"]
    assert got["stats_gap"] <= 1e-5, got


def test_sharded_serve_group_of_16_chips_on_4_devices_matches_solo(blocks_run):
    got = blocks_run["serve"]
    assert got == {"mesh": 4, "matches_solo": True, "closes": True}


def test_one_device_keeps_the_vmap_fallback():
    cfg = _cfg(chips=16, cores=32)
    session = Interface(cfg).compile(fabric.random_connectivity(KEY, cfg))
    spikes = jax.random.bernoulli(jax.random.PRNGKey(7), 0.3,
                                  (2, 2, cfg.cores, cfg.neurons_per_core))
    cur, _ = session.run_batched(spikes)
    cur_s, _ = session.run_batched(spikes, shard="chips")
    assert session.chip_mesh is None
    assert bool(jnp.all(cur == cur_s))


def test_chip_mesh_asks_for_devices_not_chips():
    from repro.launch import mesh as launch_mesh

    n = len(jax.devices())
    assert launch_mesh.make_chip_mesh(n).devices.size == n
    with pytest.raises(ValueError, match=f"need {n + 1} devices"):
        launch_mesh.make_chip_mesh(n + 1)


# ---- hierarchy tables & routing index ---------------------------------------


def test_build_tables_dispatches_on_chips():
    cfg = _cfg(chips=4, cores=16)
    params = fabric.random_connectivity(KEY, cfg)
    tables = interface_pipeline.build_tables(params, cfg)
    assert isinstance(tables, hierarchy.HierTables)
    assert tables.chips == 4 and tables.cores_per_chip == 4
    flat = interface_pipeline.build_tables(
        params, dataclasses.replace(cfg, chips=1))
    assert not isinstance(flat, hierarchy.HierTables)
    # the subscription matrix is tier-independent
    assert bool(jnp.all(tables.subs == flat.subs))
    assert bool(jnp.all(tables.dest_counts == flat.dest_counts))


def test_stale_chip_tables_raise():
    cfg = _cfg(chips=4, cores=16)
    params = fabric.random_connectivity(KEY, cfg)
    stale = interface_pipeline.build_tables(
        params, dataclasses.replace(cfg, chips=2))
    spikes = jnp.zeros((cfg.cores, cfg.neurons_per_core), bool)
    with pytest.raises(ValueError, match="chips"):
        interface_pipeline.interface_tick(params, spikes, cfg, stale)


def test_routing_index_resolves_chip_core_neuron():
    cfg = _cfg(chips=4, cores=16)
    params = fabric.random_connectivity(KEY, cfg)
    idx = interface_pipeline.build_routing_index(params, cfg)
    n = cfg.neurons_per_core
    core_g = idx.src_idx // n
    assert bool(jnp.all(idx.src_chip == core_g // cfg.cores_per_chip))
    assert bool(jnp.all(idx.src_core == core_g % cfg.cores_per_chip))
    assert int(jnp.max(idx.src_chip)) < cfg.chips
    # flat config: everything lives on chip 0
    flat_idx = interface_pipeline.build_routing_index(
        params, dataclasses.replace(cfg, chips=1))
    assert int(jnp.max(flat_idx.src_chip)) == 0


def test_local_only_connectivity_pays_no_chip_hops():
    """When every CAM entry subscribes to a source on its own chip, the
    inter-chip tier is free (mesh schemes; broadcast still floods)."""
    cfg = _cfg(chips=2, cores=8, entries=16)
    n, cpc = cfg.neurons_per_core, cfg.cores_per_chip
    local_per_chip = cpc * n
    core = jnp.arange(cfg.cores)
    chip = core // cpc
    # each core's entries point at neuron 0 of its chip's first core
    src = jnp.broadcast_to((chip * local_per_chip)[:, None],
                           (cfg.cores, 16))
    params = fabric.FabricParams(
        tags=fabric.int_to_bits(src, cfg.tag_bits),
        valid=jnp.ones((cfg.cores, 16), bool),
        weights=jnp.ones((cfg.cores, 16), jnp.float32),
        targets=jnp.zeros((cfg.cores, 16), jnp.int32))
    spikes = jnp.ones((cfg.cores, n), bool)
    _, st = Interface(cfg).compile(params).step(spikes)
    assert float(st.chip_hops) == 0.0
    assert float(st.chip_latency) == 0.0


# ---- config reconciliation --------------------------------------------------


@pytest.mark.parametrize("make", [fabric.FabricConfig, InterfaceConfig])
def test_chips_config_reconciliation(make):
    cfg = make(chips=4, cores_per_chip=4, neurons_per_core=16)
    assert cfg.cores == 16 and cfg.cores_per_chip == 4
    cfg = make(cores=16, chips=4, neurons_per_core=16)
    assert cfg.cores_per_chip == 4
    assert make(cores=16, neurons_per_core=16).chips == 1
    with pytest.raises(ValueError, match="divide"):
        make(cores=10, chips=4)
    with pytest.raises(ValueError, match="chips"):
        make(chips=0)
    with pytest.raises(ValueError, match="conflicts"):
        make(cores=10, chips=4, cores_per_chip=4)
    with pytest.raises(ValueError, match="stale"):
        make(cores=16, cores_per_chip=5, neurons_per_core=16)
    # replace() with a stale derived cores_per_chip re-derives from cores
    multi = make(chips=4, cores_per_chip=4, neurons_per_core=16)
    flat = dataclasses.replace(multi, chips=1)
    assert flat.cores == 16 and flat.cores_per_chip == 16
    # ... including on a default-sized config (cores resolves to 4, so
    # replace splits those 4 cores instead of growing the fabric)
    split = dataclasses.replace(make(neurons_per_core=16), chips=2)
    assert split.cores == 4 and split.cores_per_chip == 2


def test_from_fabric_roundtrip_carries_chips():
    fab = fabric.FabricConfig(chips=2, cores_per_chip=4, neurons_per_core=16)
    cfg = InterfaceConfig.from_fabric(fab)
    assert cfg.chips == 2 and cfg.cores == 8 and cfg.cores_per_chip == 4
    back = cfg.fabric()
    assert back.chips == 2 and back.cores == 8


def test_ppa_report_hierarchy_section():
    rep = ppa_report(_cfg(chips=4, cores=16))
    assert rep["config"]["chips"] == 4
    assert rep["config"]["cores_per_chip"] == 4
    h = rep["hierarchy"]
    assert h["chip_mesh_dims"] == topology.mesh_dims(4)
    assert h["chip_links"] == topology.num_links(4)
    assert h["chip_hop_latency_ns"] > rep["noc"]["hop_latency_ns"]
    assert h["chip_hop_energy"] > rep["noc"]["hop_energy"]
    # per-chip local mesh, chips x local links in total
    assert rep["noc"]["mesh_dims"] == topology.mesh_dims(4)
    assert rep["noc"]["links"] == 4 * topology.num_links(4)
