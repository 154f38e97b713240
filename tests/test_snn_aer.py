"""SNN on the simulated fabric + AER encode/decode + PPA accounting."""

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import aer, fabric
from repro.data.pipeline import snn_batch
from repro.models import snn

KEY = jax.random.PRNGKey(0)


def _cfg():
    return snn.SNNConfig(
        fabric=fabric.FabricConfig(cores=2, neurons_per_core=64,
                                   cam_entries_per_core=64),
        d_in=16, d_out=4, t_steps=8)


def test_aer_roundtrip():
    raster = jax.random.bernoulli(KEY, 0.1, (5, 64))
    enc = aer.encode_raster(raster)
    dec = aer.decode_events(enc["addresses"], enc["counts"], 64)
    assert bool(jnp.all(dec == raster))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_address(seed):
    addrs = jax.random.randint(jax.random.PRNGKey(seed), (32,), 0, 256)
    fields = aer.pack_address(addrs, 256)
    assert fields.shape == (32, 4)  # log4(256) levels
    assert bool(jnp.all(aer.unpack_address(fields) == addrs))


def test_routing_matrix_equals_fabric_step():
    cfg = _cfg()
    params, topo = snn.init_snn(KEY, cfg)
    fab = snn.fabric_params(params, topo)
    spikes = jax.random.bernoulli(KEY, 0.1, (2, 64))
    cur_fab, _ = fabric.step(fab, spikes, cfg.fabric)
    r = snn.routing_matrix(fab, cfg.fabric)
    cur_mat = (spikes.reshape(-1).astype(jnp.float32) @ r).reshape(2, 64)
    assert jnp.allclose(cur_fab, cur_mat, atol=1e-4)


def test_snn_trains():
    cfg = _cfg()
    params, topo = snn.init_snn(KEY, cfg)
    batch = snn_batch(KEY, 32, cfg.t_steps, cfg.d_in, cfg.d_out)
    loss_g = jax.jit(jax.value_and_grad(
        lambda p: snn.snn_loss(p, topo, batch, cfg)))
    from repro.optim import adamw
    opt_cfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=1, total_steps=60,
                                weight_decay=0.0)
    opt = adamw.init(opt_cfg, params)
    losses = []
    for _ in range(40):
        loss, grads = loss_g(params)
        params, opt, _ = adamw.update(opt_cfg, grads, opt, params)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9
    assert all(jnp.isfinite(jnp.asarray(losses)))


def test_surrogate_gradient_flows():
    v = jnp.linspace(-2, 2, 9)
    g = jax.vmap(jax.grad(snn.spike_fn))(v)
    assert float(g[4]) > 0.5          # steep near threshold
    assert float(g[0]) < 0.1          # flat far away
    y = snn.spike_fn(v)
    assert bool(jnp.all((y == 0) | (y == 1)))


def test_ppa_accounting_scales_with_activity():
    cfg = _cfg()
    params, topo = snn.init_snn(KEY, cfg)
    quiet = jnp.zeros((2, cfg.t_steps, cfg.d_in))
    loud = jnp.ones((2, cfg.t_steps, cfg.d_in)) * 3.0
    _, _, s_quiet = snn.snn_forward(params, topo, quiet, cfg, account=True)
    _, _, s_loud = snn.snn_forward(params, topo, loud, cfg, account=True)
    assert float(s_loud.events) > float(s_quiet.events)
    assert float(s_loud.cam_energy) >= float(s_quiet.cam_energy)


def test_interface_area_report():
    cfg = _cfg()
    rep = fabric.interface_area_um2(cfg.fabric)
    assert rep["arbiter_units"] == pytest.approx(9.0)  # 3*log4(64)
    assert rep["cam_um2"] > rep["cam_um2_baseline"]    # CSCD adds a bit


def test_lif_kernel_path_matches_surrogate_forward():
    cfg = _cfg()
    params, topo = snn.init_snn(KEY, cfg)
    x = jax.random.bernoulli(KEY, 0.3, (2, cfg.t_steps, cfg.d_in)
                             ).astype(jnp.float32)
    l1, r1, _ = snn.snn_forward(params, topo, x, cfg, impl="xla")
    l2, r2, _ = snn.snn_forward(params, topo, x, cfg, impl="pallas")
    assert jnp.allclose(l1, l2, atol=1e-5)
    assert jnp.allclose(r1, r2, atol=1e-5)
