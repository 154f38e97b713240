"""The paper's own workload: train a multi-core SNN and report what the
core interface costs - comparing HAT against the other arbitration
schemes and the CSCD CAM against the conventional one.

    PYTHONPATH=src python examples/snn_multicore.py

Smoke knobs (used by tests/test_examples.py to keep the example cheap):
SNN_STEPS (train steps), SNN_EVAL_BATCH (accuracy batch size).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses as dc

import jax
import jax.numpy as jnp

from repro.configs import paper_dynaps
from repro.core import arbiter, cam
from repro.data.pipeline import snn_batch
from repro.interface import Interface, ppa_report
from repro.models import snn
from repro.noc import placement, topology
from repro.optim import adamw

STEPS = int(os.environ.get("SNN_STEPS", "40"))
EVAL_BATCH = int(os.environ.get("SNN_EVAL_BATCH", "128"))


def main():
    cfg = paper_dynaps.smoke_config()
    params, topo = snn.init_snn(jax.random.PRNGKey(0), cfg)
    opt_cfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=80,
                                weight_decay=0.0)
    opt = adamw.init(opt_cfg, params)
    loss_g = jax.jit(jax.value_and_grad(
        lambda p, b: snn.snn_loss(p, topo, b, cfg)))

    print(f"[snn] {cfg.fabric.cores} cores x {cfg.fabric.neurons_per_core} "
          f"neurons, CAM {cfg.fabric.cam.entries}x{cfg.fabric.cam.bits}")
    key = jax.random.PRNGKey(1)
    for step in range(STEPS):
        key, sub = jax.random.split(key)
        batch = snn_batch(sub, 32, cfg.t_steps, cfg.d_in, cfg.d_out)
        loss, grads = loss_g(params, batch)
        params, opt, _ = adamw.update(opt_cfg, grads, opt, params)
        if step % 10 == 0:
            print(f"  step {step:2d} loss {float(loss):.4f}")

    # accuracy
    batch = snn_batch(jax.random.PRNGKey(99), EVAL_BATCH, cfg.t_steps,
                      cfg.d_in, cfg.d_out)
    logits, rates, stats = snn.snn_forward(params, topo, batch["x"], cfg,
                                           account=True)
    acc = float(jnp.mean(jnp.argmax(logits, -1) == batch["y"]))
    print(f"[snn] accuracy {acc:.2%}, mean rate {float(rates.mean()):.3f}")

    # --- core-interface report (the paper's PPA story) ---------------------
    n = cfg.fabric.neurons_per_core
    print("\n[interface] per-tick stats (trained network):")
    for k, v in stats._asdict().items():
        print(f"  {k:16s} {float(v):10.2f}")

    print("\n[interface] arbitration alternatives at this core size:")
    for scheme in arbiter.SCHEMES:
        sp = arbiter.sparse_latency_units(scheme, n)
        ar = arbiter.area_units(scheme, n)
        print(f"  {scheme:12s} sparse {sp:7.1f} units  area {ar:6.1f} arbiters")

    print("\n[interface] CAM variants (512x11, per-search energy units):")
    for name, c in {
        "conventional": cam.CamConfig(512, cscd=False, feedback=False,
                                      speculative=False),
        "proposed (CSCD+fb+ss)": cam.CamConfig(512),
    }.items():
        e = cam.search_energy(c, n_match=1, n_mismatch=511)
        t = cam.cycle_time_ns(c)
        print(f"  {name:22s} energy {e:8.1f}  cycle {t:5.2f} ns")

    # --- NoC: what the inter-core transport costs on this trained net ------
    # one precompiled session per transport scheme; same spikes, and the
    # currents are bit-identical across sessions (tested invariant)
    fab = snn.fabric_params(params, topo)
    sp = jax.random.bernoulli(jax.random.PRNGKey(3), float(rates.mean()),
                              (cfg.fabric.cores, cfg.fabric.neurons_per_core))
    print("\n[noc] transport schemes (same spikes, same currents):")
    for scheme in ("broadcast", "unicast", "multicast_tree"):
        c2 = dc.replace(cfg.fabric, noc=topology.NocConfig(scheme))
        _, st2 = Interface(c2).compile(fab).step(sp)
        print(f"  {scheme:14s} cam_searches {float(st2.cam_searches):8.0f}"
              f"  noc_hops {float(st2.noc_hops):7.0f}"
              f"  noc_energy {float(st2.noc_energy):9.0f}")

    # --- unified static PPA report (area / latency / energy per config) ----
    rep = ppa_report(cfg.fabric)
    print("\n[ppa] unified interface report:")
    for section in ("arbiter", "cam", "noc"):
        vals = ", ".join(f"{k}={v:.3g}" if isinstance(v, float)
                         else f"{k}={v}" for k, v in rep[section].items())
        print(f"  {section:8s} {vals}")

    print("\n[noc] neuron-to-core placement (hyperedge-overlap optimizer):")
    a = placement.fanout_adjacency(fab, cfg.fabric)
    total = cfg.fabric.cores * cfg.fabric.neurons_per_core
    for name, perm in {
        "identity": placement.identity_placement(total),
        "greedy": placement.greedy_overlap_placement(
            a, cfg.fabric.cores, cfg.fabric.neurons_per_core),
    }.items():
        cost = placement.traffic_cost(a, perm, cfg.fabric.cores,
                                      cfg.fabric.neurons_per_core)
        srch = placement.cam_search_count(a, perm, cfg.fabric.cores,
                                          cfg.fabric.neurons_per_core)
        print(f"  {name:10s} traffic_cost {cost:8.0f}  cam_searches/tick"
              f" (all-fire) {srch:8.0f}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
