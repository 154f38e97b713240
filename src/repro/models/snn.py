"""Multi-core spiking neural network on the simulated core-interface fabric.

The paper's target workload: LIF neuron cores exchanging spikes through
the core interface (HAT arbiter out, CAM routing LUT in).  This model
trains with surrogate gradients; the synaptic routing used in the
training fast-path is the dense-matrix equivalent of the CAM fan-out
(bit-exact with the `repro.interface` tick, tested), while `account=True`
runs the full behavioural interface models through a precompiled
`InterfaceSession` to report latency/energy per timestep.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import fabric as fabric_mod
from repro.interface import session as interface_session
from repro.kernels.lif_step import ops as lif_ops


@dataclasses.dataclass(frozen=True)
class SNNConfig:
    fabric: fabric_mod.FabricConfig
    d_in: int = 64
    d_out: int = 10
    t_steps: int = 16
    decay: float = 0.9
    threshold: float = 1.0
    input_rate: float = 0.3

    @property
    def n_total(self) -> int:
        return self.fabric.cores * self.fabric.neurons_per_core


@jax.custom_jvp
def spike_fn(v):
    """Heaviside spike with sigmoid surrogate gradient."""
    return (v >= 0.0).astype(v.dtype)


@spike_fn.defjvp
def _spike_jvp(primals, tangents):
    (v,), (dv,) = primals, tangents
    y = spike_fn(v)
    sg = 4.0 * jax.nn.sigmoid(4.0 * v) * (1.0 - jax.nn.sigmoid(4.0 * v))
    return y, sg * dv


def init_snn(key, cfg: SNNConfig):
    """Returns (params, topology).

    params: float pytree (differentiable) - input/readout/synapse weights.
    topology: static int/bool routing structure (CAM tags, targets, valid).
    """
    k1, k2, k3 = jax.random.split(key, 3)
    n = cfg.n_total
    fab = fabric_mod.random_connectivity(k2, cfg.fabric)
    params = {
        "w_in": jax.random.normal(k1, (cfg.d_in, n)) / jnp.sqrt(cfg.d_in),
        "syn_w": fab.weights,
        "w_out": jax.random.normal(k3, (n, cfg.d_out)) / jnp.sqrt(n),
    }
    topology = {"tags": fab.tags, "valid": fab.valid, "targets": fab.targets}
    return params, topology


def fabric_params(params, topology) -> fabric_mod.FabricParams:
    return fabric_mod.FabricParams(tags=topology["tags"],
                                   valid=topology["valid"],
                                   weights=params["syn_w"],
                                   targets=topology["targets"])


def routing_matrix(fp: fabric_mod.FabricParams, cfg: fabric_mod.FabricConfig):
    """Dense (N_total, N_total) equivalent of the CAM fan-out routing."""
    cores, entries = fp.valid.shape
    n = cfg.neurons_per_core
    total = cores * n
    src_global = jnp.arange(total)
    src_bits = fabric_mod.int_to_bits(src_global, cfg.tag_bits)  # (N, bits)
    r = jnp.zeros((total, total), jnp.float32)

    def core_rows(tags_c, valid_c, weights_c, targets_c, c_idx):
        # match[entry, src] = entry subscribed to src
        eq = jnp.all(tags_c[:, None, :] == src_bits[None, :, :], axis=-1)
        hit = eq & valid_c[:, None]
        w = jnp.where(hit, weights_c[:, None], 0.0)      # (entries, N)
        tgt = jnp.zeros((n, total), jnp.float32).at[targets_c].add(w)
        return tgt                                        # (n, N_src)

    rows = jax.vmap(core_rows)(fp.tags, fp.valid, fp.weights, fp.targets,
                               jnp.arange(cores))
    return rows.reshape(total, total).T                   # (src, tgt)


def snn_forward(params, topology, x_seq, cfg: SNNConfig, *, impl: str = "xla",
                account: bool = False):
    """x_seq (B, T, d_in) spike/rate inputs -> logits (B, d_out).

    Returns (logits, rates, stats|None).
    """
    b = x_seq.shape[0]
    n = cfg.n_total
    fab = fabric_params(params, topology)
    r_mat = routing_matrix(fab, cfg.fabric)

    def step(carry, x_t):
        v, s_prev = carry
        current = x_t @ params["w_in"] + s_prev @ r_mat
        if impl == "xla":
            # differentiable path: surrogate-gradient spike + reset
            v_pre = v * cfg.decay + current
            s = spike_fn(v_pre - cfg.threshold)
            v_next = v_pre * (1.0 - s)                    # reset to 0
        else:
            # fused kernel path (inference): bit-identical forward values
            v_next, s = lif_ops.lif_step(
                v, current, decay=cfg.decay, threshold=cfg.threshold,
                impl=impl, interpret=jax.default_backend() != "tpu")
        return (v_next, s), s

    v0 = jnp.zeros((b, n), x_seq.dtype)
    s0 = jnp.zeros((b, n), x_seq.dtype)
    (_, _), spikes = jax.lax.scan(step, (v0, s0), jnp.moveaxis(x_seq, 1, 0))
    spikes = jnp.moveaxis(spikes, 0, 1)                   # (B, T, N)
    rates = jnp.mean(spikes, axis=1)
    logits = rates @ params["w_out"]

    stats = None
    if account:
        sp = spikes.reshape(b * cfg.t_steps, cfg.fabric.cores,
                            cfg.fabric.neurons_per_core) > 0.5
        # compile-once session: arbiter plan + NoC tables built a single
        # time, then every accounted tick runs under one lax.scan
        sess = interface_session.Interface(cfg.fabric).compile(fab)
        _, acc = sess.run(sp)
        stats = acc.mean(b * cfg.t_steps)
    return logits, rates, stats


def snn_loss(params, topology, batch, cfg: SNNConfig, *, impl: str = "xla"):
    logits, rates, _ = snn_forward(params, topology, batch["x"], cfg,
                                   impl=impl)
    labels = batch["y"]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    # mild rate regularization keeps events sparse (the paper's regime)
    loss = loss + 0.01 * jnp.mean(jnp.square(rates))
    return loss
