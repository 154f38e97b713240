"""Rate-coded cluster gating over the flat neuron space of the fabric.

Neurons form contiguous clusters of ``cluster_size`` global ids.  Each
tick every cluster is independently gated on with ``active_prob``;
neurons in an active cluster fire with ``rate``.
"""

import jax
import jax.numpy as jnp


def generate(key, ticks, cores, neurons_per_core, *, cluster_size=16,
             active_prob=0.25, rate=0.5):
    if cluster_size < 1:
        raise ValueError(f"cluster_size={cluster_size} must be >= 1")
    total = cores * neurons_per_core
    num_clusters = -(-total // cluster_size)  # ceil
    k_g, k_q = jax.random.split(key)
    gates = jax.random.bernoulli(k_g, active_prob, (ticks, num_clusters))
    cluster_of = jnp.arange(total) // cluster_size
    gate_per_neuron = gates[:, cluster_of]  # (ticks, total)
    fire = jax.random.bernoulli(k_q, rate, (ticks, total))
    return (gate_per_neuron & fire).reshape(ticks, cores, neurons_per_core)
