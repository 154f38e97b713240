"""i.i.d. Bernoulli(rate) spikes: the paper's sparse-event operating mode."""

import jax


def generate(key, ticks, cores, neurons_per_core, *, rate=0.02):
    return jax.random.bernoulli(key, rate, (ticks, cores, neurons_per_core))
