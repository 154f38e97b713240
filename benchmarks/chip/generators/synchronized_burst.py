"""Near-silent frames punctuated by fabric-wide synchronized bursts.

Every ``period`` ticks, ``duty`` consecutive ticks fire each neuron with
``burst_rate``; the other ticks fire at ``background``.
"""

import jax
import jax.numpy as jnp


def generate(key, ticks, cores, neurons_per_core, *, period=4, duty=1,
             burst_rate=0.9, background=0.005):
    if not 1 <= duty <= period:
        raise ValueError(f"duty={duty} must be in [1, period={period}]")
    _, k_q = jax.random.split(key)
    bursting = (jnp.arange(ticks) % period) < duty
    p = jnp.where(bursting, burst_rate, background)[:, None, None]
    return jax.random.uniform(k_q, (ticks, cores, neurons_per_core),
                              minval=0.0, maxval=1.0) < p
