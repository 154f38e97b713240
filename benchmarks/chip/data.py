"""Inputs of a run, made from ``--seed``: PRNG keys, connectivity, rasters.

The connectivity is the benchmark's own data, not the program's: a copy of
the random CAM wiring `repro.interface.random_connectivity` makes (each
entry subscribes to a uniformly drawn source neuron, is valid with
probability ``fan_in``, and carries a N(1, 0.5) weight onto a uniformly
drawn target neuron of its core).  It is made on the device in one jitted
call, handed to the program as its routing state, and read back to the
host for the plain reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chip import generators


def seed_key(seed: int, stream: int):
    """A PRNG key for one input stream of a run; any whole-number seed.

    The seed may exceed 32 bits, so its high word is folded in after its
    low word rather than truncated.
    """
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def tag_bits(fabric: dict) -> int:
    """AER address width: bits to tag every neuron of the fabric."""
    total = fabric["cores"] * fabric["neurons_per_core"]
    return max(1, math.ceil(math.log2(total)))


@functools.partial(jax.jit, static_argnames=("cores", "n", "entries", "bits",
                                             "fan_in"))
def _connectivity(key, *, cores, n, entries, bits, fan_in):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    src = jax.random.randint(k1, (cores, entries), 0, cores * n)
    tags = ((src[..., None] >> jnp.arange(bits - 1, -1, -1)) & 1).astype(
        jnp.int32)
    valid = jax.random.bernoulli(k2, fan_in, (cores, entries))
    weights = jax.random.normal(k3, (cores, entries)) * 0.5 + 1.0
    targets = jax.random.randint(k4, (cores, entries), 0, n)
    return src.astype(jnp.int32), tags, valid, weights, targets


def connectivity(key, config: dict):
    """``(device arrays, host copy)`` of the fabric's CAM wiring.

    The device arrays are ``(tags, valid, weights, targets)`` in the
    layout of `repro.interface.InterfaceParams`; the host copy is a dict
    of NumPy arrays ``src`` (global source index of each entry),
    ``valid``, ``weights`` and ``targets`` for the reference.
    """
    fab = config["fabric"]
    src, tags, valid, weights, targets = _connectivity(
        key, cores=fab["cores"], n=fab["neurons_per_core"],
        entries=fab["cam_entries_per_core"], bits=tag_bits(fab),
        fan_in=float(config["assumed"]["fan_in"]))
    host = {"src": src, "valid": valid, "weights": weights,
            "targets": targets}
    host = {k: np.asarray(v) for k, v in host.items()}
    return (tags, valid, weights, targets), host


def raster_fn(generator: str, params: dict, ticks: int, fabric: dict):
    """A jitted ``key -> (lanes, ticks, cores, n)`` raster batch maker."""
    gen = generators.load(generator)
    cores, n = fabric["cores"], fabric["neurons_per_core"]

    def one(key):
        return gen(key, ticks, cores, n, **params)

    return jax.jit(lambda keys: jax.vmap(one)(keys))
