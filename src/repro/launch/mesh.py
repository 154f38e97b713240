"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialization).
"""

from __future__ import annotations

import jax

from repro.compat import (AxisType, set_mesh, shard_map,  # noqa: F401
                          mesh_axis_kwargs as _axis_kwargs)


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """(16, 16) data x model single pod, or (2, 16, 16) pod x data x model.

    `shape` overrides the single-pod (data, model) factorization with the
    same 256-chip budget (e.g. (8, 32) for serving wide-TP)."""
    if shape is not None and not multi_pod:
        axes = ("data", "model")
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(tuple(shape), axes, **_axis_kwargs(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return jax.make_mesh((data, model), ("data", "model"), **_axis_kwargs(2))


def make_chip_mesh(devices: int):
    """1D ``("chips",)`` mesh over ``devices`` devices for sharded sessions.

    `InterfaceSession.run(shard="chips")` gives each device ``chips /
    devices`` consecutive simulated neuromorphic chips, so ``devices`` is a
    divisor of the simulated chip count; callers fall back to vmap on one
    device when no divisor above 1 fits the host."""
    n = len(jax.devices())
    if devices > n:
        raise ValueError(f"need {devices} devices for a chip mesh, have {n}")
    return jax.make_mesh((devices,), ("chips",), **_axis_kwargs(1))
