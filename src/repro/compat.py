"""JAX mesh API re-exports (single home, import from here).

The codebase targets the ambient-mesh API of the installed JAX (0.9):
`jax.set_mesh`, top-level `jax.shard_map` and `jax.sharding.AxisType`.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

set_mesh = jax.set_mesh
shard_map = jax.shard_map


def mesh_axis_kwargs(n: int) -> dict:
    """`jax.make_mesh` keywords giving all ``n`` axes the Auto type."""
    return {"axis_types": (AxisType.Auto,) * n}
