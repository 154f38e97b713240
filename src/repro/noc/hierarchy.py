"""Hierarchical two-tier NoC: chip-local meshes + an inter-chip router level.

The paper's core interface scales a *multi-core* processor; its hardware
lineage (DYNAPs, Moradi et al., arXiv:1708.04198) extends the same fabric
across *chips* with a hierarchical router tier: each chip keeps its own
2D core mesh, and a top-level (R3-style) router grid carries events
between chips.  This module models that second tier.

Fabric model (``chips x cores_per_chip`` total cores):

  * every chip runs the configured transport scheme (broadcast / unicast
    / multicast_tree, via the usual registry entry) over its *own*
    ``cores_per_chip``-core mesh;
  * chips sit on their own near-square grid, and an event whose
    subscribers span chips travels an XY multicast spanning tree over
    that grid (the same closed form as the core-level tree - the chip
    grid is just another mesh);
  * on a remote chip the event enters at the chip's router port (core 0)
    and is delivered over the local mesh from there.

`HierTables` is attribute-compatible with `repro.noc.router.NocTables`
(``subs`` / ``dest_counts`` / ``hops`` / ``depth`` / ``link_table`` keep
their flat-fabric semantics, with the local fields aggregated over chip-
local meshes), so `noc_router.noc_step_costs` and every registered
``cam_accounting`` policy consume it unchanged.  The inter-chip tier adds
``chip_hops`` / ``chip_depth`` / ``chip_link_table``, costed by
`chip_step_costs` with its own PPA constants (`repro.core.ppa`:
``CHIP_HOP_LATENCY_NS`` / ``CHIP_LINK_SERIALIZATION_NS`` /
``CHIP_HOP_ENERGY``) and surfaced through `StepStats.chip_*` and
`ppa_report`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ppa
from repro.interface import registry as interface_registry
from repro.noc import multicast, topology
from repro.noc.router import NocScheme, _multicast_link_loads, event_sum


class HierTables(NamedTuple):
    """Precomputed two-tier routing tables (compile once, reuse per tick).

    The first five data fields mirror `NocTables` semantics so existing
    per-tick consumers (``noc_step_costs``, ``cam_accounting``) work by
    attribute access; the ``chip_*`` fields are the inter-chip tier.
    """

    scheme: str
    chips: int
    cores_per_chip: int
    subs: jnp.ndarray            # (cores_total, S) bool subscription matrix
    dest_counts: jnp.ndarray     # (S,) int32 subscribed-core count
    hops: jnp.ndarray            # (S,) int32 chip-local link traversals
    depth: jnp.ndarray           # (S,) int32 deepest chip-local path
    link_table: jnp.ndarray      # (S, chips*L_local) per-local-link events
    chip_hops: jnp.ndarray       # (S,) int32 inter-chip link traversals
    chip_depth: jnp.ndarray      # (S,) int32 deepest inter-chip path
    chip_link_table: jnp.ndarray  # (S, L_chip) per-chip-link events


def chip_of_core(core: jnp.ndarray, cores_per_chip: int) -> jnp.ndarray:
    """Global core id -> (chip, local core) under the row-major chip split."""
    return core // cores_per_chip, core % cores_per_chip


def build_hier_tables(tags: jnp.ndarray, valid: jnp.ndarray, *, chips: int,
                      cores_per_chip: int, neurons_per_core: int,
                      tag_bits: int,
                      scheme: str = "multicast_tree") -> HierTables:
    """Two-tier routing tables from the CAM state (cf. `router.build_tables`).

    The configured transport scheme governs each chip-local mesh; the
    inter-chip tier always routes one copy along the XY spanning tree over
    the destination chips (remote replication happens at chip routers, so
    even ``unicast`` pays each chip link once per event).
    """
    entry: NocScheme = interface_registry.get_noc_scheme(scheme)
    cores_total = chips * cores_per_chip
    subs = multicast.subscription_matrix(tags, valid, cores_total,
                                         neurons_per_core, tag_bits)
    dmask = subs.T                                             # (S, C_total)
    total = cores_total * neurons_per_core
    src_core = jnp.arange(total, dtype=jnp.int32) // neurons_per_core
    src_chip, src_local = chip_of_core(src_core, cores_per_chip)

    # physically-routed destinations (broadcast widens to every core)
    routed = entry.expand_dests(dmask, cores_total)            # (S, C_total)
    routed_c = routed.reshape(-1, chips, cores_per_chip)

    # ---- inter-chip tier: XY tree over the chip grid ----------------------
    chip_mask = jnp.any(routed_c, axis=-1)                     # (S, chips)
    remote = chip_mask & (jnp.arange(chips)[None, :] != src_chip[:, None])
    chip_hops = multicast.multicast_tree_hops(remote, src_chip, chips)
    chip_link_table = _multicast_link_loads(remote, src_chip, chips)
    chip_hopmat = topology.hop_matrix(chips)
    chip_depth = jnp.max(jnp.where(remote, chip_hopmat[src_chip], 0),
                         axis=-1).astype(jnp.int32)

    # ---- chip-local tier: the configured scheme on every chip's mesh ------
    # On a remote chip the event is re-injected at the router port (local
    # core 0); on the source chip it starts at the source core itself.
    mask_k = jnp.moveaxis(routed_c, 1, 0)                      # (chips, S, c)
    is_src = jnp.arange(chips)[:, None] == src_chip[None, :]   # (chips, S)
    local_src = jnp.where(is_src, src_local[None, :], 0).astype(jnp.int32)
    local_hopmat = topology.hop_matrix(cores_per_chip)

    def one_chip(mask, src):
        hops_k = entry.hops(mask, src, cores_per_chip)
        loads_k = entry.link_loads(mask, src, cores_per_chip)
        routed_k = entry.expand_dests(mask, cores_per_chip)
        depth_k = jnp.max(jnp.where(routed_k, local_hopmat[src], 0),
                          axis=-1).astype(jnp.int32)
        return hops_k, loads_k, depth_k

    hops_k, loads_k, depth_k = jax.vmap(one_chip)(mask_k, local_src)
    link_table = jnp.moveaxis(loads_k, 0, 1)                   # (S, chips, L)
    link_table = link_table.reshape(link_table.shape[0], -1)

    return HierTables(
        scheme=scheme, chips=chips, cores_per_chip=cores_per_chip,
        subs=subs, dest_counts=jnp.sum(dmask, axis=-1).astype(jnp.int32),
        hops=jnp.sum(hops_k, axis=0).astype(jnp.int32),
        depth=jnp.max(depth_k, axis=0),
        link_table=link_table,
        chip_hops=chip_hops, chip_depth=chip_depth,
        chip_link_table=chip_link_table)


def chip_step_costs(tables, spikes_flat: jnp.ndarray):
    """Per-tick inter-chip cost from a flat (S,) spike vector.

    Returns (chip_hops, chip_latency_ns, chip_energy); all zeros for flat
    single-chip tables (`NocTables`), so callers need not branch on the
    fabric shape inside a trace.
    """
    if not isinstance(tables, HierTables):
        z = jnp.zeros((), jnp.float32)
        return z, z, z
    ev = spikes_flat.astype(jnp.float32)
    hops = jnp.sum(ev * tables.chip_hops)
    loads = event_sum(ev, tables.chip_link_table)              # (L_chip,)
    depth = jnp.max(jnp.where(spikes_flat > 0, tables.chip_depth, 0))
    latency = (depth.astype(jnp.float32) * ppa.CHIP_HOP_LATENCY_NS +
               jnp.max(loads, initial=0.0) * ppa.CHIP_LINK_SERIALIZATION_NS)
    energy = hops * ppa.CHIP_HOP_ENERGY
    return hops, latency, energy


def chip_step_costs_events(tables, ev_idx: jnp.ndarray, ev_w: jnp.ndarray):
    """Event-indexed `chip_step_costs` for the sparse tick.

    Gathers the per-source chip-tier columns at this tick's events
    (``ev_idx``/``ev_w`` as in `repro.noc.router.noc_step_costs_events`)
    instead of multiplying the full spike vector through them; exact
    integer sums keep the float32 results bit-identical to the dense
    form.  Zeros for flat single-chip tables, like `chip_step_costs`.
    """
    if not isinstance(tables, HierTables):
        z = jnp.zeros((), jnp.float32)
        return z, z, z
    hops = jnp.sum(ev_w * tables.chip_hops[ev_idx])
    loads = event_sum(ev_w, tables.chip_link_table[ev_idx])
    depth = jnp.max(ev_w * tables.chip_depth[ev_idx].astype(jnp.float32))
    latency = (depth * ppa.CHIP_HOP_LATENCY_NS +
               jnp.max(loads, initial=0.0) * ppa.CHIP_LINK_SERIALIZATION_NS)
    energy = hops * ppa.CHIP_HOP_ENERGY
    return hops, latency, energy


__all__ = ["HierTables", "build_hier_tables", "chip_step_costs",
           "chip_step_costs_events", "chip_of_core"]
