"""Mesh router model: per-link event loads, contention latency, energy.

`build_tables` precomputes, from the CAM routing tables alone, everything
the per-tick interface step needs as exact event-weighted sums against
the spike vector:

  dest_counts (S,)    cores subscribed to each source  -> CAM search count
  hops        (S,)    mesh links traversed per event under the NoC scheme
  depth       (S,)    deepest source->destination path -> traversal latency
  link_table  (S, L)  events injected on each physical link per source spike

All tables depend only on the routing state (tags/valid), not on spikes, so
the hot path (`noc_step_costs`, called from the interface tick) is O(S * L).

Scheme dispatch goes through `repro.interface.registry`: each transport
scheme registers a :class:`NocScheme` bundle (destination expansion, hop
counts, per-link loads, CAM search accounting) and both `build_tables` and
the fabric cost accounting are generic over the entry - a new transport
plugs in with ``register_noc_scheme(name, NocScheme(...))``.

Latency model (constants in `repro.core.ppa`): an event pays one router
traversal per hop (`NOC_HOP_LATENCY_NS`); concurrent events contend for
links, so a tick's completion time adds the serialization backlog of the
most loaded link (`NOC_LINK_SERIALIZATION_NS` per event).  Energy is
`NOC_HOP_ENERGY` model units per link traversal, the same unit domain as
the CAM energy model so the two can be summed into a system number.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax.numpy as jnp

from repro.core import ppa
from repro.interface import registry as interface_registry
from repro.noc import multicast, topology


class NocTables(NamedTuple):
    scheme: str
    subs: jnp.ndarray          # (cores, S) bool subscription matrix
    dest_counts: jnp.ndarray   # (S,) int32 subscribed-core count
    hops: jnp.ndarray          # (S,) int32 link traversals per event
    depth: jnp.ndarray         # (S,) int32 deepest path per event
    link_table: jnp.ndarray    # (S, L) float32 per-link events per spike


@dataclasses.dataclass(frozen=True)
class NocScheme:
    """Registry entry: the transport policy of one NoC scheme.

    expand_dests(dest_mask, cores) -> (S, C) bool
        the cores an event is physically delivered to (broadcast widens the
        subscription mask to every core; mesh schemes keep it).
    hops(dest_mask, src_core, cores) -> (S,) int32 link traversals/event.
    link_loads(dest_mask, src_core, cores) -> (S, L) per-link events/spike.
    cam_accounting(tables, spikes_flat, valid_cnt, total_events, cores)
        -> (searches, entries_per_search): how many CAM searches a tick's
        events trigger and how many entries each sweeps on average.
    sparse_cam_accounting(tables, ev_idx, ev_w, valid_cnt, total_events,
        cores) -> (searches, entries_per_search): the event-indexed form
        of ``cam_accounting`` for the ``impl="pallas_sparse"`` tick -
        ``ev_idx`` (events,) flat source indices and ``ev_w`` (events,)
        float32 live-event weights replace the dense spike vector.  Must
        return bit-identical float32 values (exact integer sums either
        way).  Optional: schemes without it cannot run the sparse tick.
    """

    name: str
    expand_dests: Callable
    hops: Callable
    link_loads: Callable
    cam_accounting: Callable
    sparse_cam_accounting: Callable | None = None


def event_sum(ev: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """``ev @ table`` as an elementwise product summed over the events.

    Exact for the small integer counts the accounting carries, on every
    backend: a TPU float32 matmul at default precision rounds its
    operands to bfloat16, which loses integers above 256 (for example
    ``valid_cnt @ subs`` with 512 CAM entries).
    """
    return jnp.sum(ev[:, None] * table, axis=0)


def _flatten_links(h_inc: jnp.ndarray, v_inc: jnp.ndarray) -> jnp.ndarray:
    """(S, H, W-1) + (S, H-1, W) -> (S, L) in topology link order."""
    s = h_inc.shape[0]
    return jnp.concatenate([h_inc.reshape(s, -1), v_inc.reshape(s, -1)],
                           axis=-1)


def _unicast_link_loads(dest_mask: jnp.ndarray, src_core: jnp.ndarray,
                        cores: int) -> jnp.ndarray:
    """One routed copy per destination on every link of its XY path.

    Closed forms via prefix sums - no path enumeration.
    """
    w, h = topology.mesh_dims(cores)
    xy = topology.core_coords(cores)
    dx, dy = xy[:, 0], xy[:, 1]
    sx, sy = xy[src_core, 0], xy[src_core, 1]                  # (S,)
    m = dest_mask.astype(jnp.float32)                          # (S, C)

    rows = jnp.arange(h)
    cols_h = jnp.arange(max(w - 1, 0))
    rows_v = jnp.arange(max(h - 1, 0))
    cols = jnp.arange(w)

    # dests per column / per (column, row)
    in_col = (dx[:, None] == cols[None, :]).astype(jnp.float32)  # (C, W)
    cnt_w = jnp.sum(m[:, :, None] * in_col, axis=1)
    at = ((dx[:, None] == cols[None, :])[:, :, None] &
          (dy[:, None] == rows[None, :])[:, None, :])          # (C, W, H)
    cnt_wy = jnp.sum(m[:, :, None, None] * at, axis=1)        # (S, W, H)
    pre_w = jnp.cumsum(cnt_w, axis=-1)                         # (S, W)
    tot_w = pre_w[:, -1:]
    # horizontal link j on the source row: crossed by dests right/left
    crossings = jnp.where(cols_h[None, :] >= sx[:, None],
                          tot_w - pre_w[:, :-1],               # dx > j
                          pre_w[:, :-1])                       # dx <= j
    h_inc = (rows[None, :, None] == sy[:, None, None]) * \
        crossings[:, None, :]                                  # (S, H, W-1)
    pre_y = jnp.cumsum(cnt_wy, axis=-1)                        # (S, W, H)
    tot_y = pre_y[:, :, -1:]
    v_cross = jnp.where(rows_v[None, None, :] >= sy[:, None, None],
                        tot_y - pre_y[:, :, :-1],              # dy > i
                        pre_y[:, :, :-1])                      # (S, W, H-1)
    v_inc = jnp.moveaxis(v_cross, 1, 2)                        # (S, H-1, W)
    return _flatten_links(h_inc, v_inc)


def _multicast_link_loads(dest_mask: jnp.ndarray, src_core: jnp.ndarray,
                          cores: int) -> jnp.ndarray:
    """XY spanning tree: row trunk + one column branch per dest column."""
    w, h = topology.mesh_dims(cores)
    xy = topology.core_coords(cores)
    dx, dy = xy[:, 0], xy[:, 1]
    sx, sy = xy[src_core, 0], xy[src_core, 1]                  # (S,)

    rows = jnp.arange(h)
    cols_h = jnp.arange(max(w - 1, 0))
    rows_v = jnp.arange(max(h - 1, 0))
    cols = jnp.arange(w)

    big = jnp.int32(1 << 20)
    has = jnp.any(dest_mask, axis=-1, keepdims=True)
    minx = jnp.min(jnp.where(dest_mask, dx[None, :], big), axis=-1)
    maxx = jnp.max(jnp.where(dest_mask, dx[None, :], -big), axis=-1)
    lo = jnp.minimum(sx, minx)[:, None]
    hi = jnp.maximum(sx, maxx)[:, None]
    h_span = has & (cols_h[None, :] >= lo) & (cols_h[None, :] < hi)
    h_inc = ((rows[None, :, None] == sy[:, None, None]) &
             h_span[:, None, :]).astype(jnp.float32)

    in_col = dest_mask[:, :, None] & (dx[None, :, None] == cols[None, None, :])
    miny = jnp.min(jnp.where(in_col, dy[None, :, None], big), axis=1)
    maxy = jnp.max(jnp.where(in_col, dy[None, :, None], -big), axis=1)
    has_col = jnp.any(in_col, axis=1)                          # (S, W)
    vlo = jnp.minimum(sy[:, None], miny)[:, None, :]           # (S, 1, W)
    vhi = jnp.maximum(sy[:, None], maxy)[:, None, :]
    v_inc = (has_col[:, None, :] & (rows_v[None, :, None] >= vlo) &
             (rows_v[None, :, None] < vhi)).astype(jnp.float32)
    return _flatten_links(h_inc, v_inc)


def _all_cores_mask(dest_mask: jnp.ndarray, cores: int) -> jnp.ndarray:
    return jnp.ones((dest_mask.shape[0], cores), bool)


def _broadcast_link_loads(dest_mask, src_core, cores):
    """Broadcast floods the multicast tree over every core."""
    return _multicast_link_loads(_all_cores_mask(dest_mask, cores), src_core,
                                 cores)


def link_loads(dest_mask: jnp.ndarray, src_core: jnp.ndarray, cores: int,
               scheme: str) -> jnp.ndarray:
    """(S, L) events per physical link per source spike (registry dispatch)."""
    entry: NocScheme = interface_registry.get_noc_scheme(scheme)
    return entry.link_loads(dest_mask, src_core, cores)


def build_tables(tags: jnp.ndarray, valid: jnp.ndarray, *, cores: int,
                 neurons_per_core: int, tag_bits: int,
                 scheme: str = "multicast_tree") -> NocTables:
    """Precompute routing tables for the interface tick from the CAM state."""
    entry: NocScheme = interface_registry.get_noc_scheme(scheme)
    subs = multicast.subscription_matrix(tags, valid, cores,
                                         neurons_per_core, tag_bits)
    dmask = subs.T                                             # (S, C)
    total = cores * neurons_per_core
    src_core = jnp.arange(total, dtype=jnp.int32) // neurons_per_core
    hopmat = topology.hop_matrix(cores)

    routed = entry.expand_dests(dmask, cores)
    hops = entry.hops(dmask, src_core, cores)
    depth = jnp.max(jnp.where(routed, hopmat[src_core], 0),
                    axis=-1).astype(jnp.int32)

    return NocTables(scheme=scheme, subs=subs,
                     dest_counts=jnp.sum(dmask, axis=-1).astype(jnp.int32),
                     hops=hops, depth=depth,
                     link_table=entry.link_loads(dmask, src_core, cores))


def noc_step_costs(tables: NocTables, spikes_flat: jnp.ndarray):
    """Per-tick NoC cost from a flat (S,) spike vector.

    Returns (hops, latency_ns, energy, per-link loads).
    """
    ev = spikes_flat.astype(jnp.float32)
    hops = jnp.sum(ev * tables.hops)
    loads = event_sum(ev, tables.link_table)                   # (L,)
    depth = jnp.max(jnp.where(spikes_flat > 0, tables.depth, 0))
    latency = (depth.astype(jnp.float32) * ppa.NOC_HOP_LATENCY_NS +
               jnp.max(loads, initial=0.0) * ppa.NOC_LINK_SERIALIZATION_NS)
    energy = hops * ppa.NOC_HOP_ENERGY
    return hops, latency, energy, loads


def noc_step_costs_events(tables: NocTables, ev_idx: jnp.ndarray,
                          ev_w: jnp.ndarray):
    """Event-indexed `noc_step_costs` for the sparse tick.

    ev_idx: (events,) int32 flat source indices of this tick's events
    (pad slots pointing anywhere); ev_w: (events,) float32 1.0/0.0 live
    weights (`repro.kernels.sparse_tick.event_indices`).  Gathers the
    per-source table columns at the events instead of multiplying the
    full (S,) spike vector through them, so cost scales with events, not
    fabric size.  Every reduction sums the same exact small integers as
    the dense form, so the float32 results are bit-identical.
    """
    hops = jnp.sum(ev_w * tables.hops[ev_idx])
    loads = event_sum(ev_w, tables.link_table[ev_idx])         # (L,)
    depth = jnp.max(ev_w * tables.depth[ev_idx].astype(jnp.float32))
    latency = (depth * ppa.NOC_HOP_LATENCY_NS +
               jnp.max(loads, initial=0.0) * ppa.NOC_LINK_SERIALIZATION_NS)
    energy = hops * ppa.NOC_HOP_ENERGY
    return hops, latency, energy, loads


# ---------------------------------------------------------------------------
# CAM search accounting policies.
# ---------------------------------------------------------------------------


def _flood_cam_accounting(tables, spikes_flat, valid_cnt, total_events, cores):
    """Flood: every event is searched in every core (seed accounting)."""
    searches = total_events * cores
    entries_per_search = jnp.mean(valid_cnt)
    return searches, entries_per_search


def _flood_sparse_cam_accounting(tables, ev_idx, ev_w, valid_cnt,
                                 total_events, cores):
    """Flood accounting never reads the spike vector; same closed form."""
    return _flood_cam_accounting(tables, None, valid_cnt, total_events, cores)


def _subscribed_cam_accounting(tables, spikes_flat, valid_cnt, total_events,
                               cores):
    """Mesh: an event is searched only where some CAM entry subscribes."""
    searches = jnp.sum(spikes_flat * tables.dest_counts).astype(jnp.float32)
    swept = jnp.sum(valid_cnt[:, None] * tables.subs *
                    spikes_flat[None, :])
    entries_per_search = swept / jnp.maximum(searches, 1.0)
    return searches, entries_per_search


def _subscribed_sparse_cam_accounting(tables, ev_idx, ev_w, valid_cnt,
                                      total_events, cores):
    """Event-indexed `_subscribed_cam_accounting` (bit-identical).

    ``event_sum(valid_cnt, subs)`` is the per-source swept-entry total;
    it depends only on routing state, so XLA hoists it out of the
    per-tick scan.
    """
    searches = jnp.sum(ev_w * tables.dest_counts[ev_idx])
    swept = jnp.sum(ev_w * event_sum(valid_cnt, tables.subs)[ev_idx])
    entries_per_search = swept / jnp.maximum(searches, 1.0)
    return searches, entries_per_search


# ---------------------------------------------------------------------------
# Built-in transport schemes.
# ---------------------------------------------------------------------------

for _entry in (
    NocScheme("broadcast",
              expand_dests=_all_cores_mask,
              hops=lambda m, src, cores: multicast.broadcast_tree_hops(
                  src, cores),
              link_loads=_broadcast_link_loads,
              cam_accounting=_flood_cam_accounting,
              sparse_cam_accounting=_flood_sparse_cam_accounting),
    NocScheme("unicast",
              expand_dests=lambda m, cores: m,
              hops=lambda m, src, cores: multicast.unicast_hops(
                  m, src, cores),
              link_loads=_unicast_link_loads,
              cam_accounting=_subscribed_cam_accounting,
              sparse_cam_accounting=_subscribed_sparse_cam_accounting),
    NocScheme("multicast_tree",
              expand_dests=lambda m, cores: m,
              hops=lambda m, src, cores: multicast.multicast_tree_hops(
                  m, src, cores),
              link_loads=_multicast_link_loads,
              cam_accounting=_subscribed_cam_accounting,
              sparse_cam_accounting=_subscribed_sparse_cam_accounting),
):
    if _entry.name not in interface_registry.NOC_SCHEMES:
        interface_registry.register_noc_scheme(_entry.name, _entry)
del _entry
