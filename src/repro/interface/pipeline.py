"""The core-interface tick: arbiter -> AER encode -> NoC -> CAM, once.

This module owns the per-tick computation that used to live in
`repro.core.fabric.step`.  It is pure-functional JAX, duck-typed over the
config (`InterfaceConfig` or the legacy `FabricConfig`), and dispatches
every scheme decision through `repro.interface.registry` - no string-``if``
chains in the hot path.

Event-driven hot path (the default): a `RoutingIndex` built once per
(params, cfg) decodes every CAM entry's stored tag back to its global
source-neuron index (the same int-pack trick as
`noc.multicast.subscription_matrix`), so the per-tick CAM match collapses
to a gather ``spikes_flat[src_idx] & active`` plus one weighted
scatter-add per core - no (entries x cores*n x tag_bits) equality tensor
is ever materialized.  Arbiter latency comes from the scheme's vectorized
``tick_latency`` policy (`repro.core.arbiter.batched_tick_latency`)
instead of an in-tick discrete-event simulation, and the AER address
stream is produced by `repro.kernels.hat_encode`.  ``cfg.impl`` selects
the match backend: ``"xla"`` (gather), ``"pallas"`` (the
`repro.kernels.cam_search` kernel; interpret-mode off-TPU), or
``"pallas_sparse"`` (`_sparse_event_tick`: per-core event compaction
feeding the `repro.kernels.sparse_tick` arbiter kernel, with a dense
fallback when a core overflows ``cfg.sparse_capacity`` - per-tick cost
scales with events rather than fabric size, results stay bit-identical).

The pre-optimization dense sweep survives as ``interface_tick(...,
oracle=True)`` - the reference the fast path is held bit-identical to in
`tests/test_interface.py` and `benchmarks/noc_bench.py`.

The synaptic currents are computed from the same CAM-match semantics
regardless of NoC scheme (delivery only changes *where* searches happen,
not their results), so currents are bit-identical across schemes, impls,
and to the seed broadcast implementation.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import arbiter as arb
from repro.core import cam as cam_mod
from repro.interface import registry as interface_registry
from repro.interface.stats import StepStats
from repro.interface.types import int_to_bits
from repro.kernels.cam_search import ops as cam_ops
from repro.kernels.hat_encode import ops as hat_ops
from repro.kernels.sparse_tick import ops as sparse_ops
from repro.noc import hierarchy
from repro.noc import router as noc_router
from repro.obs import telemetry as obs_telemetry


def build_tables(params, cfg):
    """NoC routing tables for the configured scheme (build once, reuse).

    Returns flat single-chip `NocTables`, or two-tier
    `repro.noc.hierarchy.HierTables` (chip-local meshes + inter-chip
    router level) when ``cfg.chips > 1``.
    """
    chips = getattr(cfg, "chips", 1)
    if chips > 1:
        return hierarchy.build_hier_tables(
            params.tags, params.valid, chips=chips,
            cores_per_chip=cfg.cores_per_chip,
            neurons_per_core=cfg.neurons_per_core,
            tag_bits=cfg.tag_bits, scheme=cfg.noc.scheme)
    return noc_router.build_tables(params.tags, params.valid,
                                   cores=cfg.cores,
                                   neurons_per_core=cfg.neurons_per_core,
                                   tag_bits=cfg.tag_bits,
                                   scheme=cfg.noc.scheme)


class RoutingIndex(NamedTuple):
    """Compile-time decode of the CAM tags into gather/kernel operands.

    Everything here depends only on (params, cfg) - `InterfaceSession`
    builds it once; the per-tick step just gathers through it.  Each CAM
    entry's stored tag resolves to a *global* source address at compile
    time: ``src_idx`` is the flat neuron index, and ``src_chip`` /
    ``src_core`` decode it to (chip, core-within-chip) under the fabric's
    chip tier (``src_chip`` is all-zero on flat single-chip configs).
    """

    src_idx: jnp.ndarray     # (cores, entries) int32 global source index
    active: jnp.ndarray      # (cores, entries) bool: valid & tag in range
    src_chip: jnp.ndarray    # (cores, entries) int32 source chip
    src_core: jnp.ndarray    # (cores, entries) int32 source core within chip
    q_words: jnp.ndarray     # (cores*entries, W) int32 packed entry tags
    src_words: jnp.ndarray   # (cores*neurons, W) int32 packed source addrs


def build_routing_index(params, cfg) -> RoutingIndex:
    """Decode each CAM entry's tag to a source index, once (int-pack)."""
    total = cfg.cores * cfg.neurons_per_core
    bits = cfg.tag_bits
    bit_w = jnp.left_shift(1, jnp.arange(bits - 1, -1, -1))      # big-endian
    src_int = jnp.sum(params.tags * bit_w, axis=-1)              # (C, E)
    # tag values outside the populated address space never match a source
    active = params.valid & (src_int < total)
    src_idx = jnp.minimum(src_int, total - 1).astype(jnp.int32)
    per_chip = getattr(cfg, "cores_per_chip", None) or cfg.cores
    src_chip, src_core = hierarchy.chip_of_core(
        src_idx // cfg.neurons_per_core, per_chip)
    q_words = cam_ops.pack_bits(params.tags.reshape(-1, bits))
    src_words = cam_ops.pack_bits(int_to_bits(jnp.arange(total), bits))
    return RoutingIndex(src_idx=src_idx, active=active,
                        src_chip=src_chip.astype(jnp.int32),
                        src_core=src_core.astype(jnp.int32),
                        q_words=q_words, src_words=src_words)


def _hat_order(spikes, n):
    idx = jnp.arange(n, dtype=jnp.int32)
    key = jnp.where(spikes, idx, n)
    return jnp.sort(key), jnp.sum(spikes)


def _entry_drive(params, spikes_flat, routing: RoutingIndex, cfg):
    """(cores, entries) float32 {0,1}: is this entry's source spiking?"""
    impl = getattr(cfg, "impl", "xla")
    if impl == "pallas":
        interpret = jax.default_backend() != "tpu"
        counts = cam_ops.cam_match_counts(
            routing.q_words, routing.src_words, spikes_flat,
            impl="pallas", interpret=interpret)
        hit = counts.reshape(params.valid.shape) > 0
        return (hit & params.valid).astype(jnp.float32)
    return (spikes_flat[routing.src_idx] & routing.active).astype(jnp.float32)


def route_currents(entry_drive, weights, targets, n: int):
    """Weighted scatter-add of the driven CAM entries into currents.

    (cores, entries) drive/weights/targets -> (cores, n) float32.  Every
    event-driven path (dense, sparse, chip-sharded) routes through this
    one expression, so their currents are bit-identical by construction
    on any backend, whatever order its scatter sums duplicates in.
    """
    return jax.vmap(
        lambda c, t: jnp.zeros((n,), jnp.float32).at[t].add(c)
    )(entry_drive * weights, targets)


def _addr_streams(spikes, cfg, n):
    """(cores, n) int32 AER address streams (service order, padded with n)."""
    impl = getattr(cfg, "impl", "xla")
    row = 256
    hat_impl = "xla"
    interpret = False
    if impl == "pallas" and n % row == 0 and n <= hat_ops.MAX_PALLAS_N:
        hat_impl = "pallas"
        interpret = jax.default_backend() != "tpu"

    def one(core_spikes):
        stream, _ = hat_ops.encode_stream(core_spikes, row=row,
                                          impl=hat_impl, interpret=interpret)
        return stream

    return jax.vmap(one)(spikes)


def resolve_sparse_plan(cfg, arb_cfg: arb.ArbiterConfig | None = None):
    """Validate and resolve the ``impl="pallas_sparse"`` policy bundle.

    Returns ``(latency_fn, encode_fn, sparse_cam_accounting, capacity)``.
    Sessions call this at compile time so unsupported configurations fail
    fast with a nameable error instead of mid-scan.

    Raises:
      ValueError: when the arbiter scheme provides no sparse tick policy
        at this fabric size (e.g. ``greedy_tree`` with ``n <= 2``,
        ``hier_ring`` with a non-square address space), or the NoC scheme
        has no event-indexed CAM accounting, or ``sparse_capacity`` is
        not a positive int.
    """
    n = cfg.neurons_per_core
    if arb_cfg is None:
        arb_cfg = arb.ArbiterConfig(cfg.scheme, n)
    entry = interface_registry.get_arbiter(cfg.scheme)
    ctx = arb.make_context(arb_cfg)
    latency_fn = (entry.sparse_tick_latency(ctx)
                  if entry.sparse_tick_latency is not None else None)
    encode_fn = (entry.sparse_encode_energy(ctx)
                 if entry.sparse_encode_energy is not None else None)
    if latency_fn is None or encode_fn is None:
        raise ValueError(
            f"impl='pallas_sparse' is unsupported for arbiter scheme "
            f"{cfg.scheme!r} at n={n}: the scheme's sparse tick policies "
            f"are undefined there (use impl='xla' or 'pallas')")
    noc_scheme = interface_registry.get_noc_scheme(cfg.noc.scheme)
    if noc_scheme.sparse_cam_accounting is None:
        raise ValueError(
            f"impl='pallas_sparse' is unsupported for NoC scheme "
            f"{cfg.noc.scheme!r}: it registers no event-indexed CAM "
            f"accounting (use impl='xla' or 'pallas')")
    capacity = sparse_ops.resolve_capacity(
        getattr(cfg, "sparse_capacity", None), n)
    return latency_fn, encode_fn, noc_scheme.sparse_cam_accounting, capacity


def sparse_accounting_stats(cfg, tables, counts, ev_idx, ev_w, latencies,
                            enc_per_core, hits_total, valid, cam_cycle_ns,
                            sparse_cam_accounting) -> StepStats:
    """Event-indexed `accounting_stats` for the sparse tick.

    Mirrors the dense accounting term by term, but gathers every
    per-source table column at this tick's events (``ev_idx``/``ev_w``
    from `repro.kernels.sparse_tick.event_indices`) instead of reducing
    over the full fabric.  Every reduction sums the same exact small
    integers as the dense form, so the `StepStats` it returns is
    bit-identical (held to that across the grid in tests/conformance).
    """
    total_events = jnp.sum(counts).astype(jnp.float32)
    valid_cnt = jnp.sum(valid, axis=1).astype(jnp.float32)
    searches, entries_per_search = sparse_cam_accounting(
        tables, ev_idx, ev_w, valid_cnt, total_events, cfg.cores)
    match_per_search = hits_total.astype(jnp.float32) / jnp.maximum(searches,
                                                                    1.0)
    mismatch_per_search = entries_per_search - match_per_search
    cam_energy = searches * cam_mod._energy_jnp(cfg.cam, match_per_search,
                                                mismatch_per_search)
    cam_time = searches * cam_cycle_ns

    noc_hops, noc_latency, noc_energy, _ = noc_router.noc_step_costs_events(
        tables, ev_idx, ev_w)
    chip_hops, chip_latency, chip_energy = hierarchy.chip_step_costs_events(
        tables, ev_idx, ev_w)

    return StepStats(events=total_events,
                     encode_latency=jnp.max(latencies),
                     encode_energy=jnp.sum(enc_per_core * counts),
                     cam_searches=searches,
                     cam_energy=cam_energy,
                     cam_time_ns=cam_time,
                     noc_hops=noc_hops,
                     noc_latency=noc_latency,
                     noc_energy=noc_energy,
                     chip_hops=chip_hops,
                     chip_latency=chip_latency,
                     chip_energy=chip_energy)


def _sparse_event_tick(params, spikes, cfg, tables, arb_cfg, routing,
                       cam_cycle_ns, noc_scheme, unchecked=False):
    """The ``impl="pallas_sparse"`` tick: compact, then sparse or dense.

    Compacts the frame into per-core event buffers, then runs *one*
    `jax.lax.cond`: the sparse branch feeds the buffers through the
    `repro.kernels.sparse_tick` arbiter kernel, routes currents through
    the shared `route_currents` gather/scatter, and accounts from the
    events; the dense branch is the ordinary event-driven tick, taken
    whenever any core fired more than ``sparse_capacity`` events this
    tick.  Both branches produce bit-identical ``(currents, latencies,
    enc_per_core, StepStats)``, so the fallback only changes cost, never
    results.

    The per-tick ``cond`` itself is not free (XLA conditionals cost tens
    of microseconds per tick on CPU hosts), so callers that have already
    proven *no* frame of a stream overflows - `InterfaceSession` checks
    ``max per-core events <= capacity`` host-side once per `run` call -
    pass ``unchecked=True`` to compile the sparse branch alone, with no
    cond in the scan body.  Results are bit-identical by construction;
    passing ``unchecked=True`` on a stream that does overflow silently
    truncates events, which is why the flag is session-internal.

    Under `jax.vmap` (``run_batched``) the cond lowers to a select that
    evaluates both branches - correct, but the sparse speedup only
    materializes through the unchecked path (the session's host-side
    precheck covers the whole batch, so fully-sparse batches take it).
    """
    n = cfg.neurons_per_core
    latency_fn, encode_fn, sparse_cam, capacity = resolve_sparse_plan(
        cfg, arb_cfg)
    spikes_flat = spikes.reshape(-1)
    buf, counts = sparse_ops.compact_events(spikes, capacity)

    def sparse_branch(_):
        with jax.named_scope("repro.sparse_tick"):
            latencies, enc_per_core = sparse_ops.sparse_tick(
                buf, counts, latency_fn=latency_fn, encode_fn=encode_fn)
            entry_drive = _entry_drive(params, spikes_flat, routing, cfg)
            currents = route_currents(entry_drive, params.weights,
                                      params.targets, n)
            hits_total = jnp.sum(entry_drive)
            ev_idx, ev_w = sparse_ops.event_indices(buf, n)
            stats = sparse_accounting_stats(
                cfg, tables, counts, ev_idx, ev_w, latencies, enc_per_core,
                hits_total, params.valid, cam_cycle_ns, sparse_cam)
        return currents, latencies, enc_per_core, stats

    def dense_branch(_):
        with jax.named_scope("repro.sparse_dense_fallback"):
            latencies = arb.batched_tick_latency(arb_cfg, spikes)
            entry_drive = _entry_drive(params, spikes_flat, routing, cfg)
            currents = route_currents(entry_drive, params.weights,
                                      params.targets, n)
            hits_total = jnp.sum(entry_drive)
            addr_seq = _addr_streams(spikes, cfg, n)
            enc_per_core = jax.vmap(
                lambda seq: arb.encode_energy_units(cfg.scheme, n, seq)
            )(addr_seq)
            stats = accounting_stats(cfg, tables, spikes, latencies,
                                     enc_per_core, hits_total, params.valid,
                                     cam_cycle_ns, noc_scheme)
        return currents, latencies, enc_per_core, stats

    if unchecked:
        return sparse_branch(None)
    overflow = jnp.any(counts > capacity)
    return jax.lax.cond(overflow, dense_branch, sparse_branch, None)


def interface_tick(params, spikes: jnp.ndarray, cfg,
                   tables: noc_router.NocTables | None = None,
                   arb_cfg: arb.ArbiterConfig | None = None,
                   routing: RoutingIndex | None = None,
                   cam_cycle_ns: float | None = None,
                   oracle: bool = False,
                   telemetry: str = "off",
                   sparse_unchecked: bool = False,
                   ) -> tuple[jnp.ndarray, StepStats]:
    """One fabric tick.

    spikes:  (cores, neurons_per_core) bool
    tables:  optional precomputed `build_tables(params, cfg)` - pass it when
        stepping in a loop (`InterfaceSession` does) to avoid rebuilding the
        subscription masks every tick.  They depend only on (params, cfg).
    arb_cfg: optional prebuilt arbiter plan (the session builds it once).
    routing: optional prebuilt `build_routing_index(params, cfg)`.
    cam_cycle_ns: optional precomputed `cam.cycle_time_ns(cfg.cam)` (the
        session passes its `cam_cycle_ns` attribute).
    oracle:  run the pre-optimization reference path - dense tag-vs-every-
        source CAM sweep + per-core discrete-event arbiter simulation.  The
        default event-driven path is bit-identical to it (tested).
    telemetry: ``"off"`` (default) returns ``(currents, StepStats)``
        exactly as always; ``"cores"`` additionally returns a
        `repro.obs.telemetry.CoreStats` per-core breakdown as a third
        element.  The tick computation is identical either way - currents
        and stats are bit-identical across telemetry modes.
    sparse_unchecked: only meaningful under ``impl="pallas_sparse"``:
        skip the per-tick overflow ``lax.cond`` and run the sparse
        branch unconditionally.  Callers must have proven no core exceeds
        ``sparse_capacity`` events on any frame they will pass (the
        session's host-side precheck); see `_sparse_event_tick`.
    returns: currents (cores, neurons_per_core) float32, `StepStats`
        (plus `CoreStats` under ``telemetry="cores"``)
    """
    if telemetry not in ("off", "cores"):
        raise ValueError(
            f"interface_tick telemetry must be 'off' or 'cores' (the "
            f"'ticks' mode is a session-level scan concern), got {telemetry!r}")
    cores, n = spikes.shape
    if n != cfg.neurons_per_core or cores != cfg.cores:
        raise ValueError(
            f"spikes shape ({cores}, {n}) does not match config "
            f"({cfg.cores}, {cfg.neurons_per_core})")
    if spikes.dtype != jnp.bool_:
        spikes = spikes > 0

    if tables is None:
        tables = build_tables(params, cfg)
    if tables.scheme != cfg.noc.scheme:
        raise ValueError(
            f"NoC tables were built for scheme {tables.scheme!r} but the "
            f"config requests {cfg.noc.scheme!r}; rebuild them with "
            f"repro.interface.build_tables(params, cfg)")
    if getattr(tables, "chips", 1) != getattr(cfg, "chips", 1):
        raise ValueError(
            f"NoC tables were built for chips={getattr(tables, 'chips', 1)} "
            f"but the config requests chips={getattr(cfg, 'chips', 1)}; "
            f"rebuild them with repro.interface.build_tables(params, cfg)")
    if arb_cfg is None:
        arb_cfg = arb.ArbiterConfig(cfg.scheme, n)
    if cam_cycle_ns is None:
        cam_cycle_ns = cam_mod.cycle_time_ns(cfg.cam)
    noc_scheme = interface_registry.get_noc_scheme(cfg.noc.scheme)

    spikes_flat = spikes.reshape(-1)

    if oracle:
        # ---- reference path: DES arbiter + dense CAM sweep ----------------
        arbiter = arb.Arbiter(arb_cfg)

        def encode_core(core_spikes):
            req = jnp.where(core_spikes, 0.0, jnp.inf).astype(jnp.float32)
            grants = arbiter.simulate(req)
            return jnp.where(
                jnp.any(core_spikes),
                jnp.max(jnp.where(jnp.isfinite(grants), grants, 0.0)), 0.0)

        latencies = jax.vmap(encode_core)(spikes)

        # global source tags of every spiking neuron (dense mask form)
        neuron_global = (jnp.arange(cores)[:, None] * n +
                         jnp.arange(n)[None, :])
        src_bits = int_to_bits(neuron_global, cfg.tag_bits)  # (cores, n, bits)

        # match[entry, c_src * n + neuron] = entry subscribed to that source
        def core_inputs(tags_c, valid_c, weights_c, targets_c):
            # (entries, bits) vs (cores*n, bits)
            flat_bits = src_bits.reshape(-1, cfg.tag_bits)
            eq = jnp.all(tags_c[:, None, :] == flat_bits[None, :, :], axis=-1)
            hit = eq & valid_c[:, None] & spikes_flat[None, :]
            entry_drive = jnp.sum(hit, axis=1).astype(jnp.float32)
            contrib = entry_drive * weights_c
            currents = jnp.zeros((n,), jnp.float32).at[targets_c].add(contrib)
            return currents, jnp.sum(hit)

        currents, hits = jax.vmap(core_inputs)(params.tags, params.valid,
                                               params.weights, params.targets)
        hits_total = jnp.sum(hits)
        addr_seq = jax.vmap(lambda s: _hat_order(s, n)[0])(spikes)
    else:
        # ---- event-driven path: policy latency + gather/scatter -----------
        if routing is None:
            routing = build_routing_index(params, cfg)
        if getattr(cfg, "impl", "xla") == "pallas_sparse":
            currents, latencies, enc_per_core, stats = _sparse_event_tick(
                params, spikes, cfg, tables, arb_cfg, routing, cam_cycle_ns,
                noc_scheme, unchecked=sparse_unchecked)
            if telemetry == "cores":
                with jax.named_scope("repro.telemetry_cores"):
                    core = per_core_stats(cfg, tables, spikes, latencies,
                                          enc_per_core)
                return currents, stats, core
            return currents, stats
        with jax.named_scope("repro.arbiter_latency"):
            latencies = arb.batched_tick_latency(arb_cfg, spikes)
        with jax.named_scope("repro.cam_match"):
            entry_drive = _entry_drive(params, spikes_flat, routing, cfg)
            currents = route_currents(entry_drive, params.weights,
                                      params.targets, n)
            hits_total = jnp.sum(entry_drive)
        with jax.named_scope("repro.aer_encode"):
            addr_seq = _addr_streams(spikes, cfg, n)

    # ---- NoC delivery + PPA accounting ------------------------------------
    with jax.named_scope("repro.accounting"):
        enc_per_core = jax.vmap(
            lambda seq: arb.encode_energy_units(cfg.scheme, n, seq))(addr_seq)
        stats = accounting_stats(cfg, tables, spikes, latencies, enc_per_core,
                                 hits_total, params.valid, cam_cycle_ns,
                                 noc_scheme)
    if telemetry == "cores":
        with jax.named_scope("repro.telemetry_cores"):
            core = per_core_stats(cfg, tables, spikes, latencies, enc_per_core)
        return currents, stats, core
    return currents, stats


def accounting_stats(cfg, tables, spikes, latencies, enc_per_core,
                     hits_total, valid, cam_cycle_ns,
                     noc_scheme=None) -> StepStats:
    """The per-tick PPA accounting tail, shared by every execution path.

    Both `interface_tick` (flat and oracle) and the chip-sharded session
    tick funnel through this function, so the `StepStats` arithmetic is
    identical by construction across paths: callers only differ in how
    they produce the per-core quantities (``latencies`` (cores,) grant
    completion times, ``enc_per_core`` (cores,) address-line toggles per
    event, ``hits_total`` scalar CAM hits).
    """
    if noc_scheme is None:
        noc_scheme = interface_registry.get_noc_scheme(cfg.noc.scheme)
    spikes_flat = spikes.reshape(-1)
    total_events = jnp.sum(spikes).astype(jnp.float32)

    valid_cnt = jnp.sum(valid, axis=1).astype(jnp.float32)
    searches, entries_per_search = noc_scheme.cam_accounting(
        tables, spikes_flat, valid_cnt, total_events, cfg.cores)
    match_per_search = hits_total.astype(jnp.float32) / jnp.maximum(searches, 1.0)
    mismatch_per_search = entries_per_search - match_per_search
    cam_energy = searches * cam_mod._energy_jnp(cfg.cam, match_per_search,
                                                mismatch_per_search)
    cam_time = searches * cam_cycle_ns

    noc_hops, noc_latency, noc_energy, _ = noc_router.noc_step_costs(
        tables, spikes_flat)
    chip_hops, chip_latency, chip_energy = hierarchy.chip_step_costs(
        tables, spikes_flat)

    return StepStats(events=total_events,
                     encode_latency=jnp.max(latencies),
                     encode_energy=jnp.sum(enc_per_core * jnp.sum(spikes, 1)),
                     cam_searches=searches,
                     cam_energy=cam_energy,
                     cam_time_ns=cam_time,
                     noc_hops=noc_hops,
                     noc_latency=noc_latency,
                     noc_energy=noc_energy,
                     chip_hops=chip_hops,
                     chip_latency=chip_latency,
                     chip_energy=chip_energy)


def per_core_stats(cfg, tables, spikes, latencies,
                   enc_per_core) -> obs_telemetry.CoreStats:
    """Per-core telemetry breakdown of one tick (``telemetry="cores"``).

    NoC/chip hops are attributed to each event's *source* core (the core
    whose arbiter emitted it) through the same precomputed per-source hop
    tables `accounting_stats` totals over, so the per-core vectors sum
    exactly back to `StepStats.noc_hops` / ``chip_hops``; events and
    encode energy likewise sum, and the per-tick ``encode_latency`` is the
    max over cores (a tick completes when its slowest arbiter does).
    """
    cores = spikes.shape[0]
    ev_flat = spikes.reshape(-1).astype(jnp.float32)
    events = jnp.sum(spikes, axis=1).astype(jnp.float32)
    noc_hops = jnp.sum((ev_flat * tables.hops).reshape(cores, -1), axis=1)
    if isinstance(tables, hierarchy.HierTables):
        chip_hops = jnp.sum((ev_flat * tables.chip_hops).reshape(cores, -1),
                            axis=1)
    else:
        chip_hops = jnp.zeros((cores,), jnp.float32)
    return obs_telemetry.CoreStats(events=events,
                                   encode_latency=latencies.astype(jnp.float32),
                                   encode_energy=enc_per_core * events,
                                   noc_hops=noc_hops,
                                   chip_hops=chip_hops)
