"""Arbitration architectures for the neuromorphic core output interface.

Implements the five schemes compared in the paper (Tables I-III, Fig. 5):

  binary_tree  - flat binary arbiter tree (Boahen-style)
  greedy_tree  - binary tree with greedy re-grant of hot subtrees
  token_ring   - single token ring over all N neurons
  hier_ring    - two-level hierarchical token ring (HTR, Purohit & Manohar)
  hier_tree    - the paper's HAT: log4(N) levels of shared four-input
                 arbiters, 2 address bits encoded per level, with the
                 asynchronous encoding pipeline holding higher-level grants
                 while a cluster drains.

Two complementary models:

  * closed-form unit-domain costs (re-exported from :mod:`repro.core.ppa`),
  * a mechanistic discrete-event simulator (`simulate`) in pure JAX whose
    emergent latencies match the closed forms (exactly for sparse mode, to
    within a few percent for burst mode - the same gap the paper reports
    between theory and pre-layout simulation).

Scheme dispatch goes through `repro.interface.registry`: each architecture
registers an :class:`ArbiterScheme` bundle of policy callables (grant
selection, grant delay, token update, encode energy) and the simulator is
a single generic event loop over those callables.  A new architecture
plugs in with ``register_arbiter(name, ArbiterScheme(...))`` - no edits to
the simulator or the fabric.

TPU adaptation (DESIGN.md §2): arbitration on a deterministic machine is a
*scheduling policy*, not an analog race.  Ties break by ascending address;
metastability/grant-overlap become testable determinism properties.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import ppa
from repro.interface import registry as interface_registry

SCHEMES = ppa.SCHEMES

# Re-export the closed forms so callers have one import surface.
sparse_latency_units = ppa.sparse_latency_units
burst_latency_units = ppa.burst_latency_units
area_units = ppa.area_units
sparse_latency_ns = ppa.sparse_latency_ns
burst_latency_ns = ppa.burst_latency_ns
area_normalized = ppa.area_normalized

INF = jnp.inf


@dataclasses.dataclass(frozen=True)
class ArbiterContext:
    """Static per-instance quantities shared by every policy callable."""

    n: int
    lg: float               # log2(n)
    sqrt_n: int
    levels: int             # HAT hierarchy levels
    fill: int               # HAT pipeline fill latency (units)
    addrs: jnp.ndarray      # (n,) int32


@dataclasses.dataclass(frozen=True)
class ArbiterScheme:
    """Registry entry: the policy bundle of one arbitration architecture.

    select_key(ctx, tok_hi, tok_lo) -> (n,) float32
        priority key among *arrived* requests; argmin wins the grant.
    grant_delay(ctx, sel, backlog, tok_hi, tok_lo, prev_addr, granted_any)
        -> float32 scalar delay between service start and grant.
    token_update(ctx, sel, taken, tok_hi, tok_lo) -> (tok_hi, tok_lo)
        optional ring-token advance after a grant.
    encode_energy(n, addr_seq) -> float32
        average address-line toggles per event for a grant sequence.
    tick_latency(ctx) -> Optional[(n,) bool -> float32]
        optional factory of a *vectorized* per-tick latency policy: given a
        frame of simultaneous requests (all at t=0), return the completion
        time the event-loop simulator would emerge with - without running
        it.  May return ``None`` when the closed form does not apply at
        this ``ctx`` (the dispatcher then falls back to the simulator).
        The simulator stays the source of truth; `tests/test_arbiter.py`
        property-tests every policy against it.  When replacing
        ``grant_delay`` on a derived scheme, drop or replace
        ``tick_latency`` too - it encodes the built-in delays.
    sparse_tick_latency(ctx) -> Optional[(buf, counts) -> (cores, 1) f32]
        optional factory of the *event-compacted* form of ``tick_latency``
        for the ``impl="pallas_sparse"`` tick: ``buf`` is a
        (cores, capacity + 1) buffer of ascending active addresses padded
        with ``ctx.n`` (`repro.kernels.sparse_tick.compact_events`) and
        ``counts`` the (cores, 1) live event counts.  The policy runs
        inside a TPU Pallas kernel, so it keeps every value 2-D.  Must
        return exactly the float32 values ``tick_latency`` yields on the
        equivalent dense frame (the conformance grid holds the whole
        sparse tick bit-identical to the dense oracle).  May return ``None`` when no
        closed form applies at this ``ctx``; schemes without the policy
        cannot run ``impl="pallas_sparse"`` (sessions refuse at compile).
    sparse_encode_energy(ctx) -> Optional[(buf, counts) -> (cores, 1) f32]
        the event-compacted form of ``encode_energy``, same contract:
        bit-identical per-core toggles/event from the compacted buffer.
    """

    name: str
    select_key: Callable
    grant_delay: Callable
    encode_energy: Callable
    token_update: Optional[Callable] = None
    tick_latency: Optional[Callable] = None
    sparse_tick_latency: Optional[Callable] = None
    sparse_encode_energy: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class ArbiterConfig:
    """Static description of one arbitration architecture instance."""

    scheme: str
    n: int                      # neurons per core (power of two)
    branching: int = 4          # HAT: four-input arbiter per hierarchy level
    pipeline_fill: int = 3      # HAT: static-HC pipeline fill latency (units)

    def __post_init__(self):
        if self.scheme not in interface_registry.ARBITERS:
            raise ValueError(
                f"unknown scheme {self.scheme!r}; registered arbiters: "
                f"{', '.join(interface_registry.ARBITERS.names())}")
        if self.n & (self.n - 1):
            raise ValueError("n must be a power of two")

    @property
    def levels(self) -> int:
        """HAT hierarchy levels (2 address bits per level)."""
        return max(1, round(math.log(self.n, self.branching)))

    @property
    def addr_bits(self) -> int:
        return int(math.log2(self.n))


# ---------------------------------------------------------------------------
# Generic discrete-event simulation.
#
# State carried through the lax.scan (one step = one granted event):
#   clock        server-free time (units)
#   token_hi/lo  ring token positions (ring schemes)
#   prev_addr    last granted address (cluster-switch penalties, HAT)
#   served       bool mask of granted events
# All scheme-specific decisions are deferred to the registered
# `ArbiterScheme` policies, resolved once per trace from the static name.
# ---------------------------------------------------------------------------


def _ring_dist(frm, to, n):
    return jnp.mod(to - frm, n)


def _make_context(n: int, levels: int, fill: int) -> ArbiterContext:
    return ArbiterContext(n=n, lg=float(math.log2(n)),
                          sqrt_n=int(round(math.sqrt(n))), levels=levels,
                          fill=fill, addrs=jnp.arange(n))


def make_context(config: ArbiterConfig) -> ArbiterContext:
    """The static `ArbiterContext` every policy callable receives."""
    return _make_context(config.n, config.levels, config.pipeline_fill)


@partial(jax.jit, static_argnames=("entry", "n", "levels", "fill"))
def _simulate(request_times, entry: ArbiterScheme, n: int, levels: int,
              fill: int):
    """Serve every finite request; returns grant_times (inf where no request).

    `entry` (not its name) is the static jit key, so re-registering a
    scheme with ``overwrite=True`` cannot serve stale traces of the old
    policies.
    """
    ctx = _make_context(n, levels, fill)
    addrs = ctx.addrs
    active = jnp.isfinite(request_times)

    def step(state, _):
        clock, tok_hi, tok_lo, prev_addr, served, granted_any = state
        pending = active & ~served
        arr = jnp.where(pending, request_times, INF)

        # --- selection policy: who is granted next -----------------------
        # If something has arrived, the scheme's priority key decides; if
        # the pipeline is idle, wait for the earliest arrival (addr tiebreak).
        arrived = pending & (arr <= clock)
        any_arrived = jnp.any(arrived)
        key_arrived = jnp.where(arrived, entry.select_key(ctx, tok_hi, tok_lo),
                                INF)
        key_waiting = arr * jnp.float32(n) + addrs
        sel = jnp.where(any_arrived, jnp.argmin(key_arrived),
                        jnp.argmin(key_waiting))

        sel_arr = request_times[sel]
        start = jnp.maximum(sel_arr, clock)
        backlog = clock > sel_arr  # pipeline already busy when the event arrived

        delay = entry.grant_delay(ctx, sel, backlog, tok_hi, tok_lo,
                                  prev_addr, granted_any).astype(jnp.float32)
        grant = start + delay

        # --- state update -------------------------------------------------
        taken = pending[sel]
        if entry.token_update is not None:
            tok_hi, tok_lo = entry.token_update(ctx, sel, taken, tok_hi, tok_lo)
        served = served.at[sel].set(served[sel] | taken)
        clock = jnp.where(taken, grant, clock)
        prev_addr = jnp.where(taken, sel, prev_addr)
        granted_any = granted_any | taken
        out = (sel, jnp.where(taken, grant, INF))
        return (clock, tok_hi, tok_lo, prev_addr, served, granted_any), out

    init = (jnp.float32(0.0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.zeros(n, dtype=bool), jnp.bool_(False))
    (_, _, _, _, _, _), (sel_seq, grant_seq) = jax.lax.scan(step, init, None, length=n)

    grant_times = jnp.full(n, INF, dtype=jnp.float32)
    # steps beyond the active count re-select served events; .min keeps first.
    grant_times = grant_times.at[sel_seq].min(grant_seq)
    return grant_times


def batched_tick_latency(config: ArbiterConfig, spikes: jnp.ndarray
                         ) -> jnp.ndarray:
    """Per-core encode completion time for one frame of simultaneous spikes.

    spikes: (cores, n) bool - every request arrives at t=0.
    returns (cores,) float32, exactly what ``max(finite grants)`` of the
    event-loop simulator yields per core, but via the scheme's vectorized
    ``tick_latency`` policy (O(n) vector work instead of an O(n^2) scan).
    Schemes without an applicable policy fall back to the simulator.
    """
    entry: ArbiterScheme = interface_registry.get_arbiter(config.scheme)
    ctx = make_context(config)
    fn = entry.tick_latency(ctx) if entry.tick_latency is not None else None
    if fn is None:
        def fn(core_spikes):
            req = jnp.where(core_spikes, 0.0, INF).astype(jnp.float32)
            grants = _simulate(req, entry, config.n, config.levels,
                               config.pipeline_fill)
            return jnp.where(
                jnp.any(core_spikes),
                jnp.max(jnp.where(jnp.isfinite(grants), grants, 0.0)), 0.0)
    return jax.vmap(fn)(spikes)


class Arbiter:
    """Discrete-event model of one core-output arbiter."""

    def __init__(self, config: ArbiterConfig):
        self.config = config

    def simulate(self, request_times) -> jnp.ndarray:
        """request_times: (n,) float, inf = no request → grant_times (n,)."""
        request_times = jnp.asarray(request_times, dtype=jnp.float32)
        if request_times.shape != (self.config.n,):
            raise ValueError(f"expected shape ({self.config.n},)")
        entry = interface_registry.get_arbiter(self.config.scheme)
        return _simulate(request_times, entry, self.config.n,
                         self.config.levels, self.config.pipeline_fill)

    # ---- experiment drivers (paper §III-D) -------------------------------

    def sparse_event_latency(self, key, num_trials: int = 64) -> jnp.ndarray:
        """Average latency of isolated random single-neuron events (units)."""
        n = self.config.n
        positions = jax.random.randint(key, (num_trials,), 0, n)

        def one(pos):
            req = jnp.full((n,), INF, dtype=jnp.float32).at[pos].set(0.0)
            return self.simulate(req)[pos]

        return jnp.mean(jax.vmap(one)(positions))

    def burst_latency(self) -> jnp.ndarray:
        """Completion time of a full-frame burst (all neurons fire at t=0)."""
        req = jnp.zeros((self.config.n,), dtype=jnp.float32)
        grants = self.simulate(req)
        return jnp.max(grants)

    # ---- closed forms ----------------------------------------------------

    def theoretical_sparse_units(self) -> float:
        return sparse_latency_units(self.config.scheme, self.config.n)

    def theoretical_burst_units(self) -> float:
        return burst_latency_units(self.config.scheme, self.config.n)

    def theoretical_area_units(self) -> float:
        return area_units(self.config.scheme, self.config.n)


# ---------------------------------------------------------------------------
# Encoding energy model (paper §II-A / §III-B): flat trees drive log2(N)
# address lines per event; HAT re-encodes a level only when its cluster
# grant changes.  Units: address-line toggles per event.
# ---------------------------------------------------------------------------


def encode_energy_units(scheme: str, n: int, addr_seq) -> jnp.ndarray:
    """Average address-line toggles/event for a granted address sequence."""
    entry: ArbiterScheme = interface_registry.get_arbiter(scheme)
    return entry.encode_energy(n, jnp.asarray(addr_seq))


def _flat_encode_energy(n: int, addr_seq) -> jnp.ndarray:
    """Every event re-drives all log2(N) address lines."""
    return jnp.float32(math.log2(n)) * jnp.ones((), jnp.float32)


def _hat_encode_energy(n: int, addr_seq) -> jnp.ndarray:
    """Level l re-encodes its 2 bits iff the prefix above level l changed.

    Vectorized over the levels axis (a Python loop here unrolled into every
    trace that embedded it - once per core under the interface tick's vmap).
    """
    levels = max(1, round(math.log(n, 4)))
    prev = jnp.concatenate([jnp.array([-1], addr_seq.dtype), addr_seq[:-1]])
    div = (4 ** jnp.arange(levels)).astype(addr_seq.dtype)        # (levels,)
    changed = (addr_seq[:, None] // div) != (prev[:, None] // div)
    toggles = jnp.sum(jnp.where(changed, 2.0, 0.0), axis=-1)
    return jnp.mean(toggles)


# ---------------------------------------------------------------------------
# Built-in scheme policies (registered below).
# ---------------------------------------------------------------------------


def _tree_select(ctx, tok_hi, tok_lo):
    """Trees grant the lowest pending address (deterministic tie-break)."""
    return ctx.addrs.astype(jnp.float32)


def _token_ring_select(ctx, tok_hi, tok_lo):
    """Rings grant the nearest pending request downstream of the token."""
    return _ring_dist(tok_hi, ctx.addrs, ctx.n).astype(jnp.float32)


def _hier_ring_select(ctx, tok_hi, tok_lo):
    hi, lo = ctx.addrs // ctx.sqrt_n, ctx.addrs % ctx.sqrt_n
    dist = _ring_dist(tok_hi, hi, ctx.sqrt_n) * (ctx.sqrt_n + 2) + _ring_dist(
        jnp.where(hi == tok_hi, tok_lo, 0), lo, ctx.sqrt_n)
    return dist.astype(jnp.float32)


def _binary_tree_delay(ctx, sel, backlog, tok_hi, tok_lo, prev_addr,
                       granted_any):
    return jnp.float32(2.0 * (ctx.lg - 1.0))       # full round trip, always


def _greedy_tree_delay(ctx, sel, backlog, tok_hi, tok_lo, prev_addr,
                       granted_any):
    # greedy re-grant services backlog at leaf level (~3 units);
    # a lone event still pays the full climb.
    return jnp.where(backlog, 3.0, 2.0 * (ctx.lg - 1.0))


def _token_ring_delay(ctx, sel, backlog, tok_hi, tok_lo, prev_addr,
                      granted_any):
    # idle: token travels dist hops then grants (+1); backlogged: the
    # hop overlaps the previous handshake -> 1 unit/event (burst = N).
    dist = _ring_dist(tok_hi, sel, ctx.n).astype(jnp.float32)
    return jnp.where(backlog, jnp.maximum(dist, 1.0), dist + 1.0)


def _hier_ring_delay(ctx, sel, backlog, tok_hi, tok_lo, prev_addr,
                     granted_any):
    hi, lo = sel // ctx.sqrt_n, sel % ctx.sqrt_n
    d_hi = _ring_dist(tok_hi, hi, ctx.sqrt_n).astype(jnp.float32)
    d_lo = _ring_dist(jnp.where(hi == tok_hi, tok_lo, 0), lo,
                      ctx.sqrt_n).astype(jnp.float32)
    # idle: top hops + bottom hops + grant; backlogged: 1 unit/event
    # with a 3-unit section-switch penalty (enter/exit the sub-ring).
    return jnp.where(backlog, jnp.maximum(d_lo + 3.0 * d_hi, 1.0),
                     d_hi + d_lo + 1.0)


def _hier_tree_delay(ctx, sel, backlog, tok_hi, tok_lo, prev_addr,
                     granted_any):
    # Sparse (idle pipeline): 2 two-input stages per level = log2 N.
    # Backlogged: 1 unit/event + 1 unit when the level-2 cluster
    # (16 neurons) switches, + one-off pipeline fill.
    cluster = sel // (4 ** (ctx.levels - 1))
    prev_cluster = prev_addr // (4 ** (ctx.levels - 1))
    switch = (cluster != prev_cluster).astype(jnp.float32)
    first = (~granted_any).astype(jnp.float32)
    return jnp.where(backlog, 1.0 + switch + first * ctx.fill,
                     2.0 * ctx.levels)


def _token_ring_update(ctx, sel, taken, tok_hi, tok_lo):
    return jnp.where(taken, sel, tok_hi), tok_lo


def _hier_ring_update(ctx, sel, taken, tok_hi, tok_lo):
    return (jnp.where(taken, sel // ctx.sqrt_n, tok_hi),
            jnp.where(taken, sel % ctx.sqrt_n, tok_lo))


# ---------------------------------------------------------------------------
# Vectorized per-tick latency policies (`ArbiterScheme.tick_latency`).
#
# For a frame of simultaneous requests (all at t=0) the event loop is fully
# determined: the first grant takes the idle-pipeline delay, every later one
# the backlogged delay, and service order follows the selection key.  Each
# policy below is the closed form of that trajectory, exact in fp32 (all
# intermediate quantities are small integers), so the interface tick pays
# O(n) vector work per core instead of an O(n^2) lax.scan.  Property tests
# in tests/test_arbiter.py hold them to bit-equality with `_simulate`.
# ---------------------------------------------------------------------------


def _binary_tree_tick_latency(ctx):
    # every grant pays the full 2(log2 N - 1) round trip, back to back
    per_grant = jnp.float32(2.0 * (ctx.lg - 1.0))

    def lat(spikes):
        return jnp.sum(spikes).astype(jnp.float32) * per_grant
    return lat


def _greedy_tree_tick_latency(ctx):
    # first grant climbs the whole tree; the backlog re-grants at ~3 units
    if ctx.lg <= 1.0:
        return None       # zero climb delay -> the event loop never backlogs
    first = jnp.float32(2.0 * (ctx.lg - 1.0))

    def lat(spikes):
        k = jnp.sum(spikes).astype(jnp.float32)
        return jnp.where(k > 0.0, first + (k - 1.0) * 3.0, 0.0)
    return lat


def _token_ring_tick_latency(ctx):
    # token starts at 0 and sweeps ascending; hop/handshake overlap makes
    # every gap cost max(gap, 1) = gap, telescoping to max_addr + 1
    def lat(spikes):
        top = jnp.max(jnp.where(spikes, ctx.addrs, -1))
        return jnp.where(jnp.any(spikes), top.astype(jnp.float32) + 1.0, 0.0)
    return lat


def _hier_ring_tick_latency(ctx):
    # sections drain ascending from 0; within a section the lo-gaps
    # telescope to lo_max, and each section switch costs lo_entry + 3*d_hi
    if ctx.sqrt_n * ctx.sqrt_n != ctx.n:
        return None           # top ring wraps inside the address space
    s = ctx.sqrt_n
    hi, lo = ctx.addrs // s, ctx.addrs % s

    def lat(spikes):
        lo_max = jnp.full((s,), jnp.int32(-1)).at[hi].max(
            jnp.where(spikes, lo, -1))
        occupied = lo_max >= 0
        sec = jnp.arange(s)
        s_first = jnp.min(jnp.where(occupied, sec, s))
        s_last = jnp.max(jnp.where(occupied, sec, -1))
        total = (1.0 + s_first + 3.0 * (s_last - s_first) +
                 jnp.sum(jnp.where(occupied, lo_max, 0)))
        return jnp.where(jnp.any(spikes), total.astype(jnp.float32), 0.0)
    return lat


def _hier_tree_tick_latency(ctx):
    # first grant fills the 2*levels pipeline; each later one costs 1 unit
    # plus 1 when the level-2 cluster switches (ascending order visits each
    # occupied cluster exactly once -> Q-1 switches)
    size = 4 ** (ctx.levels - 1)
    clusters = -(-ctx.n // size)
    cluster = ctx.addrs // size

    def lat(spikes):
        k = jnp.sum(spikes).astype(jnp.float32)
        occ = jnp.zeros((clusters,), bool).at[cluster].max(spikes)
        q = jnp.sum(occ).astype(jnp.float32)
        return jnp.where(k > 0.0,
                         2.0 * ctx.levels + (k - 1.0) + (q - 1.0), 0.0)
    return lat


# ---------------------------------------------------------------------------
# Sparse (event-compacted) per-tick policies (`ArbiterScheme.
# sparse_tick_latency` / ``sparse_encode_energy``).
#
# Same closed forms as the dense `tick_latency` policies, re-derived from
# the compacted event buffer the ``impl="pallas_sparse"`` tick carries:
# ``buf`` (cores, capacity + 1) holds each core's active addresses in
# ascending service order padded with ``ctx.n``, ``counts`` (cores, 1) the
# live totals, and each policy returns (cores, 1) float32.  Every value
# stays 2-D, because the policies run inside the `repro.kernels.
# sparse_tick` Pallas kernel and the TPU lowering refuses 1-D values.
# Every quantity is an exact small integer in fp32, so the results are
# bit-identical to the dense policies (asserted per scheme in
# tests/test_sparse_tick.py).  Address prefixes use arithmetic right
# shifts (``4**l`` and ``sqrt_n`` are powers of two wherever these
# policies apply), which floor-divide correctly for the ``-1`` boundary
# sentinel.  Constants are Python scalars: the kernel body rejects
# captured traced arrays.
# ---------------------------------------------------------------------------


def _shift_in(buf, fill, *, from_left: bool):
    """``buf`` moved one slot along the event axis, ``fill`` shifted in.

    ``from_left`` gives each slot its predecessor (slot 0 gets ``fill``);
    otherwise each slot gets its successor (the last slot gets ``fill``).
    """
    edge = jnp.full((buf.shape[0], 1), fill, buf.dtype)
    if from_left:
        return jnp.concatenate([edge, buf[:, :-1]], axis=1)
    return jnp.concatenate([buf[:, 1:], edge], axis=1)


def _binary_tree_sparse_latency(ctx):
    per_grant = 2.0 * (ctx.lg - 1.0)

    def lat(buf, counts):
        return counts.astype(jnp.float32) * jnp.float32(per_grant)
    return lat


def _greedy_tree_sparse_latency(ctx):
    if ctx.lg <= 1.0:
        return None       # mirrors the dense policy: simulator territory
    first = 2.0 * (ctx.lg - 1.0)

    def lat(buf, counts):
        k = counts.astype(jnp.float32)
        return jnp.where(k > 0.0, jnp.float32(first) + (k - 1.0) * 3.0, 0.0)
    return lat


def _token_ring_sparse_latency(ctx):
    def lat(buf, counts):
        top = jnp.max(jnp.where(buf < ctx.n, buf, -1), axis=1, keepdims=True)
        return jnp.where(counts > 0, top.astype(jnp.float32) + 1.0, 0.0)
    return lat


def _hier_ring_sparse_latency(ctx):
    # The buffer is ascending, so sections appear in order and a
    # section's largest lo-address is that of its last event: the event
    # whose successor sits in another section (pads, ``n >> shift == s``,
    # sit in none).  That replaces the dense policy's per-section
    # scatter-max with one shifted compare.
    if ctx.sqrt_n * ctx.sqrt_n != ctx.n:
        return None           # top ring wraps inside the address space
    s = ctx.sqrt_n
    shift = int(math.log2(s))

    def lat(buf, counts):
        real = buf < ctx.n
        hi = buf >> shift
        last = real & (_shift_in(buf, ctx.n, from_left=False) >> shift != hi)
        lo_max_sum = jnp.sum(jnp.where(last, buf & (s - 1), 0), axis=1,
                             keepdims=True)
        s_first = jnp.min(jnp.where(real, hi, s), axis=1, keepdims=True)
        s_last = jnp.max(jnp.where(real, hi, -1), axis=1, keepdims=True)
        total = 1 + s_first + 3 * (s_last - s_first) + lo_max_sum
        return jnp.where(counts > 0, total.astype(jnp.float32), 0.0)
    return lat


def _hier_tree_sparse_latency(ctx):
    # ascending order visits each occupied level-2 cluster once, so the
    # switch count is the number of cluster boundaries in the buffer
    shift = 2 * (ctx.levels - 1)

    def lat(buf, counts):
        real = buf < ctx.n
        cluster = buf >> shift
        prev = _shift_in(cluster, -1, from_left=True)
        q = jnp.sum(real & (cluster != prev), axis=1,
                    keepdims=True).astype(jnp.float32)
        k = counts.astype(jnp.float32)
        return jnp.where(k > 0.0,
                         2.0 * ctx.levels + (k - 1.0) + (q - 1.0), 0.0)
    return lat


def _flat_sparse_encode_energy(ctx):
    const = math.log2(ctx.n)

    def enc(buf, counts):
        return jnp.full((buf.shape[0], 1), const, jnp.float32)
    return enc


def _hat_sparse_encode_energy(ctx):
    # `_hat_encode_energy` over the dense (n,)-padded stream: all toggles
    # happen inside the compacted buffer (pairwise transitions plus the
    # -1 boundary and the first pad boundary); the remaining n -> n pad
    # transitions contribute zero, so summing buffer toggles and dividing
    # by n reproduces the dense mean bit-for-bit (exact integer sums).
    levels = ctx.levels

    def enc(buf, counts):
        prev = _shift_in(buf, -1, from_left=True)
        toggles = sum(
            jnp.sum(jnp.where((buf >> 2 * l) != (prev >> 2 * l), 2.0, 0.0),
                    axis=1, keepdims=True)
            for l in range(levels))
        return toggles / jnp.float32(ctx.n)
    return enc


for _entry in (
    ArbiterScheme("binary_tree", _tree_select, _binary_tree_delay,
                  _flat_encode_energy,
                  tick_latency=_binary_tree_tick_latency,
                  sparse_tick_latency=_binary_tree_sparse_latency,
                  sparse_encode_energy=_flat_sparse_encode_energy),
    ArbiterScheme("greedy_tree", _tree_select, _greedy_tree_delay,
                  _flat_encode_energy,
                  tick_latency=_greedy_tree_tick_latency,
                  sparse_tick_latency=_greedy_tree_sparse_latency,
                  sparse_encode_energy=_flat_sparse_encode_energy),
    ArbiterScheme("token_ring", _token_ring_select, _token_ring_delay,
                  _flat_encode_energy, _token_ring_update,
                  tick_latency=_token_ring_tick_latency,
                  sparse_tick_latency=_token_ring_sparse_latency,
                  sparse_encode_energy=_flat_sparse_encode_energy),
    ArbiterScheme("hier_ring", _hier_ring_select, _hier_ring_delay,
                  _flat_encode_energy, _hier_ring_update,
                  tick_latency=_hier_ring_tick_latency,
                  sparse_tick_latency=_hier_ring_sparse_latency,
                  sparse_encode_energy=_flat_sparse_encode_energy),
    ArbiterScheme("hier_tree", _tree_select, _hier_tree_delay,
                  _hat_encode_energy,
                  tick_latency=_hier_tree_tick_latency,
                  sparse_tick_latency=_hier_tree_sparse_latency,
                  sparse_encode_energy=_hat_sparse_encode_energy),
):
    if _entry.name not in interface_registry.ARBITERS:
        interface_registry.register_arbiter(_entry.name, _entry)
del _entry
