"""Reference implementation of the sparse event tick.

The sparse tick makes per-tick cost scale with *events* instead of
neurons: active addresses are compacted per core into a fixed-capacity
buffer (`compact_events`), and every per-event quantity downstream -
arbiter tick latency, AER encode energy, NoC/CAM accounting - is computed
from that buffer instead of from dense (cores, n) masks.

Compaction is segment-id based and sort-free: the inclusive cumsum of a
core's spike row is a sorted vector, so the address of the j-th active
event is ``searchsorted(cumsum, j + 1)`` - one binary search per output
slot, O(K log n) per core, no scatter.  Slots past the live count come
out as ``n`` (the same pad value `repro.kernels.hat_encode` uses), so the
buffer *is* a truncated AER address stream in service order and the
arbiter's sparse policies can read boundary transitions directly.

The buffer holds ``capacity + 1`` entries: a frame with exactly
``capacity`` events still carries one trailing pad, which the HAT encode
energy model needs (the pad boundary toggle is part of the dense
address-stream mean it must reproduce bit-for-bit).  Frames with more
than ``capacity`` events per core overflow; callers detect this with
``counts > capacity`` and fall back to the dense tick
(`repro.interface.pipeline` wraps both in one ``lax.cond``).

Bit-identity: every latency/energy formula sums small integers in
float32, where addition is exact regardless of order, then applies the
same final ops (division by ``n``, ``where`` selects) as the dense path
(the contract `tests/conformance` enforces).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def compact_events(spikes: jnp.ndarray, capacity: int):
    """Compact a spike frame into per-core event address buffers.

    Args:
      spikes: (cores, n) bool frame.
      capacity: max events per core the buffer can hold (K).

    Returns:
      buf:    (cores, K + 1) int32 - each row holds that core's active
              addresses in ascending (service) order, padded with ``n``.
      counts: (cores,) int32 live event count per row.  Rows where
              ``counts > capacity`` have truncated buffers and must be
              routed to the dense fallback by the caller.
    """
    csum = jnp.cumsum(spikes, axis=1)                          # (C, n) int
    slots = jnp.arange(1, capacity + 2)
    buf = jax.vmap(lambda cs: jnp.searchsorted(cs, slots))(csum)
    return buf.astype(jnp.int32), csum[:, -1].astype(jnp.int32)


def event_indices(buf: jnp.ndarray, n: int):
    """Flat global source indices + live weights for accounting gathers.

    Args:
      buf: (cores, K + 1) compacted address buffer from `compact_events`.
      n:   neurons per core (the buffer's pad value).

    Returns:
      ev_idx: (cores * K,) int32 flat source-neuron indices (pad slots
              point at index 0 and are neutralized by ``ev_w``).
      ev_w:   (cores * K,) float32 1.0 on live events, 0.0 on pads.
    """
    cores = buf.shape[0]
    addr = buf[:, :-1]                                         # (C, K)
    real = addr < n
    base = jnp.arange(cores, dtype=jnp.int32)[:, None] * n
    ev_idx = jnp.where(real, addr + base, 0).reshape(-1)
    return ev_idx, real.reshape(-1).astype(jnp.float32)


def sparse_tick_ref(buf, counts, *, latency_fn, encode_fn):
    """Sparse arbiter policies, plain-jnp reference for the Pallas kernel.

    Computes the two per-core quantities the arbiter owes the tick from
    the compacted event buffer - tick latency and AER encode energy - the
    work `repro.kernels.sparse_tick.kernel` runs as one `pallas_call`.

    Args:
      buf, counts: output of `compact_events`.
      latency_fn:  ``(buf, counts (cores, 1)) -> (cores, 1) float32``
                   sparse arbiter policy
                   (`ArbiterScheme.sparse_tick_latency(ctx)`).
      encode_fn:   the same shape of policy for encode energy
                   (`ArbiterScheme.sparse_encode_energy(ctx)`).

    Returns:
      (latencies (cores,) f32, enc_per_core (cores,) f32)
    """
    col = counts[:, None]
    return latency_fn(buf, col)[:, 0], encode_fn(buf, col)[:, 0]
