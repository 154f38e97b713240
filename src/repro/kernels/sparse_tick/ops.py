"""Dispatch layer for the sparse event tick.

`sparse_tick` mirrors the `repro.kernels.cam_search` /
`repro.kernels.hat_encode` ops idiom: an ``impl`` switch between the
plain-jnp reference (``"xla"``) and the Pallas kernel (``"pallas"``,
interpret mode off-TPU by default), with shape validation at the
dispatch boundary so kernel code never sees malformed operands.

Capacity policy: the per-core event buffer holds ``capacity`` live
addresses (+1 pad slot).  `resolve_capacity` turns the user-facing
`InterfaceConfig.sparse_capacity` knob (``None`` = heuristic
``max(8, n // 8)``) into the effective value, clamped to ``n - 1`` so a
full-frame burst always overflows into the dense fallback - which keeps
the trailing pad slot (and with it the HAT encode-energy boundary term)
present whenever the sparse path runs.
"""

from __future__ import annotations

import jax

from repro.kernels.sparse_tick import kernel as sparse_kernel
from repro.kernels.sparse_tick import ref

compact_events = ref.compact_events
event_indices = ref.event_indices

MIN_CAPACITY = 8
CAPACITY_DIVISOR = 8


def default_capacity(n: int) -> int:
    """Heuristic event capacity per core: n/8, at least `MIN_CAPACITY`."""
    return max(MIN_CAPACITY, n // CAPACITY_DIVISOR)


def resolve_capacity(requested: int | None, n: int) -> int:
    """Effective buffer capacity for a fabric with ``n`` neurons/core.

    ``requested=None`` applies `default_capacity`; explicit values must
    be positive.  Either way the result is clamped to ``n - 1``: a frame
    where every neuron fires must overflow to the dense tick, so the
    sparse encode-energy model always sees its pad boundary.
    """
    if requested is None:
        requested = default_capacity(n)
    if requested < 1:
        raise ValueError(
            f"sparse_capacity must be a positive event count, got "
            f"{requested}")
    return max(1, min(requested, n - 1))


def sparse_tick(buf, counts, *, latency_fn, encode_fn, impl: str = "pallas",
                interpret: bool | None = None):
    """Sparse arbiter policies: tick latency + encode energy per core.

    Args:
      buf, counts: output of `compact_events` ((cores, K + 1) int32 and
                  (cores,) int32).
      latency_fn: resolved ``ArbiterScheme.sparse_tick_latency(ctx)``.
      encode_fn:  resolved ``ArbiterScheme.sparse_encode_energy(ctx)``.
      impl:       ``"pallas"`` (the kernel) or ``"xla"`` (reference).
      interpret:  force/suppress Pallas interpret mode; ``None`` picks
                  interpret automatically off-TPU.

    Returns:
      (latencies (cores,) f32, enc_per_core (cores,) f32)

    Raises:
      ValueError: on an unknown ``impl`` or mismatched operand shapes.
    """
    if impl not in ("xla", "pallas"):
        raise ValueError(
            f"unknown sparse_tick impl {impl!r}; expected 'xla' or 'pallas'")
    if buf.ndim != 2 or counts.shape != buf.shape[:1]:
        raise ValueError(
            f"event buffer shapes {buf.shape}/{counts.shape} do not match "
            f"one count per core")
    if impl == "xla":
        return ref.sparse_tick_ref(buf, counts, latency_fn=latency_fn,
                                   encode_fn=encode_fn)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return sparse_kernel.sparse_tick_pallas(
        buf, counts, latency_fn=latency_fn, encode_fn=encode_fn,
        interpret=interpret)
