"""`InterfaceConfig`: the validated static description of one fabric.

Field-compatible with the legacy `repro.core.fabric.FabricConfig` (same
attribute names), so either type drives `Interface` / `interface_tick`.
Unlike the legacy config, construction is *validated*:

  * ``cam_entries_per_core`` and an explicit ``cam=CamConfig(...)`` must
    agree (the legacy config silently ignored the former),
  * the arbiter scheme and the CAM variant must be registered,
  * the NoC scheme is validated by `NocConfig` itself.
"""

from __future__ import annotations

import dataclasses
import math

from repro.core import cam as cam_mod
from repro.noc import topology as noc_topology


def resolve_cam(cam: cam_mod.CamConfig | None, entries: int | None,
                default_entries: int = 512):
    """Shared cam/cam_entries_per_core reconciliation.

    Returns the effective ``(cam, entries)`` pair; raises `ValueError`
    when an explicit config and an explicit entry count disagree.
    """
    if cam is None:
        cam = cam_mod.CamConfig(entries=default_entries if entries is None
                                else entries)
    elif entries is not None and cam.entries != entries:
        raise ValueError(
            f"cam_entries_per_core={entries} conflicts with explicit "
            f"cam=CamConfig(entries={cam.entries}); pass one or make them agree")
    return cam, cam.entries


def resolve_chips(chips: int, cores: int | None,
                  cores_per_chip: int | None, default_cores: int = 4):
    """Shared chips/cores/cores_per_chip reconciliation.

    ``cores`` is always the *total* core count across chips; configs store
    the resolved pair, so both fields survive `dataclasses.replace`.
    Resolution order:

      * ``cores`` given (never None after a config has resolved once, so
        every ``dataclasses.replace(cfg, chips=k)`` lands here): it is
        authoritative - ``chips`` must divide it, and ``cores_per_chip``
        is (re-)derived.  A disagreeing ``cores_per_chip`` is treated as
        stale, not an error: the derived field necessarily rides along
        through ``replace``.
      * ``cores`` omitted: total = ``chips * cores_per_chip`` (or the
        default core count when neither is given).

    Note the asymmetry this implies: to *repartition* an existing config,
    replace ``chips`` - ``replace(cfg, cores_per_chip=...)`` alone is
    overridden by the explicit stored ``cores``.  A ``cores_per_chip``
    that cannot be a stale derived value (it does not divide ``cores``)
    raises.

    Returns the effective ``(cores, cores_per_chip)`` pair.
    """
    if not isinstance(chips, int) or chips < 1:
        raise ValueError(f"chips must be a positive int, got {chips!r}")
    if cores is None:
        cores = (chips * cores_per_chip if cores_per_chip is not None
                 else default_cores)
    if cores_per_chip is not None and chips * cores_per_chip == cores:
        return cores, cores_per_chip
    if cores_per_chip is not None and cores % cores_per_chip != 0:
        raise ValueError(
            f"cores_per_chip={cores_per_chip} conflicts with cores={cores} "
            f"and cannot be a stale derived value; pass chips (and "
            f"optionally cores_per_chip) to repartition")
    if cores % chips != 0:
        raise ValueError(
            f"cores={cores} conflicts with chips={chips}"
            + (f" (cores_per_chip={cores_per_chip})"
               if cores_per_chip is not None else "")
            + ": chips must divide the total core count "
            "(or pass cores_per_chip alone to derive the total)")
    return cores, cores // chips


@dataclasses.dataclass(frozen=True)
class InterfaceConfig:
    """Static description of the full core-interface pipeline.

    chips:   chip tier of the fabric.  ``cores`` is always the *total*
             core count (``chips x cores_per_chip``); every chip carries
             its own ``cores_per_chip``-core mesh and chips are joined by
             an inter-chip router level (`repro.noc.hierarchy`).  With
             the default ``chips=1`` the fabric is the flat single-chip
             mesh and behaves bit-identically to configs predating the
             chip tier.
    scheme:  arbiter architecture (registry: `repro.interface.ARBITERS`)
    cam:     CAM variant/size (registry: `repro.interface.CAM_VARIANTS`)
    noc:     transport scheme (registry: `repro.interface.NOC_SCHEMES`)
    impl:    tick compute backend - "xla" (gather/scatter fast path),
             "pallas" (route the CAM match through the
             `repro.kernels.cam_search` kernel and the AER address stream
             through `repro.kernels.hat_encode`; falls back to interpret
             mode off-TPU), or "pallas_sparse" (the sparse event
             path: per-core event compaction feeding the
             `repro.kernels.sparse_tick` kernel for arbiter latency +
             AER encode energy and event-indexed accounting, with a
             dense fallback when a core exceeds ``sparse_capacity``
             events).  Currents and stats are bit-identical across
             impls.
    sparse_capacity: per-core event-buffer capacity for
             ``impl="pallas_sparse"``; ``None`` applies the
             `repro.kernels.sparse_tick.ops.default_capacity` heuristic
             (n/8, at least 8).  Effective values are clamped to
             ``neurons_per_core - 1``; ticks where any core fires more
             events than this run the dense tick instead (bit-identical
             either way - the knob trades sparse-path coverage against
             per-tick buffer work).  Ignored by the other impls.
    """

    cores: int | None = None                  # total; default 4 when omitted
    neurons_per_core: int = 256
    cam_entries_per_core: int | None = None   # defaults to 512 w/o explicit cam
    scheme: str = "hier_tree"
    cam: cam_mod.CamConfig | None = None
    noc: noc_topology.NocConfig | None = None
    impl: str = "xla"
    chips: int = 1
    cores_per_chip: int | None = None         # derived: cores // chips
    sparse_capacity: int | None = None        # pallas_sparse event budget

    def __post_init__(self):
        cores, per_chip = resolve_chips(self.chips, self.cores,
                                        self.cores_per_chip)
        object.__setattr__(self, "cores", cores)
        object.__setattr__(self, "cores_per_chip", per_chip)
        cam, entries = resolve_cam(self.cam, self.cam_entries_per_core)
        object.__setattr__(self, "cam", cam)
        object.__setattr__(self, "cam_entries_per_core", entries)
        if self.noc is None:
            object.__setattr__(self, "noc", noc_topology.NocConfig())
        if self.impl not in ("xla", "pallas", "pallas_sparse"):
            raise ValueError(
                f"unknown impl {self.impl!r}; expected 'xla', 'pallas' or "
                f"'pallas_sparse'")
        if self.sparse_capacity is not None and self.sparse_capacity < 1:
            raise ValueError(
                f"sparse_capacity must be a positive event count, got "
                f"{self.sparse_capacity}")
        # Fail at construction, not at first tick, on unregistered schemes.
        from repro.core import arbiter as _arb  # deferred: avoids import cycle
        from repro.interface import registry
        if self.scheme not in registry.ARBITERS:
            raise ValueError(
                f"unknown arbiter scheme {self.scheme!r}; registered: "
                f"{', '.join(registry.ARBITERS.names())}")
        if self.cam.variant not in registry.CAM_VARIANTS:
            raise ValueError(
                f"unknown CAM variant {self.cam.variant!r}; registered: "
                f"{', '.join(registry.CAM_VARIANTS.names())}")
        del _arb

    @property
    def tag_bits(self) -> int:
        """AER address width: bits needed to tag every neuron uniquely."""
        return max(1, math.ceil(math.log2(self.cores * self.neurons_per_core)))

    @classmethod
    def from_fabric(cls, cfg) -> "InterfaceConfig":
        """Lift a legacy `FabricConfig` into a validated `InterfaceConfig`."""
        return cls(cores=cfg.cores, neurons_per_core=cfg.neurons_per_core,
                   scheme=cfg.scheme, cam=cfg.cam, noc=cfg.noc,
                   impl=getattr(cfg, "impl", "xla"),
                   chips=getattr(cfg, "chips", 1),
                   sparse_capacity=getattr(cfg, "sparse_capacity", None))

    def fabric(self):
        """The equivalent legacy `FabricConfig` (for un-migrated call sites)."""
        from repro.core import fabric as fabric_mod
        return fabric_mod.FabricConfig(
            cores=self.cores, neurons_per_core=self.neurons_per_core,
            scheme=self.scheme, cam=self.cam, noc=self.noc, impl=self.impl,
            chips=self.chips, sparse_capacity=self.sparse_capacity)


def as_interface_config(config) -> InterfaceConfig:
    """Accept an `InterfaceConfig` or any field-compatible legacy config."""
    if isinstance(config, InterfaceConfig):
        return config
    return InterfaceConfig.from_fabric(config)
