"""Compile-once sessions over the core-interface pipeline.

`Interface(config).compile(params)` pre-builds everything the per-tick
step needs exactly once - the arbiter plan, the NoC subscription/link
tables (two-tier when ``cfg.chips > 1``), the CAM routing index (stored
tags decoded back to (chip, core, neuron) source addresses), the CAM
calibration constants - and returns an `InterfaceSession` whose `run` /
`run_batched` execute multi-timestep simulation as a single jit-compiled
`jax.lax.scan` (+`vmap` for the batched form) with streaming `StepStats`
accumulation.

Chip sharding: ``run(spikes, shard="chips")`` executes the per-chip slice
of every tick - the CAM match/scatter, the per-core arbiter latency, and
the AER encode stage - under `repro.compat.shard_map` over a 1D
``("chips",)`` device mesh (`repro.launch.mesh.make_chip_mesh`) of ``d``
devices, ``d`` the largest divisor of ``chips`` that the host's device
count allows: ``chips / d`` consecutive simulated chips per device, each
device running the per-chip body under `jax.vmap` over its own chips.
When that divisor is 1 (a single-device host, or a prime chip count above
the device count) the same per-chip body runs under `jax.vmap` over all
chips on one device instead, so results never depend on the host
topology.  Both mapped paths
reassemble the per-core vectors in fabric order and funnel through
`pipeline.accounting_stats`: currents are bit-identical to the unsharded
oracle on either path (and stats too under the vmap fallback); on a real
multi-device mesh the stats agree to float tolerance, since XLA may
partition the replicated accounting reductions differently.

This replaces the seed pattern of calling `fabric.step` in a Python loop,
which re-entered jit dispatch every tick and silently rebuilt the NoC
tables whenever the caller forgot to thread them through.

Observability: ``run(..., telemetry="ticks"|"cores")`` swaps the
accumulate-only carry for stacked per-tick `StepStats` scan ys (and, at
``"cores"``, per-core event/latency/hop breakdowns), all still under one
jit - see `repro.obs.telemetry` for the returned containers and their
sum-back invariants.  Compile and run dispatch are wrapped in
`repro.obs.trace` spans, no-ops unless a tracer is active.

Masked / ragged streams (the `repro.serve` substrate): ``run(spikes,
mask=...)`` and ``run_batched(spikes, mask=...)`` accept a per-tick bool
mask (``(T,)`` / ``(B, T)``).  Masked ticks contribute exactly zero to
the accumulated `StepStats` and zero currents, so tenants with ragged
stream lengths can be right-padded onto one batch and stay bit-identical
to their solo runs.  ``stats0`` seeds the scan's accumulator carry
(per-lane ``(B,)`` leaves in the batched form): chunked serving threads
the accumulator through successive calls, keeping the float accumulation
order exactly the tick-sequential order a single solo `run` uses - which
is what makes chunk-streamed stats bit-identical, not merely close.
Masking composes with ``shard="chips"`` (the masked scan runs the
per-chip mapped tick - the serving tier's cross-device tenant groups)
but not with telemetry; rejected combinations raise the typed
`CompositionError` instead of silently falling back to another path.

Fault injection (the `repro.ft` substrate): ``compile(params,
fault=FaultModel(...))`` bakes deterministic fabric faults into the
session - dead cores and corrupted CAM entries perturb the routing state
before tables are built, dropped events are masked by a jitted
per-(lane, tick) Bernoulli transform keyed on a dynamic ``fault_tick0``
offset - so faulted runs stay inside the one compiled step, degrade
predictably, and chunked faulted streams replay the exact fault sequence
of one uninterrupted run.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import arbiter as arb
from repro.core import cam as cam_mod
from repro.interface import pipeline
from repro.interface.config import as_interface_config
from repro.interface.stats import StepStats
from repro.obs import telemetry as obs_telemetry
from repro.obs import trace as obs_trace

_SHARD_MODES = (None, "chips")


class CompositionError(ValueError):
    """A requested run-mode combination is not supported.

    Typed rejection (still a ValueError for legacy handlers) raised when
    orthogonal execution modes cannot compose - today that is telemetry
    together with ``shard="chips"`` or with ``mask``.  Masking *does*
    compose with sharding (the serving tier's cross-device tenant
    groups); combinations rejected here are rejected loudly instead of
    silently falling back to a different execution path.
    """


class Interface:
    """Factory for precompiled sessions over one interface configuration."""

    def __init__(self, config):
        """config: `InterfaceConfig` or a legacy `FabricConfig`."""
        self.config = as_interface_config(config)

    def compile(self, params, fault=None) -> "InterfaceSession":
        """Bind routing state; build all plans/tables/constants once.

        fault: optional `repro.ft.faults.FaultModel` compiled into the
        session - dead cores / corrupted CAM entries perturb the routing
        state *before* tables are built, and dropped/dead-core spikes are
        masked at run time by a jit-compatible transform, so faulted runs
        stay inside the one compiled step and degrade instead of crash.

        Raises:
          ValueError: when the fault model does not fit the config, or
            ``config.impl == "pallas_sparse"`` and a configured scheme
            lacks sparse tick policies (`pipeline.resolve_sparse_plan`).
        """
        return InterfaceSession(self.config, params, fault=fault)

    def ppa_report(self) -> dict:
        """Unified area/latency/energy report for this configuration."""
        from repro.interface import report
        return report.ppa_report(self.config)


class InterfaceSession:
    """A precompiled (config, params) binding with scan-based execution.

    Attributes built once at construction:
      tables    NoC subscription/hop/link tables (`NocTables`, or
                `repro.noc.hierarchy.HierTables` when ``cfg.chips > 1``)
      arb_plan  arbiter plan (`ArbiterConfig`: scheme entry, levels, fill)
      routing   CAM tags decoded to (chip, core, neuron) source addresses
                (`RoutingIndex`) - the per-tick CAM match is a gather
                through it (or the `cam_search` kernel when
                ``cfg.impl == "pallas"``, or the
                `repro.kernels.sparse_tick` event-buffer kernel when
                ``cfg.impl == "pallas_sparse"``)
      cam_cycle_ns  CAM search cycle time for the configured variant
    """

    def __init__(self, config, params, fault=None):
        """Build every plan/table/constant once; see `Interface.compile`."""
        self.config = as_interface_config(config)
        if fault is not None:
            fault.validate(self.config)
            if fault.is_null:
                fault = None          # compiles exactly as fault-free
        self.fault = fault
        if fault is not None:
            params = fault.apply_params(params, self.config)
        self.params = params
        cfg = self.config
        with obs_trace.span("interface.compile", cores=cfg.cores,
                            chips=cfg.chips, impl=cfg.impl):
            self.tables = pipeline.build_tables(params, cfg)
            self.arb_plan = arb.ArbiterConfig(cfg.scheme, cfg.neurons_per_core)
            self.routing = pipeline.build_routing_index(params, cfg)
            self.cam_cycle_ns = cam_mod.cycle_time_ns(cfg.cam)
            if cfg.impl == "pallas_sparse":
                # Fail at compile, not mid-scan, when a scheme lacks the
                # sparse tick policies (e.g. hier_ring on a non-square n).
                pipeline.resolve_sparse_plan(cfg, self.arb_plan)
        tables, arb_plan, routing = self.tables, self.arb_plan, self.routing
        cam_cycle_ns = self.cam_cycle_ns

        def tick(p, spikes_cn):
            """One frame through the pipeline with the prebuilt plans."""
            return pipeline.interface_tick(p, spikes_cn, cfg, tables, arb_plan,
                                           routing=routing,
                                           cam_cycle_ns=cam_cycle_ns)

        def run(p, spikes_tcn):
            """Accumulate-only scan over a (T, C, n) stream."""
            def body(acc, s_t):
                currents, st = tick(p, s_t)
                return acc.accumulate(st), currents
            acc, currents = jax.lax.scan(body, StepStats.zeros(), spikes_tcn)
            return currents, acc

        self._tick = jax.jit(tick)
        self._run = jax.jit(run)
        self._run_batched = jax.jit(jax.vmap(run, in_axes=(None, 0)))
        self._run_fast = self._run_batched_fast = self._sparse_fits = None
        if cfg.impl == "pallas_sparse":
            # The per-tick overflow cond costs tens of us/tick on CPU - a
            # large fraction of the sparse tick itself.  Check the whole
            # stream against capacity ONCE per run() call (host-side) and
            # dispatch to a cond-free sparse scan when every frame fits;
            # streams with any overflowing frame keep the guarded scan.
            capacity = pipeline.resolve_sparse_plan(cfg, arb_plan)[3]

            def tick_fast(p, spikes_cn):
                return pipeline.interface_tick(
                    p, spikes_cn, cfg, tables, arb_plan, routing=routing,
                    cam_cycle_ns=cam_cycle_ns, sparse_unchecked=True)

            def run_fast(p, spikes_tcn):
                def body(acc, s_t):
                    currents, st = tick_fast(p, s_t)
                    return acc.accumulate(st), currents
                acc, currents = jax.lax.scan(body, StepStats.zeros(),
                                             spikes_tcn)
                return currents, acc

            self._run_fast = jax.jit(run_fast)
            self._run_batched_fast = jax.jit(
                jax.vmap(run_fast, in_axes=(None, 0)))
            self._sparse_fits = jax.jit(
                lambda s: jnp.max(jnp.sum(s != 0, axis=-1)) <= capacity)
        self._sharded_cache = None
        self._telemetry_cache = {}
        self._masked_cache = None
        self._masked_sharded_cache = None
        self._sharded_tick_cache = None
        self._fault_cache = None
        # the ("chips",) mesh the sharded tick runs on; None until it is
        # built, and None under the single-device vmap fallback
        self.chip_mesh = None

    # ---- execution -------------------------------------------------------

    def step(self, spikes) -> tuple[jnp.ndarray, StepStats]:
        """One tick.  spikes: (cores, neurons_per_core) bool."""
        return self._tick(self.params, self._check(spikes, 2))

    def run(self, spikes, shard: str | None = None, telemetry: str = "off",
            mask=None, stats0: StepStats | None = None, fault_tick0=None
            ) -> tuple[jnp.ndarray, StepStats]:
        """Multi-timestep simulation under one jit-compiled lax.scan.

        spikes: (T, cores, neurons_per_core) bool
        shard:  None (default) runs the flat fabric-wide tick; ``"chips"``
            maps the per-chip tick over a device mesh (see module
            docstring), falling back to vmap when the host has fewer
            devices than chips.  Sharded execution always uses the XLA
            gather backend for the CAM match (bit-identical to
            ``impl="pallas"``, which is tested against it).
        telemetry: ``"off"`` (default) is today's accumulate-only scan,
            returning ``(currents, accumulated stats)``.  ``"ticks"``
            additionally stacks the per-tick `StepStats` as scan ys and
            returns ``(currents, stats, TickTelemetry)``; ``"cores"``
            returns ``(currents, stats, CoreTelemetry)`` with per-core
            event/latency/hop breakdowns (see `repro.obs.telemetry`).
            Currents and accumulated stats are bit-identical in every
            mode.  Telemetry composes with the flat path only - combine
            it with ``shard="chips"`` on a multi-chip config and this
            raises (run unsharded for tier attribution).
        mask: optional (T,) bool - ticks where it is False contribute
            exactly zero stats and zero currents (padding lanes of a
            ragged stream).  Composes with ``shard="chips"`` (the masked
            scan steps the per-chip mapped tick); mutually exclusive
            with telemetry (typed `CompositionError`).
        stats0: optional `StepStats` seeding the accumulator carry (only
            with ``mask``); defaults to zeros.  Chunk-streamed callers
            thread the returned stats back in to keep accumulation
            bit-identical to one uninterrupted run.
        fault_tick0: global tick index of ``spikes[0]`` for the session's
            compiled `FaultModel` drop stream (only meaningful when the
            session was compiled with a spike-perturbing fault; defaults
            to 0 there).  A *dynamic* scalar: chunked callers pass their
            running offset without growing the jit cache, and chunked
            faulted runs stay bit-identical to one uninterrupted run.
        returns (currents (T, cores, neurons_per_core), accumulated stats);
        use ``stats.summary(ticks=T)`` for per-tick means.

        Raises:
          CompositionError: ``mask`` or ``shard="chips"`` combined with
            ``telemetry`` (a typed ValueError; masking composes with
            sharding, telemetry composes with neither).
          ValueError: on a spike stream whose trailing axes do not match
            the config; an unknown ``shard`` mode; ``stats0`` or a
            mis-shaped ``mask`` without a matching masked call; or
            ``fault_tick0`` on a session without a spike-perturbing
            fault.
        """
        spikes = self._check(spikes, 3)
        spikes = self._apply_fault("run", spikes, fault_tick0)
        if mask is not None:
            fns = self._masked_fns(shard, telemetry)
            mask = self._check_mask(mask, spikes, 1)
            acc0 = StepStats.zeros() if stats0 is None else stats0
            with obs_trace.span("interface.run", masked=True,
                                **self._shard_args(shard)):
                spikes = fns["mask_solo"](spikes, mask)
                return fns["run"](self.params, spikes, acc0)
        if stats0 is not None:
            raise ValueError("stats0 is only meaningful with mask")
        fn = self._shard_fn("run", shard)
        if telemetry != "off":
            t_fn = self._telemetry_fn("run", telemetry, sharded=fn is not None)
            with obs_trace.span("interface.run", telemetry=telemetry):
                return t_fn(self.params, spikes)
        if fn is not None:
            with obs_trace.span("interface.run", **self._shard_args(shard)):
                return fn(spikes)
        with obs_trace.span("interface.run"):
            if self._all_frames_fit(spikes):
                return self._run_fast(self.params, spikes)
            return self._run(self.params, spikes)

    def run_batched(self, spikes, shard: str | None = None,
                    telemetry: str = "off", mask=None,
                    stats0: StepStats | None = None, fault_tick0=None
                    ) -> tuple[jnp.ndarray, StepStats]:
        """Batched scan: spikes (B, T, cores, neurons_per_core) bool.

        Returns (currents (B, T, C, N), stats with (B,)-shaped leaves,
        each accumulated over that batch element's T ticks).  ``shard``
        behaves as in `run` (the batch axis is vmapped over the sharded
        scan); ``telemetry`` as in `run`, with the series leaves gaining
        a leading batch axis (``(B, T)`` / ``(B, T, cores)``).

        ``mask`` (B, T) bool marks the live ticks of each lane: masked
        ticks contribute zero stats/currents, so ragged tenant streams
        right-padded to one T stay bit-identical to their solo runs (an
        all-False lane is a no-op that returns its ``stats0`` row
        unchanged).  ``stats0`` seeds the per-lane accumulator carry
        ((B,)-shaped `StepStats` leaves; zeros when omitted) - thread the
        returned stats back in when chunking one long stream over
        multiple calls.  Composes with ``shard="chips"`` (each lane's
        scan steps the per-chip mapped tick, spreading the group over
        the chip mesh); mutually exclusive with telemetry.

        ``fault_tick0`` behaves as in `run`, per lane: a scalar (shared
        offset) or a (B,) vector of per-lane global tick offsets for the
        compiled `FaultModel`'s drop stream; each lane folds its index
        into the stream so lanes draw independent faults.

        Raises:
          ValueError: under the same conditions as `run` (shape/mode/
            composition violations), applied to the batched shapes.
        """
        spikes = self._check(spikes, 4)
        spikes = self._apply_fault("run_batched", spikes, fault_tick0)
        if mask is not None:
            fns = self._masked_fns(shard, telemetry)
            mask = self._check_mask(mask, spikes, 2)
            acc0 = stats0
            if acc0 is None:
                b = spikes.shape[0]
                acc0 = jax.tree.map(
                    lambda x: jnp.zeros((b,), x.dtype), StepStats.zeros())
            with obs_trace.span("interface.run_batched", masked=True,
                                **self._shard_args(shard)):
                spikes = fns["mask"](spikes, mask)
                return fns["run_batched"](self.params, spikes, acc0)
        if stats0 is not None:
            raise ValueError("stats0 is only meaningful with mask")
        fn = self._shard_fn("run_batched", shard)
        if telemetry != "off":
            t_fn = self._telemetry_fn("run_batched", telemetry,
                                      sharded=fn is not None)
            with obs_trace.span("interface.run_batched", telemetry=telemetry):
                return t_fn(self.params, spikes)
        if fn is not None:
            with obs_trace.span("interface.run_batched",
                                **self._shard_args(shard)):
                return fn(spikes)
        with obs_trace.span("interface.run_batched"):
            if self._all_frames_fit(spikes):
                return self._run_batched_fast(self.params, spikes)
            return self._run_batched(self.params, spikes)

    def _all_frames_fit(self, spikes) -> bool:
        """Host-side sparse precheck: does every frame of this stream fit
        the session's event capacity?  Always False off the pallas_sparse
        impl, so the plain scans stay untouched there.  One reduction over
        the stream plus one device sync per `run` call, amortized across
        all its ticks; empty streams trivially fit."""
        if self._sparse_fits is None:
            return False
        return spikes.size == 0 or bool(self._sparse_fits(spikes))

    # ---- masked / ragged streams -----------------------------------------

    def _masked_fns(self, shard: str | None, telemetry: str) -> dict:
        """The jitted masked-scan family for a shard mode; built lazily.

        ``shard=None`` is the flat masked scan.  ``shard="chips"`` on a
        multi-chip config runs the masked scan with the per-chip mapped
        tick (shard_map over the chip mesh, or the single-device vmap
        fallback) - the serving tier's cross-device tenant groups.  On a
        one-chip config the flat scan IS the per-chip tick, same as the
        unmasked path.  Telemetry still does not compose with masking
        (`CompositionError`): the masked scan's accumulator-as-argument
        carry has no ys slot for the stacked series.
        """
        if telemetry != "off":
            raise CompositionError(
                "mask does not compose with telemetry; run the masked "
                "scan without telemetry (currents and accumulated stats "
                "are bit-identical across paths)")
        if shard is not None:
            if shard not in _SHARD_MODES:
                raise ValueError(
                    f"unknown shard mode {shard!r}; expected one of "
                    f"{', '.join(repr(m) for m in _SHARD_MODES)}")
            if self.config.chips > 1:
                if self._masked_sharded_cache is None:
                    self._masked_sharded_cache = self._build_masked_sharded()
                return self._masked_sharded_cache
        if self._masked_cache is None:
            self._masked_cache = self._build_masked()
        return self._masked_cache

    def _build_masked(self) -> dict:
        """The plain accumulate scan, with the accumulator as an argument.

        Masking exploits an exact property of the tick: a tick whose
        spikes are all-False produces exactly-zero `StepStats` and zero
        currents for every registered arbiter/NoC scheme (asserted in
        tests/test_serve.py), so a masked tick is erased by
        ``spikes & mask`` *before* the scan and the scan body stays
        byte-for-byte the unmasked one - no predication nodes that could
        perturb XLA's float scheduling.  The accumulator is a scan
        *argument* (``acc0``) rather than the constant
        `StepStats.zeros()`, so chunked callers thread it through
        successive calls and preserve the tick-sequential float
        accumulation order of one uninterrupted run.
        """
        cfg = self.config
        tables, arb_plan, routing = self.tables, self.arb_plan, self.routing
        cam_cycle_ns = self.cam_cycle_ns

        def tick(p, spikes_cn):
            return pipeline.interface_tick(p, spikes_cn, cfg, tables, arb_plan,
                                           routing=routing,
                                           cam_cycle_ns=cam_cycle_ns)

        def run(p, spikes_tcn, acc0):
            def body(acc, s_t):
                currents, st = tick(p, s_t)
                return acc.accumulate(st), currents
            acc, currents = jax.lax.scan(body, acc0, spikes_tcn)
            return currents, acc

        # No buffer donation: the bool spikes cannot alias any output,
        # and the accumulator is a few floats per lane - while a donated
        # accumulator would leave a retried step (`repro.serve`) holding
        # a deleted buffer.
        mask_lane = jax.jit(lambda s, m: s & m[:, None, None])
        return {"run": jax.jit(run),
                "run_batched": jax.jit(jax.vmap(run, in_axes=(None, 0, 0))),
                "mask": jax.jit(jax.vmap(mask_lane)),
                "mask_solo": mask_lane}

    # ---- fault injection -------------------------------------------------

    def _apply_fault(self, kind: str, spikes, fault_tick0):
        """Run the compiled `FaultModel`'s jitted spike transform.

        No-op (and rejects ``fault_tick0``) when the session has no
        spike-perturbing fault, so the fault-free path stays byte-for-
        byte the plain one.  The tick offset is a dynamic argument -
        one cache entry covers every chunk offset.
        """
        if self.fault is None or not self.fault.perturbs_spikes:
            if fault_tick0 is not None:
                raise ValueError(
                    "fault_tick0 is only meaningful on a session compiled "
                    "with a spike-perturbing FaultModel (dead_cores or "
                    "drop_rate)")
            return spikes
        if self._fault_cache is None:
            self._fault_cache = self._build_fault()
        t0 = jnp.asarray(0 if fault_tick0 is None else fault_tick0,
                         jnp.int32)
        if kind == "run_batched":
            t0 = jnp.broadcast_to(t0, (spikes.shape[0],))
        return self._fault_cache[kind](spikes, t0)

    def _build_fault(self) -> dict:
        """Jitted dead-core/drop transforms; lanes fold their index in."""
        fault = self.fault

        def solo(s, t0):
            return fault.apply_spikes(s, tick0=t0, lane=jnp.int32(0))

        def lane(s, t0, i):
            return fault.apply_spikes(s, tick0=t0, lane=i)

        batched = jax.vmap(lane, in_axes=(0, 0, 0))

        def run_b(s, t0):
            lanes = jnp.arange(s.shape[0], dtype=jnp.int32)
            return batched(s, t0, lanes)

        return {"run": jax.jit(solo), "run_batched": jax.jit(run_b)}

    def _check_mask(self, mask, spikes, ndim: int) -> jnp.ndarray:
        mask = jnp.asarray(mask)
        if mask.shape != spikes.shape[:ndim]:
            raise ValueError(
                f"mask shape {mask.shape} does not cover the spike stream's "
                f"leading axes {spikes.shape[:ndim]}")
        if mask.dtype != jnp.bool_:
            mask = mask > 0
        return mask

    # ---- in-jit telemetry ------------------------------------------------

    def _telemetry_fn(self, kind: str, mode: str, sharded: bool):
        """The jitted telemetry scan for (kind, mode); built lazily once."""
        obs_telemetry.validate_mode(mode)
        if sharded:
            raise CompositionError(
                "telemetry is not supported together with shard='chips'; "
                "run unsharded (the default) to collect per-tick/per-core "
                "series - currents are bit-identical across both paths")
        if mode not in self._telemetry_cache:
            self._telemetry_cache[mode] = self._build_telemetry(mode)
        return self._telemetry_cache[mode][kind]

    def _build_telemetry(self, mode: str) -> dict:
        """Scan with stacked ys: per-tick `StepStats`, plus per-core
        breakdowns under ``"cores"``.  The tick body is the same
        `pipeline.interface_tick` the plain run uses, so currents and the
        accumulated stats stay bit-identical to ``telemetry="off"``."""
        cfg = self.config
        tables, arb_plan, routing = self.tables, self.arb_plan, self.routing
        cam_cycle_ns = self.cam_cycle_ns
        tick_telemetry = "cores" if mode == "cores" else "off"

        def tick(p, spikes_cn):
            return pipeline.interface_tick(p, spikes_cn, cfg, tables, arb_plan,
                                           routing=routing,
                                           cam_cycle_ns=cam_cycle_ns,
                                           telemetry=tick_telemetry)

        if mode == "ticks":
            def run(p, spikes_tcn):
                def body(acc, s_t):
                    currents, st = tick(p, s_t)
                    return acc.accumulate(st), (currents, st)
                acc, (currents, series) = jax.lax.scan(
                    body, StepStats.zeros(), spikes_tcn)
                return currents, acc, obs_telemetry.TickTelemetry(
                    per_tick=series)
        else:
            def run(p, spikes_tcn):
                def body(acc, s_t):
                    currents, st, core = tick(p, s_t)
                    return acc.accumulate(st), (currents, st, core)
                acc, (currents, series, core_series) = jax.lax.scan(
                    body, StepStats.zeros(), spikes_tcn)
                return currents, acc, obs_telemetry.CoreTelemetry(
                    per_tick=series, per_core=core_series)

        return {"run": jax.jit(run),
                "run_batched": jax.jit(jax.vmap(run, in_axes=(None, 0)))}

    # ---- chip sharding ---------------------------------------------------

    def _shard_fn(self, kind: str, shard: str | None):
        if shard is None:
            return None
        if shard not in _SHARD_MODES:
            raise ValueError(
                f"unknown shard mode {shard!r}; expected one of "
                f"{', '.join(repr(m) for m in _SHARD_MODES)}")
        if self.config.chips == 1:
            return None          # flat fabric: the unsharded scan IS the tick
        if self._sharded_cache is None:
            self._sharded_cache = self._build_sharded()
        return self._sharded_cache[kind]

    def _shard_args(self, shard: str | None) -> dict:
        """Span args of a call: the shard mode and, on a multi-chip
        fabric, how its chips sit on the devices.  Empty for flat calls."""
        if shard is None:
            return {}
        if self.config.chips == 1:
            return {"shard": shard}
        self._sharded_tick()
        devices = 1 if self.chip_mesh is None else self.chip_mesh.devices.size
        return {"shard": shard, "devices": devices,
                "chips_per_device": self.config.chips // devices}

    def _chip_body(self):
        """Per-chip tick work: local CAM match/scatter + encode stage.

        Closure signature: (params_chip, src_idx, active, spikes_chip,
        spikes_flat_global) -> (currents (cpc, n), latencies (cpc,),
        enc_per_core (cpc,), hits scalar).  Pure per-chip function - no
        collectives - so the identical body runs under shard_map (the
        replicated ``spikes_flat`` argument becomes the one all-gather at
        the shard_map boundary) and under the single-device vmap fallback.
        """
        cfg = self.config
        n = cfg.neurons_per_core
        arb_plan = self.arb_plan
        scheme = cfg.scheme
        stream_cfg = (cfg if cfg.impl == "xla"
                      else dataclasses.replace(cfg, impl="xla"))

        def chip_body(p_chip, src_idx, active, spikes_chip, spikes_flat):
            drive = (spikes_flat[src_idx] & active).astype(jnp.float32)
            currents = pipeline.route_currents(drive, p_chip.weights,
                                               p_chip.targets, n)
            latencies = arb.batched_tick_latency(arb_plan, spikes_chip)
            addr = pipeline._addr_streams(spikes_chip, stream_cfg, n)
            enc = jax.vmap(
                lambda seq: arb.encode_energy_units(scheme, n, seq))(addr)
            return currents, latencies, enc, jnp.sum(drive)

        return chip_body

    def _sharded_tick(self):
        """The per-chip mapped tick closure, built (and placed) once.

        Shared by the plain sharded scans and the masked sharded scans,
        so the per-chip constants are device-pinned a single time and
        both families step through the identical tick body.
        """
        if self._sharded_tick_cache is not None:
            return self._sharded_tick_cache
        cfg = self.config
        chips, cpc, n = cfg.chips, cfg.cores_per_chip, cfg.neurons_per_core
        body = self._chip_body()

        # static per-chip operands, stacked (chips, cores_per_chip, ...)
        per_chip = jax.tree.map(
            lambda x: x.reshape((chips, cpc) + x.shape[1:]),
            (self.params, self.routing.src_idx, self.routing.active))

        # the body over a run of consecutive chips: all of them on the
        # fallback, a device's own block of them on the mesh
        over_chips = jax.vmap(body, in_axes=(0, 0, 0, 0, None))
        devices = max(d for d in range(1, min(chips, len(jax.devices())) + 1)
                      if chips % d == 0)
        if devices > 1:
            from repro.launch import mesh as launch_mesh
            from repro.parallel import sharding as shd

            with obs_trace.span("interface.chip_mesh", chips=chips,
                                devices=devices,
                                chips_per_device=chips // devices):
                mesh = launch_mesh.make_chip_mesh(devices)
                # pin the per-chip constants to their devices once, at build
                per_chip = jax.device_put(
                    per_chip,
                    shd.to_named(shd.leading_axis_specs(per_chip, "chips"),
                                 mesh))

            self.chip_mesh = mesh
            mapped = compat.shard_map(
                over_chips, mesh=mesh,
                in_specs=(P("chips"), P("chips"), P("chips"), P("chips"),
                          P()),
                out_specs=P("chips"))
        else:
            mapped = over_chips

        p_chips, src_idx, active = per_chip
        tables, cam_cycle_ns = self.tables, self.cam_cycle_ns
        valid = self.params.valid

        def tick(spikes_cn):
            if spikes_cn.dtype != jnp.bool_:
                spikes_cn = spikes_cn > 0
            spikes_flat = spikes_cn.reshape(-1)
            sp_chips = spikes_cn.reshape(chips, cpc, n)
            cur_c, lat_c, enc_c, hits_c = mapped(p_chips, src_idx, active,
                                                 sp_chips, spikes_flat)
            currents = cur_c.reshape(cfg.cores, n)
            stats = pipeline.accounting_stats(
                cfg, tables, spikes_cn, lat_c.reshape(cfg.cores),
                enc_c.reshape(cfg.cores), jnp.sum(hits_c), valid,
                cam_cycle_ns)
            return currents, stats

        self._sharded_tick_cache = tick
        return tick

    def _build_sharded(self) -> dict:
        tick = self._sharded_tick()

        def run(spikes_tcn):
            def scan_body(acc, s_t):
                currents, st = tick(s_t)
                return acc.accumulate(st), currents
            acc, currents = jax.lax.scan(scan_body, StepStats.zeros(),
                                         spikes_tcn)
            return currents, acc

        return {"run": jax.jit(run), "run_batched": jax.jit(jax.vmap(run))}

    def _build_masked_sharded(self) -> dict:
        """The masked scan family over the per-chip mapped tick.

        Same masking contract as `_build_masked` - masked ticks are
        erased by ``spikes & mask`` *before* the scan, the accumulator
        rides as the ``acc0`` argument - but each tick runs the
        `_sharded_tick` body (shard_map over the chip mesh, or the vmap
        fallback), so one serving-tier `TenantGroup` spreads its lanes'
        fabric work across `launch.mesh` devices.  Signatures match the
        flat masked family (the leading ``params`` argument is unused:
        the sharded tick closes over its device-pinned per-chip
        constants), so callers dispatch on the dict alone.
        """
        tick = self._sharded_tick()

        def run(p, spikes_tcn, acc0):
            del p  # per-chip constants are baked into the sharded tick
            def body(acc, s_t):
                currents, st = tick(s_t)
                return acc.accumulate(st), currents
            acc, currents = jax.lax.scan(body, acc0, spikes_tcn)
            return currents, acc

        mask_lane = jax.jit(lambda s, m: s & m[:, None, None])
        return {"run": jax.jit(run),
                "run_batched": jax.jit(jax.vmap(run, in_axes=(None, 0, 0))),
                "mask": jax.jit(jax.vmap(mask_lane)),
                "mask_solo": mask_lane}

    def state_sharding(self, shard: str | None = None):
        """Where state carried across calls (accumulators) should live.

        One device for flat runs; replicated over the chip mesh (all of
        its devices, each holding ``chips / devices`` simulated chips) for
        ``shard="chips"`` runs that take the mesh, so the carry sits on
        the same devices as the per-chip constants the sharded tick
        closes over (a jit refuses arguments committed elsewhere).
        """
        if shard is not None and self.config.chips > 1:
            self._sharded_tick()
            if self.chip_mesh is not None:
                return NamedSharding(self.chip_mesh, P())
        return SingleDeviceSharding(jax.devices()[0])

    # ---- introspection ---------------------------------------------------

    def ppa_report(self) -> dict:
        """Unified area/latency/energy report for this configuration."""
        from repro.interface import report
        return report.ppa_report(self.config)

    def _check(self, spikes, ndim: int) -> jnp.ndarray:
        spikes = jnp.asarray(spikes)
        if spikes.ndim != ndim or spikes.shape[-2:] != (
                self.config.cores, self.config.neurons_per_core):
            raise ValueError(
                f"expected {ndim}-d spikes ending in "
                f"({self.config.cores}, {self.config.neurons_per_core}), "
                f"got shape {spikes.shape}")
        return spikes
