#!/usr/bin/env python3
"""Drive the fabric's main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed 0] [--ticks 256] [--lanes 8]
    python chip_smoke.py --chips 4        # the chip-sharded path alone

Everything runs in this one process; no child process touches the chip.

With no ``--chips`` option (one chip):

1. platform  ``jax.default_backend()`` must be ``"tpu"``; otherwise the
             script exits non-zero before any work.  There is no CPU
             fallback.
2. session   the shipped deployments at full width
             (`repro.configs.paper_dynaps`: ``config()`` 4 x 256 x 512 and
             ``scaled_config()`` 16 x 256 x 512, HAT arbiter), each under
             ``sparse_poisson`` and ``synchronized_burst`` streams of
             ``--ticks`` ticks in ``--lanes`` lanes.  For every impl
             (``xla``, ``pallas``, ``pallas_sparse``) a compiled session's
             `run` (lane 0) and `run_batched` (all lanes) are held to the
             dense oracle (``interface_tick(oracle=True)`` scanned over the
             same spikes on the same device) under the conformance contract
             of ``tests/conformance/paths.py``.  The two Pallas impls must
             carry a TPU kernel (``tpu_custom_call``) in every compiled
             scan they ran.
3. serve     a `ServeEngine` with four tenants on ``scaled_config`` and
             ``impl="pallas_sparse"``: chunks are submitted, the background
             pump is started and stopped with a drain; the ledger must close
             (submitted == served + shed + pending), every tenant's served
             currents and stats must match its solo `session.run` under the
             same contract, and the group's batched step must hold one jit
             cache entry.

With ``--chips N`` (N > 1), only the chip-sharded path runs: a
DYNAPs-style board of N chips x 4 cores x 256 x 512 under ``clustered``
traffic, `run(shard="chips")` against the unsharded `run`, and a
``shard="chips"`` serving group against the unsharded solo runs.  The
sharded session must have taken its device mesh, not the single-device
vmap fallback.

Earlier lines print observations (device, first-call and warm seconds,
which impls carried a kernel); they are not benchmark numbers.  The last
line is one JSON object: ``{"ok": true, "device": {...}}``.  Any failed
check raises, so the script exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))
IMPLS = ("xla", "pallas", "pallas_sparse")
TRAFFICS = ("sparse_poisson", "synchronized_burst")
# conformance path name of each impl (tests/conformance/paths.py)
PATH_NAMES = {"xla": "event", "pallas": "pallas",
              "pallas_sparse": "pallas_sparse"}


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def timed(fn, *args, **kw):
    """(result, seconds) of one call, ended by `block_until_ready`."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def lane(tree, i: int):
    return jax.tree.map(lambda x: x[i], tree)


def has_kernel(jitted, *args) -> bool:
    """Whether the compiled program holds a TPU kernel (Mosaic call)."""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


# ---- session phase ---------------------------------------------------------


def oracle_scan(cfg, params):
    """The dense oracle tick scanned over a (lanes, T, C, n) batch, jitted."""
    from repro.interface import StepStats, pipeline

    tables = pipeline.build_tables(params, cfg)

    def run(spikes):
        def body(acc, s_t):
            cur, st = pipeline.interface_tick(params, s_t, cfg, tables,
                                              oracle=True)
            return acc.accumulate(st), cur
        acc, cur = jax.lax.scan(body, StepStats.zeros(), spikes)
        return cur, acc

    return jax.jit(jax.vmap(run))


def session_phase(name: str, cfg, seed: int, ticks: int, lanes: int) -> None:
    import jax.numpy as jnp

    from repro import traffic
    from repro.interface import Interface, random_connectivity
    from tests.conformance.paths import assert_conformant

    params = random_connectivity(jax.random.PRNGKey(seed), cfg)
    oracle = oracle_scan(cfg, params)
    streams, refs = {}, {}
    for t_i, scenario in enumerate(TRAFFICS):
        keys = jax.random.split(jax.random.PRNGKey(seed + 1 + t_i), lanes)
        batch = jnp.stack([traffic.generate(scenario, k, ticks, cfg)
                           for k in keys])
        refs[scenario], secs = timed(oracle, batch)
        streams[scenario] = batch
        log(f"{name} oracle {scenario}: lanes={lanes} ticks={ticks} "
            f"events={float(jnp.sum(batch)):.0f} first_call_s={secs:.3f}")

    for impl in IMPLS:
        session = Interface(dataclasses.replace(cfg, impl=impl)).compile(params)
        for scenario in TRAFFICS:
            batch, ref = streams[scenario], refs[scenario]
            label = f"{name}/{impl}/{scenario}"
            got, first_s = timed(session.run, batch[0])
            got, warm_s = timed(session.run, batch[0])
            assert_conformant({"oracle": lane(ref, 0), PATH_NAMES[impl]: got},
                              label + "/run")
            got_b, b_first_s = timed(session.run_batched, batch)
            got_b, b_warm_s = timed(session.run_batched, batch)
            for i in range(lanes):
                assert_conformant({"oracle": lane(ref, i),
                                   PATH_NAMES[impl]: lane(got_b, i)},
                                  f"{label}/run_batched[{i}]")
            fast = session._all_frames_fit(batch)
            run_fn = session._run_fast if fast else session._run
            batched_fn = session._run_batched_fast if fast else session._run_batched
            kernel = (has_kernel(run_fn, session.params, batch[0]) and
                      has_kernel(batched_fn, session.params, batch))
            if impl != "xla":
                require(kernel, f"{label}: no TPU kernel in the compiled scan")
            log(f"{label}: matches oracle (run + {lanes} lanes); "
                f"run first_call_s={first_s:.3f} warm_s={warm_s:.4f}; "
                f"run_batched first_call_s={b_first_s:.3f} "
                f"warm_s={b_warm_s:.4f}; kernel={'yes' if kernel else 'no'}"
                + ("; sparse_fast_path" if fast else ""))


# ---- serve phase -----------------------------------------------------------


def serve_and_check(cfg, specs, chunk: int, rounds: int, paths: tuple):
    """Serve ``specs`` through the background pump; check ledger and solo.

    Each tenant's served currents and stats are held to its unsharded solo
    `session.run` under the conformance contract, as the pair of path
    names ``paths`` (solo first).  Returns the engine.
    """
    import numpy as np

    from repro.interface import Interface
    from repro.serve import ServeEngine, default_connectivity
    from tests.conformance.paths import assert_conformant

    engine = ServeEngine(flush_ticks=chunk, flush_deadline_s=0.0,
                         keep_currents=True)
    for spec in specs:
        engine.register(spec)
    streams = {s.name: [np.asarray(s.stream(chunk, round=r))
                        for r in range(rounds)] for s in specs}
    for r in range(rounds):
        for spec in specs:
            engine.submit(spec.name, streams[spec.name][r])
    t0 = time.perf_counter()
    engine.start()
    engine.stop(drain=True)
    wall_s = time.perf_counter() - t0

    acct = engine.accounting()
    require(acct["closes"], f"ledger does not close: {acct}")
    for name, row in acct["tenants"].items():
        require(row["served"] == row["submitted"] == chunk * rounds
                and row["pending"] == 0 and row["shed"] == 0,
                f"tenant {name} not fully served: {row}")
    entries = sum(g.jit_cache_entries() for g in engine.groups.values())
    require(len(engine.groups) == 1 and entries == 1,
            f"{len(engine.groups)} groups, {entries} jit cache entries")

    solo = Interface(cfg).compile(
        default_connectivity(cfg, specs[0].connectivity_seed))
    for spec in specs:
        served = (engine.currents(spec.name), engine.tenant_stats(spec.name))
        assert_conformant(
            {paths[0]: solo.run(np.concatenate(streams[spec.name])),
             paths[1]: served}, f"serve/{spec.name}")
    rec = engine.serve_report()[-1]
    log(f"serve: tenants={len(specs)} ticks={rec['ticks']} "
        f"events={rec['events']:.0f} wall_s={wall_s:.3f} "
        f"busy_s={rec['busy_s']:.3f}; ledger closes; matches solo runs; "
        f"jit_cache_entries={entries}")
    return engine


def serve_phase(cfg, seed: int) -> None:
    from repro.serve import TenantSpec

    cfg = dataclasses.replace(cfg, impl="pallas_sparse")
    specs = [TenantSpec(f"t{i}", cfg, scenario=TRAFFICS[i % 2], seed=seed + i)
             for i in range(4)]
    serve_and_check(cfg, specs, chunk=32, rounds=3,
                    paths=("event", "pallas_sparse"))


# ---- chip-sharded phase ----------------------------------------------------


def chips_phase(chips: int, seed: int, ticks: int) -> None:
    from repro import traffic
    from repro.interface import Interface, InterfaceConfig, random_connectivity
    from repro.serve import TenantSpec
    from tests.conformance.paths import assert_conformant

    require(len(jax.devices()) >= chips,
            f"--chips {chips} needs {chips} devices, found {jax.devices()}")
    cfg = InterfaceConfig(chips=chips, cores_per_chip=4, neurons_per_core=256,
                          cam_entries_per_core=512, scheme="hier_tree")
    params = random_connectivity(jax.random.PRNGKey(seed), cfg)
    spikes = traffic.generate("clustered", jax.random.PRNGKey(seed + 1),
                              ticks, cfg)
    session = Interface(cfg).compile(params)
    flat, flat_s = timed(session.run, spikes)
    sharded, first_s = timed(session.run, spikes, shard="chips")
    sharded, warm_s = timed(session.run, spikes, shard="chips")
    mesh = session.chip_mesh
    require(mesh is not None and mesh.devices.size == chips,
            "run(shard='chips') took the vmap fallback, not the chip mesh")
    # both runs share one partitioning, so the contract's chip group holds
    # them to each other on every field
    assert_conformant({"chips2": flat, "chips2_sharded": sharded},
                      "board/run")
    log(f"board {chips}x4x256x512 clustered ticks={ticks}: mesh "
        f"{dict(mesh.shape)} on {[d.id for d in mesh.devices.flat]}; "
        f"sharded run == unsharded run; unsharded first_call_s={flat_s:.3f}; "
        f"sharded first_call_s={first_s:.3f} warm_s={warm_s:.4f}")

    specs = [TenantSpec(f"b{i}", cfg, scenario="clustered", seed=seed + i,
                        connectivity_seed=seed, shard="chips")
             for i in range(4)]
    engine = serve_and_check(cfg, specs, chunk=32, rounds=2,
                             paths=("chips2", "chips2_sharded"))
    group = next(iter(engine.groups.values()))
    require(group.session.chip_mesh is not None,
            "the shard='chips' serve group took the vmap fallback")
    log("board serve group: sharded over the chip mesh; "
        "served == unsharded solo runs")


# ---- entry point -----------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ticks", type=int, default=256,
                    help="ticks per stream (default: %(default)s)")
    ap.add_argument("--lanes", type=int, default=8,
                    help="lanes of the run_batched checks (default: %(default)s)")
    ap.add_argument("--chips", type=int, default=1,
                    help="N > 1 runs only the N-chip sharded path")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {backend!r}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.configs import paper_dynaps
    from repro.interface import InterfaceConfig
    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache}")

    t0 = time.perf_counter()
    if args.chips > 1:
        chips_phase(args.chips, args.seed, args.ticks)
    else:
        for name, make in (("paper", paper_dynaps.config),
                           ("scaled", paper_dynaps.scaled_config)):
            cfg = InterfaceConfig.from_fabric(make().fabric)
            session_phase(name, cfg, args.seed, args.ticks, args.lanes)
        serve_phase(InterfaceConfig.from_fabric(
            paper_dynaps.scaled_config().fabric), args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
