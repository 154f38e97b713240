"""A fleet of tenants streaming requests into one `ServeEngine`.

Set-up makes the wiring and a pool of ``pool`` requests (``request_ticks``
ticks each) on the device from the seed, registers ``tenants`` tenants on
one configuration and wiring (one group, one lane each), starts the
engine's background pump and serves ``warmup_rounds`` requests a tenant,
which compiles the group's one batched step.

The window is an open loop at ``rate_per_s`` requests a second: the
arrival times are a fixed set of exponential gaps, their order drawn from
the seed, so every seed offers the same load; requests go to the tenants
evenly, in an order drawn from the seed, and each carries a pool request
drawn from the seed.  A request's latency runs from its scheduled arrival
to the moment ``ticks_served(tenant)`` (the engine's public accounting;
a tenant's requests are served in order) shows its last tick served,
polled every ``poll_s`` seconds.  Requests still in flight when the
window closes are waited for, up to ``wait_s`` seconds; their latency
counts, and one that never completes is failed.

The check drains the engine and compares every tenant's accumulated
`StepStats` (`ServeEngine.tenant_stats`, all of its requests, set-up
included) with the reference summed over the same requests: that is what
the engine serves a tenant with its defaults.  Currents leave the engine
only with ``keep_currents=True``, which copies every lane's currents of
every step to the host (gigabytes at this load) and so changes the host
path the cell measures.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from chip import compare, data, harness
from chip.drivers import common
from chip.generators import arrivals
from chip.reference import Reference, accumulate


def shrink(mix: dict) -> None:
    """The mix, in place, at a size a CPU test run can hold."""
    mix.update(tenants=4, pool=16, rate_per_s=200, request_ticks=8,
               flush_ticks=8, trace_seconds=0.5)


def plan(rng, rate, seconds, tenants, pool):
    """``(arrival times, tenant index, pool index)`` of a window's requests."""
    n = max(1, int(round(rate * seconds)))
    sched = arrivals.schedule(rng, n, rate)
    tenant = rng.permutation(np.arange(n) % tenants)
    return sched, tenant, rng.integers(0, pool, n)


def tenant_totals(config, conn, pool, picks, control=False):
    """(tenants, fields) reference stats of each tenant's requests.

    ``picks``: per tenant, the pool index of each request it was served, in
    order.  ``control`` accumulates tick by tick in bfloat16 instead.
    """
    ref = Reference(config, conn)
    used = sorted({p for ps in picks for p in ps})
    p_n, ticks = len(used), pool.shape[1]
    per_tick = ref.tick_stats(pool[used].reshape(p_n * ticks, -1))
    per_tick = per_tick.reshape(p_n, ticks, -1)
    where = {p: i for i, p in enumerate(used)}
    if not control:
        per_req = per_tick.sum(1)
        return np.stack([per_req[[where[p] for p in ps]].sum(0)
                         for ps in picks])
    longest = max(len(ps) for ps in picks)
    seq = np.zeros((len(picks), longest * ticks, per_tick.shape[-1]))
    for t, ps in enumerate(picks):
        seq[t, :len(ps) * ticks] = per_tick[[where[p] for p in ps]].reshape(
            len(ps) * ticks, -1)
    return accumulate(seq, bf16_control=True)


def warmup_picks(mix):
    """Pool index of each tenant's set-up requests, round by round."""
    t = mix["tenants"]
    return [[(r * t + j) % mix["pool"] for j in range(t)]
            for r in range(mix["warmup_rounds"])]


def control_readings(cell, seed, seconds):
    """The numbers the check compares, with the reference in bfloat16 in
    the program's place, over the request sequence a run of ``seed`` with
    a ``seconds`` window would serve."""
    import jax

    mix, config = cell.mix, cell.config
    _, conn = data.connectivity(data.seed_key(seed, 0), config)
    make = data.raster_fn(mix["generator"], mix["params"],
                          mix["request_ticks"], config["fabric"])
    pool = np.asarray(make(jax.random.split(data.seed_key(seed, 1),
                                            mix["pool"])))
    picks = [list(p) for p in zip(*warmup_picks(mix))]
    rng = np.random.default_rng(seed)
    _, tenant, pick = plan(rng, mix["rate_per_s"], seconds, mix["tenants"],
                           mix["pool"])
    for t, p in zip(tenant, pick):
        picks[t].append(int(p))
    ref = tenant_totals(config, conn, pool, picks)
    ctl = tenant_totals(config, conn, pool, picks, control=True)
    out = compare.stats_gaps(ctl, ref)
    out["unserved"] = 0.0
    return out


class Fleet:
    """The engine, its tenants and the request pool of one run."""

    def __init__(self, cell, seed):
        import jax
        import jax.numpy as jnp

        from repro.serve import ServeEngine, TenantSpec

        mix, config = cell.mix, cell.config
        self.mix, self.config, self.seed = mix, config, seed
        fab = config["fabric"]
        params, self.conn = data.connectivity(data.seed_key(seed, 0), config)
        cfg = common.program_config(config)
        make = data.raster_fn(mix["generator"], mix["params"],
                              mix["request_ticks"], fab)
        pool = make(jax.random.split(data.seed_key(seed, 1), mix["pool"]))
        self.pool_events = np.asarray(jax.jit(
            lambda p: jnp.sum(p, axis=(1, 2, 3), dtype=jnp.int32))(pool))
        self.pool = np.asarray(pool)
        self.engine = ServeEngine(flush_ticks=mix["flush_ticks"],
                                  flush_deadline_s=mix["flush_deadline_s"])
        self.names = [f"t{i:02d}" for i in range(mix["tenants"])]
        for i, name in enumerate(self.names):
            spec = TenantSpec(name, cfg)
            self.engine.register(
                spec, params=common.interface_params(params) if i == 0
                else None)
        self.impl = cfg.impl
        self.served_pool = {name: [] for name in self.names}
        self.cum = {name: 0 for name in self.names}
        self.rng = np.random.default_rng(seed)
        self.engine.start(poll_interval_s=mix["pump_poll_s"])
        for picks in warmup_picks(mix):
            for name, p in zip(self.names, picks):
                self._submit(name, p)
        self._wait_all(time.perf_counter() + mix["wait_s"])

    def _submit(self, name, p):
        self.engine.submit(name, self.pool[p])
        self.served_pool[name].append(p)
        self.cum[name] += self.mix["request_ticks"]
        return self.cum[name]

    def _wait_all(self, deadline):
        while time.perf_counter() < deadline:
            if all(self.engine.ticks_served(n) >= self.cum[n]
                   for n in self.names):
                return
            time.sleep(self.mix["poll_s"])
        raise TimeoutError("the engine did not serve the warm-up requests")

    def window(self, rate, seconds, trace_dir=None) -> dict:
        """One open-loop window at ``rate`` requests a second."""
        import jax

        mix = self.mix
        sched, tenant, pick = plan(self.rng, rate, seconds, len(self.names),
                                   mix["pool"])
        n = len(sched)
        lag = np.zeros(n)
        done = np.full(n, np.nan)
        pending = {name: collections.deque() for name in self.names}
        poll, engine = mix["poll_s"], self.engine
        i = 0
        steps = engine.registry.counter("serve.flushes")
        steps0 = steps.value
        with common.traced(trace_dir):
            start = time.perf_counter()
            while True:
                now = time.perf_counter() - start
                while i < n and sched[i] <= now:
                    name = self.names[tenant[i]]
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        lag[i] = time.perf_counter() - start - sched[i]
                        target = self._submit(name, int(pick[i]))
                    pending[name].append((i, target))
                    i += 1
                now = time.perf_counter() - start
                waiting = False
                for name, queue in pending.items():
                    if queue:
                        served = engine.ticks_served(name)
                        while queue and served >= queue[0][1]:
                            done[queue.popleft()[0]] = now
                        waiting = waiting or bool(queue)
                if i == n and not waiting:
                    break
                if now > seconds + mix["wait_s"]:
                    break
                nxt = sched[i] - now if i < n else poll
                time.sleep(min(max(nxt, 0.0), poll))
            in_window = time.perf_counter() - start
        latency = done - sched
        ok = np.isfinite(latency)
        completed = ok & (done <= seconds)
        return {"requests": n, "failed": int((~ok).sum()),
                "latency_s": latency[ok], "lag_s": lag,
                "served_events": int(self.pool_events[pick[completed]].sum()),
                "window_s": float(seconds), "wall_s": in_window,
                "sched_s": sched[ok], "steps": int(steps.value - steps0),
                "open_at_close": int(((~ok) | (done > seconds)).sum())}

    def check(self) -> dict:
        """Drain, stop the pump, and compare every tenant with the
        reference; returns the readings."""
        self.engine.stop(drain=True)
        acct = self.engine.accounting()
        expect = tenant_totals(self.config, self.conn, self.pool,
                               [self.served_pool[n] for n in self.names])
        program = [compare.stats_rows(self.engine.tenant_stats(n))
                   for n in self.names]
        readings = compare.stats_gaps(np.concatenate(program), expect)
        rows = acct["tenants"].values()
        readings["unserved"] = float(
            (not acct["closes"]) +
            sum(r["submitted"] - r["served"] for r in rows))
        return readings


def quarters(win, length):
    """p95 latency (ms) of the requests scheduled in each quarter."""
    lat, at = win["latency_s"] * 1e3, win["sched_s"]
    out = []
    for q in range(4):
        part = lat[(at >= q * length / 4) & (at < (q + 1) * length / 4)]
        out.append(f"{np.percentile(part, 95):.1f}" if len(part) else "-")
    return " ".join(out)


def run(cell, seed, seconds, t0, counter=None, trace_dir=None):
    mix = cell.mix
    fleet = Fleet(cell, seed)
    setup_s = harness.elapsed(t0)
    length = min(seconds, mix["trace_seconds"]) if trace_dir else seconds
    requests0 = counter.requests if counter else 0
    win = fleet.window(mix["rate_per_s"], length, trace_dir=trace_dir)
    compiles = (counter.requests - requests0) if counter else 0
    peak = harness.memory_peak(cell.chips)
    readings = fleet.check()

    lat_ms = win["latency_s"] * 1e3

    def pct(q):
        return float(np.percentile(lat_ms, q)) if len(lat_ms) else np.inf

    e2e = {"request_p95_ms": pct(95),
           "served_events_per_s": win["served_events"] / win["window_s"],
           "setup_s": setup_s}
    notes = [f"impl {fleet.impl}; {len(fleet.names)} tenants; "
             f"{win['requests']} requests at {mix['rate_per_s']}/s over "
             f"{win['window_s']} s; latency p50 {pct(50):.3f} p95 "
             f"{pct(95):.3f} p99 {pct(99):.3f} max {pct(100):.3f} ms; "
             f"{win['open_at_close']} open at the close; arrival lag p95 "
             f"{np.percentile(win['lag_s'], 95) * 1e3:.3f} ms; {compiles} "
             f"compiles in the window; polled every {mix['poll_s'] * 1e3} ms",
             f"p95 by quarter of the window {quarters(win, length)} ms; "
             f"arrival lag max {win['lag_s'].max() * 1e3:.3f} ms; "
             f"{win['steps']} engine steps over {win['wall_s']:.3f} s"]
    traced = {"arrival_lag_s": win["lag_s"]}
    return harness.Record(setup_s=setup_s, e2e=e2e,
                          attempted=win["requests"], failed=win["failed"],
                          readings=readings, memory_peak_bytes=peak,
                          notes=notes, traced=traced)
