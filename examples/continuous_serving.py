"""Continuous-batching SSSP server demo: asynchronous arrivals, B lanes.

The ROADMAP's serving workload end to end: a long-lived process holds one
road graph, queries trickle in (Poisson arrivals, including repeated popular
sources), and a ``ContinuousBatcher`` keeps its lanes saturated by refilling
each finished lane from the queue instead of waiting for the slowest row of
a static batch. Duplicate sources short-circuit through the LRU distance
cache. Every completed answer is validated bit-exactly against a standalone
``run_phased_static`` solve, and the run ends by printing the JSON metrics
report (throughput, latency percentiles, lane occupancy, phases/query).

    PYTHONPATH=src python examples/continuous_serving.py [--n 2500]
        [--lanes 8] [--queries 48] [--phases-per-step 8] [--seed 0]
        [--trace serving_trace.json] [--report serving_report.json]

``--trace PATH`` turns on the observability layer: the run additionally
writes a Chrome trace-event file (open in Perfetto — one timeline row per
lane, queue-depth counter track) and prints the metrics-registry dashboard.
``python -m repro.obs validate PATH`` checks the exported file; CI does
exactly that as the obs smoke test.

``--chaos SEED`` swaps the server for a ``ResilientBatcher`` behind a
seeded random fault plan (``FaultyBackend`` + ``FaultyDistCache``): rows
get corrupted at harvest, engine steps fail, the device stalls, cached
rows rot in memory — and every completed answer is still validated
bit-exactly. The run ends by printing which faults fired and what the
recovery machinery did about them (quarantines, retries, rebuilds).

CI runs this with tiny arguments as a smoke test of the serving subsystem,
and once more with ``--chaos`` as the resilience smoke.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.static_engine import run_phased_static
from repro.graphs import grid_road
from repro.obs import Observability
from repro.serving import (
    ContinuousBatcher,
    DistCache,
    FaultPlan,
    FaultyBackend,
    FaultyDistCache,
    ResilientBatcher,
    StaticBackend,
    VirtualClock,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2500,
                    help="~vertex count (grid side is sqrt)")
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--queries", type=int, default=48)
    ap.add_argument("--phases-per-step", type=int, default=32)
    ap.add_argument("--hot-frac", type=float, default=0.25,
                    help="fraction of queries drawn from a small popular set")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="capture a Chrome trace-event file here (also "
                         "enables the metrics registry + dashboard)")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="write the registry snapshot JSON here "
                         "(with --trace)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="serve through a ResilientBatcher under a seeded "
                         "random fault plan (faults fire, answers stay "
                         "bit-exact)")
    args = ap.parse_args()
    use_compile_cache()

    side = max(2, int(np.sqrt(args.n)))
    g = grid_road(side, side, seed=args.seed)
    print(f"serving road grid {side}x{side}: n={g.n}, "
          f"m={int(np.isfinite(np.asarray(g.w)).sum())}, "
          f"lanes={args.lanes}, k={args.phases_per_step}")

    obs = Observability.enabled() if args.trace else None
    if args.chaos is not None:
        plan = FaultPlan.random(args.chaos, n_faults=5,
                                horizon=4 * args.queries, lanes=args.lanes)
        clock = VirtualClock()
        print(f"chaos plan (seed {args.chaos}):")
        for f in plan.faults:
            print(f"  {f.kind:<12} at step {f.at}"
                  + (f" lane {f.lane}" if f.lane is not None else "")
                  + f" magnitude {f.magnitude:.2f}")
        server = ResilientBatcher(
            g, lanes=args.lanes, phases_per_step=args.phases_per_step,
            cache=FaultyDistCache(DistCache(capacity=256), plan),
            backend=FaultyBackend(StaticBackend(g), plan, clock=clock),
            clock=clock.now, obs=obs,
        )
    else:
        server = ContinuousBatcher(
            g, lanes=args.lanes, phases_per_step=args.phases_per_step,
            cache=DistCache(capacity=256), obs=obs,
        )

    # Arrival trace: mostly-unique sources plus a hot set that exercises the
    # cache (popular origins recur in any real serving mix).
    rng = np.random.default_rng(args.seed + 1)
    hot = rng.integers(0, g.n, size=max(1, args.lanes // 2))
    sources = np.where(
        rng.random(args.queries) < args.hot_frac,
        hot[rng.integers(0, len(hot), args.queries)],
        rng.integers(0, g.n, args.queries),
    )

    # Feed arrivals a few at a time between scheduling rounds — the batcher
    # admits into whatever lanes have freed up, never blocking on a batch.
    arrived = 0
    validated = 0
    solo_memo = {}
    burst = max(1, args.queries // 8)
    while arrived < len(sources) or not server.idle:
        for s in sources[arrived:arrived + burst]:
            server.submit(int(s))
        arrived = min(arrived + burst, len(sources))
        for req in server.step():
            validated += 1
            # memoised per source: hot sources recur by design, and the
            # point of the demo is that the *server* dedups them — the
            # validator shouldn't pay a fresh solve per duplicate either
            if req.source not in solo_memo:
                solo_memo[req.source] = run_phased_static(g, req.source)
            solo = solo_memo[req.source]
            assert np.array_equal(req.dist, np.asarray(solo.dist)), (
                f"request {req.req_id} (source {req.source}) diverged from solo solve")
            tag = ("cache" if req.cache_hit else
                   "coalesced" if req.coalesced else
                   f"lane {req.lane}, {req.phases} phases")
            print(f"  req {req.req_id:>3} src={req.source:<6} done in "
                  f"{req.latency*1e3:7.1f} ms ({tag})")

    print(f"\nall {validated} answers bit-exact vs run_phased_static")
    if args.chaos is not None:
        fired = server.backend.fired
        poisoned = server.cache.poisoned
        m = server.metrics
        print(f"chaos: {len(fired)} backend fault(s) fired "
              f"({', '.join(f.kind for f in fired) or 'none'}), "
              f"{len(poisoned)} cache row(s) poisoned")
        print(f"recovery: {m.quarantines} quarantine(s), {m.retries} "
              f"retr{'y' if m.retries == 1 else 'ies'}, "
              f"{m.engine_failures} engine rebuild(s), "
              f"{server.cache.corrupt_dropped} rotten cache row(s) dropped")
        assert validated == args.queries, (
            f"chaos run completed {validated}/{args.queries}")
    print(server.metrics.to_json(indent=1))

    if obs is not None:
        from repro.obs.__main__ import render_dashboard
        from repro.obs.tracer import validate_events

        errors = validate_events(obs.tracer.events())
        assert not errors, "\n".join(errors)
        obs.tracer.export(args.trace)
        print(f"\ntrace: {len(obs.tracer.events())} events -> {args.trace} "
              f"(open in https://ui.perfetto.dev)")
        if args.report:
            with open(args.report, "w") as f:
                f.write(obs.registry.to_json())
            print(f"report: {args.report}")
        print()
        render_dashboard(obs.registry.snapshot())


if __name__ == "__main__":
    main()
