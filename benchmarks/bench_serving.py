"""Continuous batching vs. static-batch serving on a Poisson arrival trace.

Both front-ends answer the same trace of Q SSSP queries against one road
grid (queries repeat popular origins with probability ``hot_frac``, the
skew any real serving mix has):

  * **static**: queries are grouped in arrival order into batches of B; each
    batch waits until its last member has arrived, then runs one
    ``run_phased_static_batch`` — every lane is held until the *slowest* row
    of its batch terminates (plus the batch-fill wait).
  * **continuous**: a ``ContinuousBatcher`` with B lanes admits queries as
    lanes free up; phase chunks end early on any lane finish, so a finished
    lane is refilled with zero idle trips (DESIGN.md Sec. 6).

The default rate saturates the server on this container (service rate is a
few hundred q/s on CPU-interpret kernels), which is the regime where the
*throughput* gap from tail-idling shows; at sub-saturation rates both
systems serve at the arrival rate and the win moves entirely into latency
(continuous p50 is ~10x lower because nothing waits for a batch to fill).

Time is a hybrid clock: it advances at wall rate while the engine computes
(service times are real, including per-chunk host syncs — the cost of
continuous batching is not hidden) and fast-forwards across idle gaps to the
next scheduled arrival, so the arrival process is reproducible and
machine-independent while throughput/latency stay honest.

Writes a ``BENCH_serving.json`` perf-trajectory artifact (schema
``bench_serving/v1``) with both systems' metrics and the qps speedup.

    PYTHONPATH=src python -m benchmarks.bench_serving [--n 1225]
        [--queries 48] [--lanes 8] [--k 32] [--rate 1024] [--hot-frac 0.3]
        [--seed 0] [--out BENCH_serving.json]
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import to_ell_in
from repro.core.static_engine import run_phased_static_batch
from repro.graphs import grid_road
from repro.obs.timer import now
from repro.serving import ContinuousBatcher, DistCache


class SimClock:
    """Wall-rate clock with fast-forward: sim_t = obs now() + offset."""

    def __init__(self):
        self._offset = -now()  # start at t = 0

    def __call__(self) -> float:
        return now() + self._offset

    def jump_to(self, t: float) -> None:
        """Fast-forward across an idle gap (never rewinds)."""
        self._offset = max(self._offset, t - now())


def poisson_trace(queries: int, rate_qps: float, n: int, seed: int,
                  hot_frac: float = 0.3, hot_set: int = 4):
    """(sources, arrival_times): exponential gaps; sources are uniform except
    a ``hot_frac`` share drawn from ``hot_set`` popular origins."""
    rng = np.random.default_rng(seed)
    hot = rng.integers(0, n, hot_set)
    sources = np.where(
        rng.random(queries) < hot_frac,
        hot[rng.integers(0, hot_set, queries)],
        rng.integers(0, n, queries),
    )
    arrivals = np.cumsum(rng.exponential(1.0 / rate_qps, queries))
    return sources, arrivals


def serve_static(g, ell, sources, arrivals, lanes: int):
    """The static-batch B-loop baseline on the same trace."""
    clk = SimClock()
    lat = []
    total_trips = 0
    n_batches = 0
    for lo in range(0, len(sources), lanes):
        batch_src = sources[lo:lo + lanes]
        batch_arr = arrivals[lo:lo + lanes]
        clk.jump_to(float(batch_arr[-1]))  # batch admits only when full
        res = run_phased_static_batch(g, batch_src, ell=ell)
        jax.block_until_ready(res.dist)
        t_done = clk()
        lat.extend(t_done - batch_arr)
        total_trips += int(res.total_phases)
        n_batches += 1
    span = clk() - float(arrivals[0])
    lat = np.asarray(lat)
    return {
        "throughput_qps": len(sources) / span if span > 0 else 0.0,
        "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_p99_s": float(np.percentile(lat, 99)),
        "latency_mean_s": float(lat.mean()),
        "engine_trips": total_trips,
        "batches": n_batches,
        "wall_span_s": span,
    }


def serve_continuous(g, ell, sources, arrivals, lanes: int, k: int,
                     cache: bool):
    """Replay the trace through a ContinuousBatcher on the hybrid clock.

    ``cache=False`` isolates the scheduling win (lane refill vs. batch
    tail-idling): every query runs through the engine, like the static
    baseline. ``cache=True`` measures the full subsystem, where duplicate
    hot sources also short-circuit through the dist cache / coalescing.
    """
    clk = SimClock()
    server = ContinuousBatcher(g, lanes=lanes, phases_per_step=k, ell=ell,
                               cache=DistCache(capacity=256) if cache else None,
                               clock=clk)
    i = 0
    while i < len(sources) or not server.idle:
        now = clk()
        while i < len(sources) and arrivals[i] <= now:
            server.submit(int(sources[i]), t_arrival=float(arrivals[i]))
            i += 1
        if server.idle:
            if i < len(sources):
                clk.jump_to(float(arrivals[i]))
            continue
        server.step()
    for req in server.completed:  # belt-and-braces: every answer materialised
        assert req.dist is not None
    return server.metrics.report()


def run(n: int = 1225, queries: int = 48, lanes: int = 8,
        k: int = 32, rate: float = 1024.0, hot_frac: float = 0.3, seed: int = 0,
        out_json: str | None = "BENCH_serving.json"):
    side = max(2, int(np.sqrt(n)))
    g = grid_road(side, side, seed=seed)
    ell = to_ell_in(g)
    sources, arrivals = poisson_trace(queries, rate, g.n, seed + 1,
                                      hot_frac=hot_frac)
    print(f"graph: road grid {side}x{side} (n={g.n}), "
          f"backend={jax.default_backend()}, trace: {queries} queries @ "
          f"Poisson {rate} q/s, hot_frac={hot_frac}, lanes={lanes}, k={k}")

    # Warm-up: compile every jitted shape both systems will hit (full batch,
    # the trailing partial batch, and the stepper/reset kernels).
    warm = ContinuousBatcher(g, lanes=lanes, phases_per_step=k, ell=ell)
    warm.submit(0)
    warm.drain()
    tail = len(sources) % lanes
    run_phased_static_batch(g, sources[:lanes], ell=ell)
    if tail:
        run_phased_static_batch(g, sources[:tail], ell=ell)

    stat = serve_static(g, ell, sources, arrivals, lanes)
    eng = serve_continuous(g, ell, sources, arrivals, lanes, k, cache=False)
    cont = serve_continuous(g, ell, sources, arrivals, lanes, k, cache=True)
    base = stat["throughput_qps"]
    speedup_engine = eng["throughput_qps"] / base if base else float("inf")
    speedup = cont["throughput_qps"] / base if base else float("inf")

    print(f"{'':>18} {'qps':>8} {'p50 lat':>9} {'p99 lat':>9} {'trips':>6}")
    for name, r in (("static", stat), ("continuous", eng),
                    ("continuous+cache", cont)):
        print(f"{name:>18} {r['throughput_qps']:>8.2f} "
              f"{r['latency_p50_s']*1e3:>8.0f}ms "
              f"{r['latency_p99_s']*1e3:>8.0f}ms {r['engine_trips']:>6}")
    print(f"continuous/static qps: {speedup_engine:.2f}x scheduling only, "
          f"{speedup:.2f}x with cache "
          f"(occupancy {eng['lane_occupancy']:.2f}, "
          f"{cont['cache_hits'] + cont['coalesced']} deduped)")

    report = {
        "schema": "bench_serving/v1",
        "config": {"n": g.n, "queries": queries, "lanes": lanes,
                   "phases_per_step": k, "rate_qps": rate,
                   "hot_frac": hot_frac, "seed": seed,
                   "backend": jax.default_backend()},
        "static": stat,
        "continuous_engine_only": eng,
        "continuous": cont,
        "speedup_qps_engine_only": speedup_engine,
        "speedup_qps": speedup,
    }
    if out_json:
        with open(out_json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {out_json}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1225)
    ap.add_argument("--queries", type=int, default=48)
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--rate", type=float, default=1024.0)
    ap.add_argument("--hot-frac", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serving.json")
    a = ap.parse_args()
    use_compile_cache()
    run(a.n, a.queries, a.lanes, a.k, a.rate, a.hot_frac, a.seed, a.out)
