"""Serving-style demo: answer batches of SSSP queries against one road graph.

Models the ROADMAP's query-serving workload: a long-lived process holds one
graph (ELL adjacency built once), queries arrive in batches of source ids,
and each batch is answered by a single call to ``run_phased_static_batch`` —
one jitted phase loop for the whole batch, one adjacency load per phase
shared across queries (DESIGN.md Sec. 3). Every answer is validated against
sequential Dijkstra.

    PYTHONPATH=src python examples/batch_serving.py [--n 5000] [--batch 16]
        [--requests 4]
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import dijkstra_numpy, to_ell_in
from repro.core.static_engine import run_phased_static_batch
from repro.graphs import grid_road
from repro.obs.timer import now


class SSSPServer:
    """Holds one graph; answers (B,) source batches with distance matrices."""

    def __init__(self, g):
        self.g = g
        self.ell = to_ell_in(g)  # built once, reused by every batch

    def answer(self, sources):
        res = run_phased_static_batch(self.g, sources, ell=self.ell)
        return np.asarray(res.dist), res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=5000)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--requests", type=int, default=4)
    args = ap.parse_args()
    use_compile_cache()

    side = int(np.sqrt(args.n))
    g = grid_road(side, side, seed=0)
    print(f"serving road grid {side}x{side}: n={g.n}, "
          f"m={int(np.isfinite(np.asarray(g.w)).sum())}")
    server = SSSPServer(g)
    rng = np.random.default_rng(1)

    # warm-up request compiles the phase loop for this (graph, B) shape
    server.answer(rng.integers(0, g.n, args.batch))

    total_q, total_t = 0, 0.0
    for r in range(args.requests):
        sources = rng.integers(0, g.n, args.batch)
        t0 = now()
        dist, res = server.answer(sources)
        dt = now() - t0
        total_q += len(sources)
        total_t += dt
        # validate a spot-check row per request against sequential Dijkstra
        i = int(rng.integers(len(sources)))
        ref = dijkstra_numpy(g, int(sources[i]))
        fin = np.isfinite(ref)
        ok = (np.isfinite(dist[i]) == fin).all() and np.allclose(
            dist[i][fin], ref[fin], rtol=1e-5)
        print(f"request {r}: B={len(sources)} answered in {dt*1e3:7.1f} ms "
              f"({len(sources)/dt:8.1f} q/s), phases={int(res.total_phases)}, "
              f"spot-check row {i} vs Dijkstra: {'OK' if ok else 'MISMATCH'}")
        assert ok
    print(f"\nserved {total_q} queries in {total_t*1e3:.0f} ms "
          f"-> {total_q/total_t:.1f} queries/sec sustained")


if __name__ == "__main__":
    main()
