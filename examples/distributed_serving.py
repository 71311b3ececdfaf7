"""Continuous-batching SSSP serving over a mesh-sharded graph.

The ROADMAP's "serve continuously across shards" milestone end to end: this
process block-shards one road graph's vertex state over a ("data", "model")
mesh of every device JAX finds (on a four-chip host, 2x2; under
``JAX_PLATFORMS=cpu`` the process forces 8 fake host devices, 4x2), and
serves asynchronous queries through the same
``ContinuousBatcher`` the single-device demo uses — only the engine backend
changes (``ShardedBackend``, DESIGN.md Sec. 7). Admission, coalescing, the
distance cache, and the metrics report are identical, and every completed
answer is validated bit-exactly against a standalone single-device
``run_phased_static`` solve.

    PYTHONPATH=src python examples/distributed_serving.py [--n 400]
        [--lanes 4] [--queries 16] [--phases-per-step 8]
        [--schedule reduce_scatter] [--seed 0]

CI runs this with tiny arguments as a smoke test of the sharded serving
path. (XLA_FLAGS is set before jax is imported — run in a fresh process.)
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count=8"
_XLA_FLAGS = os.environ.get("XLA_FLAGS", "")
if (os.environ.get("JAX_PLATFORMS") == "cpu"
        and "xla_force_host_platform_device_count" not in _XLA_FLAGS):
    os.environ["XLA_FLAGS"] = (_XLA_FLAGS + " " + _FLAG).strip()

import argparse

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.static_engine import run_phased_static
from repro.graphs import grid_road
from repro.launch.mesh import make_host_mesh
from repro.serving import ContinuousBatcher, DistCache, ShardedBackend


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400,
                    help="~vertex count (grid side is sqrt)")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--phases-per-step", type=int, default=8)
    ap.add_argument("--schedule", choices=("allreduce", "reduce_scatter"),
                    default="reduce_scatter")
    ap.add_argument("--hot-frac", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    mesh = make_host_mesh(tp=2)
    side = max(2, int(np.sqrt(args.n)))
    g = grid_road(side, side, seed=args.seed)
    backend = ShardedBackend(g, mesh, ("data", "model"), schedule=args.schedule)
    print(f"serving road grid {side}x{side} (n={g.n}, n_pad={backend.sg.n_pad}) "
          f"sharded over {jax.device_count()} {jax.default_backend()} devices, "
          f"lanes={args.lanes}, k={args.phases_per_step}, "
          f"schedule={args.schedule}")

    server = ContinuousBatcher(
        g, lanes=args.lanes, phases_per_step=args.phases_per_step,
        cache=DistCache(capacity=128), backend=backend,
    )

    rng = np.random.default_rng(args.seed + 1)
    hot = rng.integers(0, g.n, size=max(1, args.lanes // 2))
    sources = np.where(
        rng.random(args.queries) < args.hot_frac,
        hot[rng.integers(0, len(hot), args.queries)],
        rng.integers(0, g.n, args.queries),
    )

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
            if req.source not in solo_memo:
                solo_memo[req.source] = run_phased_static(g, req.source)
            solo = solo_memo[req.source]
            assert np.array_equal(req.dist, np.asarray(solo.dist)), (
                f"request {req.req_id} (source {req.source}) diverged from "
                f"single-device solve")
            tag = ("cache" if req.cache_hit else
                   "coalesced" if req.coalesced else
                   f"lane {req.lane}, {req.phases} phases")
            print(f"  req {req.req_id:>3} src={req.source:<6} done in "
                  f"{req.latency*1e3:7.1f} ms ({tag})")

    print(f"\nall {validated} sharded-served answers bit-exact vs "
          f"run_phased_static")
    print(server.metrics.to_json(indent=1))


if __name__ == "__main__":
    main()
