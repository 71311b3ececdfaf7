"""Batched-distributed throughput: qps vs lane count B vs exchange schedule.

Answers the tentpole question of DESIGN.md Sec. 7: how much of the
per-phase synchronisation cost of the sharded engine does lane-batching
amortise? For each B, the same Q-query workload runs against a mesh over
every device JAX finds (the forced-8-device mesh under
``JAX_PLATFORMS=cpu``) two ways:

  * **B=1 loop** — one ``step_sharded_batch`` drain per query (the
    pre-refactor serving pattern: every query pays every phase's collective
    round and dispatch alone);
  * **batched** — queries grouped into B lanes per drain; each phase's
    collectives carry ``(B,)``/``(B, n_loc)`` messages, so the fixed
    per-phase cost (dispatch, 8-way synchronisation, collective latency) is
    split across B queries and the trip count per drain is the max over
    lanes rather than the sum.

Both exchange schedules are measured. Writes a ``BENCH_distributed.json``
perf-trajectory artifact (schema ``bench_distributed/v1``).

    PYTHONPATH=src python -m benchmarks.bench_distributed_batch
        [--n 1024] [--queries 16] [--lanes 1 4 8] [--seed 0]
        [--out BENCH_distributed.json]

Under ``JAX_PLATFORMS=cpu`` the 8 fake host devices are created by this
script itself (XLA_FLAGS is set before jax is imported), so run it in a
fresh process.
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count=8"
_XLA_FLAGS = os.environ.get("XLA_FLAGS", "")
if (os.environ.get("JAX_PLATFORMS") == "cpu"
        and "xla_force_host_platform_device_count" not in _XLA_FLAGS):
    os.environ["XLA_FLAGS"] = (_XLA_FLAGS + " " + _FLAG).strip()

import argparse
import json

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.distributed import (
    harvest_sharded,
    init_sharded_batch_state,
    shard_graph_batch,
    sharded_lanes_active,
    step_sharded_batch,
)
from repro.graphs import grid_road
from repro.launch.mesh import make_host_mesh
from repro.obs.timer import now

SCHEDULES = ("allreduce", "reduce_scatter")


def _drain(sg, state, mesh, axes, schedule, cap):
    state = step_sharded_batch(sg, state, mesh, axes, cap, schedule=schedule)
    jax.block_until_ready(state.dist)
    return state


def run_batched(sg, mesh, axes, schedule, sources, b, cap):
    """Serve `sources` in groups of `b` lanes; returns (wall_s, trips)."""
    trips = 0
    t0 = now()
    for lo in range(0, len(sources), b):
        batch = np.full(b, -1, np.int32)  # ragged tail rides as empty lanes
        batch[: len(sources[lo:lo + b])] = sources[lo:lo + b]
        state = init_sharded_batch_state(sg, batch)
        state = _drain(sg, state, mesh, axes, schedule, cap)
        assert not sharded_lanes_active(state).any()
        trips += int(state.trips)
    return now() - t0, trips


def run(n: int = 1024, queries: int = 16, lanes=(1, 4, 8), seed: int = 0,
        out_json: str | None = "BENCH_distributed.json"):
    mesh = make_host_mesh(tp=2)
    axes = ("data", "model")
    side = max(2, int(np.sqrt(n)))
    g = grid_road(side, side, seed=seed)
    sg = shard_graph_batch(g, mesh.size)
    rng = np.random.default_rng(seed + 1)
    sources = rng.integers(0, g.n, queries).astype(np.int32)
    cap = g.n + 1
    print(f"graph: road grid {side}x{side} (n={g.n}, n_pad={sg.n_pad}), "
          f"mesh {tuple(mesh.devices.shape)} on {jax.device_count()} "
          f"{jax.default_backend()} devices, {queries} queries, "
          f"B in {list(lanes)}")

    lanes = sorted(set(lanes))  # baseline is the smallest B; run it first
    results = []
    print(f"{'schedule':>16} {'B':>3} {'qps':>8} {'trips':>6} {'speedup':>8}")
    for schedule in SCHEDULES:
        base_qps = None
        for b in lanes:
            # warm the (B,)-shaped compile outside the timed region
            warm = init_sharded_batch_state(sg, np.full(b, -1, np.int32))
            warm = step_sharded_batch(sg, warm, mesh, axes, 1, schedule=schedule)
            jax.block_until_ready(warm.dist)
            wall, trips = run_batched(sg, mesh, axes, schedule, sources, b, cap)
            qps = queries / wall
            if base_qps is None:
                base_qps = qps
            speedup = qps / base_qps
            results.append({
                "schedule": schedule, "lanes": b, "throughput_qps": qps,
                "wall_s": wall, "engine_trips": trips,
                "speedup_vs_min_b": speedup,
            })
            print(f"{schedule:>16} {b:>3} {qps:>8.2f} {trips:>6} {speedup:>7.2f}x")

    # correctness spot-check rides along: batched rows == B=1 rows, bit-exact
    b = max(lanes)
    res_b = harvest_sharded(_drain(
        sg, init_sharded_batch_state(sg, sources[:b]), mesh, axes,
        SCHEDULES[-1], cap))
    for i in range(min(2, b, len(sources))):
        res_1 = harvest_sharded(_drain(
            sg, init_sharded_batch_state(sg, sources[i:i + 1]), mesh, axes,
            SCHEDULES[-1], cap))
        np.testing.assert_array_equal(
            np.asarray(res_b.dist[i]), np.asarray(res_1.dist[0]))
    print("spot-check: batched rows bit-exact vs B=1 rows OK")

    report = {
        "schema": "bench_distributed/v1",
        "config": {"n": g.n, "n_pad": sg.n_pad, "queries": queries,
                   "lanes_swept": list(lanes),
                   "mesh": list(mesh.devices.shape), "seed": seed,
                   "backend": jax.default_backend(),
                   "devices": jax.device_count()},
        "results": results,
    }
    if out_json:
        with open(out_json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {out_json}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--lanes", type=int, nargs="+", default=[1, 4, 8])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_distributed.json")
    a = ap.parse_args()
    use_compile_cache()
    run(a.n, a.queries, tuple(a.lanes), a.seed, a.out)
