"""Batch-serving benchmark: queries/sec of the batched static engine vs. a
loop of single-source runs, swept over batch size B.

The batched engine shares one ELL adjacency load per phase across the whole
batch (DESIGN.md Sec. 3), so throughput should grow nearly linearly in B
until the gather saturates; the single-source loop pays the full adjacency
traffic B times and its loop trips sum over queries instead of maxing.

    PYTHONPATH=src python -m benchmarks.bench_batch [--n 2000] [--deg 10]
        [--batches 1 2 4 8 16 32] [--out bench_batch.json]
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import timed
from repro.compile_cache import use_compile_cache
from repro.core import to_ell_in
from repro.core.static_engine import run_phased_static, run_phased_static_batch
from repro.graphs import uniform_gnp


def _block(res):
    jax.block_until_ready(res.dist)
    return res


def run(n: int = 2000, deg: int = 10, batches=(1, 2, 4, 8, 16, 32),
        seed: int = 0, out_json: str | None = None):
    g = uniform_gnp(n, deg / n, seed=seed)
    ell = to_ell_in(g)
    rng = np.random.default_rng(seed)
    rows = []
    print(f"graph: uniform G({n}, {deg}/n), backend={jax.default_backend()}")
    print(f"{'B':>4} {'batched ms':>11} {'loop ms':>10} {'batched q/s':>12} "
          f"{'loop q/s':>10} {'speedup':>8} {'phases':>7}")
    for b in batches:
        srcs = rng.integers(0, n, b)

        def batched():
            return _block(run_phased_static_batch(g, srcs, ell=ell))

        def looped():
            last = None
            for s in srcs:
                last = _block(run_phased_static(g, int(s), ell=ell))
            return last

        batched()  # compile
        looped()
        t_batch, res = timed(batched)
        t_loop, _ = timed(looped)
        qps_b, qps_l = b / t_batch, b / t_loop
        rows.append({
            "B": int(b), "t_batched_s": t_batch, "t_loop_s": t_loop,
            "qps_batched": qps_b, "qps_loop": qps_l,
            "total_phases": int(res.total_phases),
        })
        print(f"{b:>4} {t_batch*1e3:>11.1f} {t_loop*1e3:>10.1f} "
              f"{qps_b:>12.1f} {qps_l:>10.1f} {t_loop/t_batch:>7.2f}x "
              f"{int(res.total_phases):>7}")
    if out_json:
        with open(out_json, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--deg", type=int, default=10)
    ap.add_argument("--batches", type=int, nargs="+",
                    default=[1, 2, 4, 8, 16, 32])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    use_compile_cache()
    run(a.n, a.deg, tuple(a.batches), a.seed, a.out)
