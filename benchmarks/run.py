"""Benchmark aggregator: one section per paper table/figure.

Prints ``name,...`` CSV lines per benchmark. The dry-run/roofline section is
included when results/dryrun exists (produced by ``python -m
repro.launch.dryrun --all --mesh both --out results/dryrun``).

Scales default to single-core-CPU-friendly sizes; pass --full for
paper-scale sweeps on real hardware.
"""
from __future__ import annotations

import argparse
import os
import sys

from repro.compile_cache import use_compile_cache
from repro.obs.timer import Stopwatch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seeds", type=int, default=3)
    args = ap.parse_args()
    use_compile_cache()

    os.makedirs("results", exist_ok=True)
    sw = Stopwatch().__enter__()

    from benchmarks import bench_fringe, bench_phases, bench_snap, bench_speedup

    print("# === Table 1 / Fig 3: phases per criterion (b*n^c fits) ===")
    bench_phases.run(args.full, args.seeds, "results/bench_phases.json")
    print("# === Table 2 / Fig 4: sum |F| over phases ===")
    bench_fringe.run(args.full, args.seeds, "results/bench_fringe.json")
    print("# === Table 3 / Fig 5-6: SNAP stand-ins ===")
    bench_snap.run(args.full, "results/bench_snap.json")
    print("# === Fig 7/8/10: engines vs Delta-stepping vs Dijkstra ===")
    bench_speedup.run(args.full, "results/bench_speedup.json")

    if os.path.isdir("results/dryrun"):
        print("# === Roofline (from multi-pod dry-run records) ===")
        sys.argv = ["roofline", "--dir", "results/dryrun",
                    "--out", "results/roofline.json"]
        from benchmarks import roofline
        roofline.main()
    else:
        print("# (no results/dryrun directory — run repro.launch.dryrun for "
              "the roofline section)")
    print(f"# total benchmark wall time: {sw.elapsed:.1f}s")


if __name__ == "__main__":
    main()
