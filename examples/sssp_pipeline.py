"""End-to-end driver for the paper's workload: generate graph families, run
every engine (generic criteria engine, kernel-backed static engine,
Delta-stepping, sequential Dijkstra), validate distances, and report
phases/work/time — the full Sec. 4 + Sec. 6 pipeline in one run.

    PYTHONPATH=src python examples/sssp_pipeline.py [--n 50000] [--deg 10]
"""
import argparse

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import (
    dijkstra_numpy,
    run_delta_stepping,
    run_phased,
    to_ell_in,
)
from repro.core.static_engine import run_phased_static
from repro.graphs import grid_road, kronecker, uniform_gnp, webgraph
from repro.obs.timer import now


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--deg", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()
    n = args.n

    graphs = {
        f"uniform G({n},{args.deg}/n)": uniform_gnp(n, args.deg / n, seed=0),
        f"kronecker 2^{int(np.log2(n))}": kronecker(int(np.log2(n)), seed=0),
        "road grid": grid_road(int(np.sqrt(n)), int(np.sqrt(n)), seed=0),
        "web graph": webgraph(n, 8, seed=0),
    }
    for name, g in graphs.items():
        m = int(np.isfinite(np.asarray(g.w)).sum())
        t0 = now()
        ref = dijkstra_numpy(g, 0)
        t_seq = now() - t0
        print(f"\n== {name}: n={g.n} m={m} (sequential Dijkstra {t_seq*1e3:.0f} ms)")
        ell = to_ell_in(g)

        def check(dist):
            d = np.asarray(dist)
            fin = np.isfinite(ref)
            return (np.isfinite(d) == fin).all() and np.allclose(
                d[fin], ref[fin], rtol=1e-4)

        for label, fn in [
            ("phased INSTATIC|OUTSTATIC",
             lambda: run_phased(g, 0, "instatic|outstatic")),
            ("phased static (pallas kernels)",
             lambda: run_phased_static(g, 0, ell=ell)),
            ("phased IN|OUT (strong)", lambda: run_phased(g, 0, "in|out")),
            ("delta-stepping", lambda: run_delta_stepping(g, 0)),
        ]:
            fn()  # compile
            t0 = now()
            r = fn()
            np.asarray(r.dist)
            t = now() - t0
            print(f"  {label:34s} phases={int(r.phases):6d} "
                  f"time={t*1e3:7.1f} ms  speedup-vs-seq=x{t_seq/t:5.2f} "
                  f"correct={check(r.dist)}")


if __name__ == "__main__":
    main()
