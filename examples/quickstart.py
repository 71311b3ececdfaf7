"""Quickstart: phased SSSP with Crauser-style criteria in ~20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import dijkstra_numpy, run_delta_stepping, run_phased
from repro.graphs import uniform_gnp


def main():
    use_compile_cache()
    # a uniform random graph, expected out-degree 10, uniform [0,1] weights
    g = uniform_gnp(n=2000, p=10 / 2000, seed=0)

    ref = dijkstra_numpy(g, source=0)  # sequential oracle

    print(f"G(n={g.n}, m~{int(np.isfinite(np.asarray(g.w)).sum())})")
    print(f"{'criterion':24s} {'phases':>7s} {'sum|F|':>9s}  correct")
    for crit in ["dijk", "instatic", "outstatic", "instatic|outstatic",
                 "insimple|outsimple", "in|out"]:
        r = run_phased(g, 0, crit)
        ok = np.allclose(
            np.where(np.isfinite(ref), ref, 0),
            np.where(np.isfinite(np.asarray(r.dist)), np.asarray(r.dist), 0),
            rtol=1e-5,
        )
        print(f"{crit:24s} {int(r.phases):7d} {int(r.sum_fringe):9d}  {ok}")

    r = run_phased(g, 0, "oracle", dist_true=ref.astype(np.float32))
    print(f"{'oracle (lower bound)':24s} {int(r.phases):7d} {int(r.sum_fringe):9d}")
    d = run_delta_stepping(g, 0)
    print(f"{'delta-stepping':24s} {int(d.phases):7d} {'-':>9s}")


if __name__ == "__main__":
    main()
