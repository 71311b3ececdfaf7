"""Paper Table 2 / Figure 4: sum of |F| over all phases (work measure)."""
from __future__ import annotations

import argparse
import json

from benchmarks.common import CRITERIA, bucket_edges, fit_power, mean_phases
from repro.compile_cache import use_compile_cache
from repro.graphs import kronecker, uniform_gnp


def run(full: bool = False, n_seeds: int = 5, out_json: str | None = None,
        reuse: str = "results/bench_phases.json"):
    import os
    if reuse and os.path.exists(reuse):
        # reuse the phase-sweep runs (mean_phases returns both quantities)
        with open(reuse) as f:
            prows = json.load(f)
        rows = []
        for r in prows:
            if "sum_fringe" not in r:
                continue
            b, c = fit_power(r["ns"], r["sum_fringe"])
            rows.append({"family": r["family"], "criterion": r["criterion"],
                         "ns": r["ns"], "sum_fringe": r["sum_fringe"],
                         "fit": f"{b:.2f}*n^{c:.2f}"})
            print(f"fringe,{r['family']},{r['criterion']},{b:.2f}*n^{c:.2f},"
                  f"{r['sum_fringe'][-1]:.0f}")
        if out_json:
            with open(out_json, "w") as f:
                json.dump(rows, f, indent=1)
        return rows
    return _run_fresh(full, n_seeds, out_json)


def _run_fresh(full: bool = False, n_seeds: int = 5, out_json: str | None = None):
    if full:
        uniform_ns = [int(100 * 1.21 ** i) for i in range(25)]
        kron_ks = list(range(7, 17))
        n_seeds = 100
    else:
        uniform_ns = [100, 178, 316, 562, 1000, 1778, 3162]
        kron_ks = list(range(7, 12))
    seeds = list(range(n_seeds))
    rows = []
    for family, grid in (("uniform", uniform_ns), ("kronecker", kron_ks)):
        for crit in CRITERIA:
            ys, ns = [], []
            for g in grid:
                if family == "uniform":
                    mk = lambda s, n=g: uniform_gnp(
                        n, 10.0 / n, seed=s, pad_to=bucket_edges(10 * n))
                    n = g
                else:
                    mk = lambda s, k=g: kronecker(
                        k, seed=s, pad_to=bucket_edges(int(2.5 ** k)))
                    n = 2 ** g
                _, sf = mean_phases(mk, crit, seeds)
                ys.append(sf)
                ns.append(n)
            b, c = fit_power(ns, ys)
            rows.append({"family": family, "criterion": crit, "ns": ns,
                         "sum_fringe": ys, "fit": f"{b:.2f}*n^{c:.2f}"})
            print(f"fringe,{family},{crit},{b:.2f}*n^{c:.2f},{ys[-1]:.0f}")
    if out_json:
        with open(out_json, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seeds", type=int, default=5)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    use_compile_cache()
    run(a.full, a.seeds, a.out)
