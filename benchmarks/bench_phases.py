"""Paper Table 1 / Figure 3: number of phases per criterion, with b*n^c fits.

Uniform graphs G(n, p) with expected out-degree 10 and Kronecker graphs with
the Graph500 initiator, exactly the two families of the paper's Sec. 4.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from benchmarks.common import CRITERIA, bucket_edges, fit_log, fit_power, mean_phases
from repro.compile_cache import use_compile_cache
from repro.graphs import kronecker, uniform_gnp


def run(full: bool = False, n_seeds: int = 5, out_json: str | None = None):
    if full:
        uniform_ns = [int(100 * 1.21 ** i) for i in range(25)]  # to ~65k
        kron_ks = list(range(7, 17))
        n_seeds = 100
    else:
        uniform_ns = [100, 178, 316, 562, 1000, 1778, 3162]
        kron_ks = list(range(7, 12))
    seeds = list(range(n_seeds))
    rows = []
    for crit in CRITERIA:
        ys, sfs = [], []
        for n in uniform_ns:
            ph, sf = mean_phases(lambda s, n=n: uniform_gnp(
                n, 10.0 / n, seed=s, pad_to=bucket_edges(10 * n)),
                                crit, seeds)
            ys.append(ph)
            sfs.append(sf)
        if crit == "oracle":
            b = fit_log(uniform_ns, ys)
            fit = f"{b:.2f}*log2(n)"
        else:
            b, c = fit_power(uniform_ns, ys)
            fit = f"{b:.2f}*n^{c:.2f}"
        rows.append({"family": "uniform", "criterion": crit,
                     "ns": uniform_ns, "phases": ys, "fit": fit,
                     "sum_fringe": sfs})
        print(f"phases,uniform,{crit},{fit},{ys[-1]:.1f}")
    for crit in CRITERIA:
        ys, ns, sfs = [], [], []
        for k in kron_ks:
            ph, sf = mean_phases(lambda s, k=k: kronecker(
                k, seed=s, pad_to=bucket_edges(int(2.5 ** k))), crit, seeds)
            ys.append(ph)
            ns.append(2 ** k)
            sfs.append(sf)
        if crit == "oracle":
            b = fit_log(ns, ys)
            fit = f"{b:.2f}*log2(n)"
        else:
            b, c = fit_power(ns, ys)
            fit = f"{b:.2f}*n^{c:.2f}"
        rows.append({"family": "kronecker", "criterion": crit,
                     "ns": ns, "phases": ys, "fit": fit,
                     "sum_fringe": sfs})
        print(f"phases,kronecker,{crit},{fit},{ys[-1]:.1f}")
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
