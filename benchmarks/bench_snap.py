"""Paper Table 3 / Figures 5-6: phase counts on web-graph and road-network
inputs, per criterion, plus the settled-per-phase profile shape.

The SNAP graphs themselves are not redistributable offline; structurally
matched stand-ins are generated instead (heavy-tail-in-degree webgraphs for
BerkStan/NotreDame; bidirected near-planar grids for TX/PA). Sizes default to
CPU-friendly; --full approaches paper scale.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from benchmarks.common import CRITERIA
from repro.compile_cache import use_compile_cache
from repro.core import dijkstra_numpy, run_phased
from repro.graphs import grid_road, webgraph


def run(full: bool = False, out_json: str | None = None):
    if full:
        inputs = {
            "web-berkstan-standin": webgraph(685_000, 11, seed=1),
            "web-notredame-standin": webgraph(325_000, 5, seed=2),
            "road-tx-standin": grid_road(1140, 1140, seed=3),
            "road-pa-standin": grid_road(1000, 1000, seed=4),
        }
    else:
        inputs = {
            "web-berkstan-standin": webgraph(20_000, 11, seed=1),
            "web-notredame-standin": webgraph(10_000, 5, seed=2),
            "road-tx-standin": grid_road(90, 90, seed=3),
            "road-pa-standin": grid_road(80, 80, seed=4),
        }
    rows = []
    for name, g in inputs.items():
        ref = dijkstra_numpy(g, 0).astype(np.float32)
        for crit in CRITERIA:
            res = run_phased(g, 0, crit,
                             dist_true=ref if crit == "oracle" else None,
                             trace_len=1)
            rows.append({"graph": name, "n": g.n, "criterion": crit,
                         "phases": int(res.phases),
                         "sum_fringe": int(res.sum_fringe)})
            print(f"snap,{name},{crit},{int(res.phases)},{int(res.sum_fringe)}")
    if out_json:
        with open(out_json, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    use_compile_cache()
    run(a.full, a.out)
