"""Paper Figures 7/8/10: phased-criteria engines vs Delta-stepping vs an
efficient sequential Dijkstra.

On this single-core container "parallel speedup" is reported two ways:
  * measured wall-time of the jitted dense engines vs heap Dijkstra
    (vectorisation speedup — the honest single-host number), and
  * the *depth model*: phases x per-phase critical path, the quantity the
    paper's speedup converges to with enough processors (phases are machine-
    independent, so these transfer to the paper's 80-thread setting).
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from benchmarks.common import timed
from repro.compile_cache import use_compile_cache
from repro.core import (
    default_delta,
    dijkstra_numpy,
    run_delta_stepping,
    run_phased,
)
from repro.graphs import kronecker, uniform_gnp


def bench_graph(name, g, out):
    t_seq, ref = timed(dijkstra_numpy, g, 0)

    def block(fn, *a, **k):
        # block_until_ready through a tuple-ish result
        r = fn(*a, **k)
        np.asarray(r.dist)
        return r

    rows = []
    for label, fn in [
        ("crauser-static", lambda: block(run_phased, g, 0, "instatic|outstatic")),
        # NOTE: the Pallas static engine is excluded from wall-time rows:
        # interpret=True executes the kernel body in Python per phase (its
        # correctness is covered by tests; its performance target is TPU).
        ("simple-dynamic", lambda: block(run_phased, g, 0, "insimple|outsimple")),
        ("full-in-out", lambda: block(run_phased, g, 0, "in|out")),
        ("delta-stepping", lambda: block(run_delta_stepping, g, 0)),
    ]:
        fn()  # compile
        t, r = timed(fn)
        d = np.asarray(r.dist)
        ok = np.allclose(np.where(np.isfinite(ref), ref, 0),
                         np.where(np.isfinite(d), d, 0), rtol=1e-4)
        rows.append({
            "graph": name, "algo": label, "time_s": t,
            "dijkstra_time_s": t_seq, "speedup_vs_dijkstra": t_seq / t,
            "phases": int(r.phases), "correct": bool(ok),
        })
        print(f"speedup,{name},{label},{t*1e3:.1f}ms,x{t_seq/t:.2f},"
              f"phases={int(r.phases)},ok={ok}")
    out.extend(rows)


def run(full: bool = False, out_json: str | None = None):
    if full:
        graphs = {
            "G(1e6,1e-4)": uniform_gnp(1_000_000, 1e-4, seed=0),
            "kron20": kronecker(20, seed=0),
        }
    else:
        graphs = {
            "G(20000,5e-4)": uniform_gnp(20_000, 5e-4, seed=0),
            "kron13": kronecker(13, seed=0),
        }
    rows: list = []
    for name, g in graphs.items():
        bench_graph(name, g, rows)
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
