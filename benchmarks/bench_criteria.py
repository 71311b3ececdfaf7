"""Criterion-family benchmark on the PRODUCTION engine (paper Sec. 5/6).

Sweeps the strengthened criteria through ``run_phased_static`` — the same
compiled stepper the batch/serving stack runs, not the dense reference loop
— and records, per criterion family x graph family:

  * ``phases``       — parallel depth (the paper's headline metric: a small
                       root of n for the strengthened criteria);
  * ``relax_edges``  — settled out-edge relax work (label-setting: <= m);
  * ``sum_fringe``   — Σ|F| over phases (the paper's Table 2 work measure);
  * ``wall_s``       — median wall-clock of a full solve on this host.

Reference rows per graph family:

  * ``oracle``  — the clairvoyant criterion through the same engine: the
                  *depth lower bound* no implementable criterion can beat;
  * ``delta``   — Delta-stepping (Meyer & Sanders), the baseline the paper
                  compares against (label-correcting, so its relax work may
                  exceed m while its phase count can undercut weak criteria).

Graph families follow the paper: ``gnm`` (uniform G(n,p)), ``rmat``
(Graph500 Kronecker), ``grid`` (road-network stand-in). Writes
``BENCH_criteria.json``; the acceptance gate is strictly fewer phases for
``in|out`` than ``instatic|outstatic`` on gnm and rmat with ``oracle`` <=
both (the work-vs-depth tradeoff the criterion plans exist to buy).

    PYTHONPATH=src python -m benchmarks.bench_criteria [--tiny]
        [--sources 3] [--out BENCH_criteria.json]
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import timed
from repro.compile_cache import use_compile_cache
from repro.core import dijkstra_numpy, run_delta_stepping
from repro.core.graph import to_ell_in, to_ell_out
from repro.core.static_engine import run_phased_static
from repro.graphs import grid_road, kronecker, uniform_gnp

# engine-implementable criterion families, weakest implemented pair first
CRITERIA = ["instatic|outstatic", "insimple|outsimple", "in|out"]


def _families(tiny: bool):
    if tiny:
        return {
            "gnm": lambda: uniform_gnp(256, 10 / 256, seed=7),
            "rmat": lambda: kronecker(8, seed=7),
            "grid": lambda: grid_road(16, 16, seed=7),
        }
    return {
        "gnm": lambda: uniform_gnp(2048, 10 / 2048, seed=7),
        "rmat": lambda: kronecker(11, seed=7),
        "grid": lambda: grid_road(45, 45, seed=7),
    }


def _solve(g, ell, ell_out, crit, src, dist_true=None):
    res = run_phased_static(g, src, ell=ell, criterion=crit,
                            dist_true=dist_true, ell_out=ell_out,
                            trace_len=1)
    jax.block_until_ready(res.dist)
    return res


def run(tiny: bool = False, n_sources: int = 3, seed: int = 0,
        out_json: str | None = "BENCH_criteria.json"):
    rng = np.random.default_rng(seed)
    rows = []
    print(f"backend={jax.default_backend()} tiny={tiny}")
    print(f"{'family':>6} {'criterion':>20} {'phases':>7} {'relax':>9} "
          f"{'sum|F|':>9} {'wall ms':>9}")
    for fam, make in _families(tiny).items():
        g = make()
        ell = to_ell_in(g)
        ell_out = to_ell_out(g)
        m_real = int(np.isfinite(np.asarray(g.w)).sum())
        srcs = [int(s) for s in rng.integers(0, g.n, n_sources)]
        truths = {s: dijkstra_numpy(g, s).astype(np.float32) for s in srcs}

        def record(crit, solve):
            phases, redges, sumf, walls = [], [], [], []
            solve(srcs[0])  # compile
            for s in srcs:
                t, res = timed(solve, s)
                phases.append(int(res.phases))
                redges.append(int(res.relax_edges))
                sumf.append(int(getattr(res, "sum_fringe", 0)))
                walls.append(t)
            row = {
                "family": fam, "n": int(g.n), "m": int(m_real),
                "criterion": crit,
                "phases_mean": float(np.mean(phases)),
                "phases": phases,
                "relax_edges_mean": float(np.mean(redges)),
                "sum_fringe_mean": float(np.mean(sumf)),
                "wall_s_median": float(np.median(walls)),
            }
            rows.append(row)
            print(f"{fam:>6} {crit:>20} {row['phases_mean']:>7.1f} "
                  f"{row['relax_edges_mean']:>9.0f} "
                  f"{row['sum_fringe_mean']:>9.0f} "
                  f"{row['wall_s_median'] * 1e3:>9.1f}")
            return row

        for crit in CRITERIA:
            record(crit, lambda s, c=crit: _solve(g, ell, ell_out, c, s))
        # depth lower bound: the clairvoyant criterion through the same engine
        record("oracle",
               lambda s: _solve(g, ell, ell_out, "oracle", s, truths[s]))
        # baseline: Delta-stepping (phases = light+heavy rounds; relax work
        # is label-correcting and may exceed m)
        def delta_solve(s):
            res = run_delta_stepping(g, s)
            jax.block_until_ready(res.dist)
            return res
        record("delta", delta_solve)

    report = {
        "config": {"tiny": bool(tiny), "n_sources": int(n_sources),
                   "seed": int(seed), "criteria": CRITERIA,
                   "backend": jax.default_backend()},
        "results": rows,
    }
    if out_json:
        with open(out_json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {out_json}")

    # the acceptance inequality the criterion plans exist to buy (and the
    # oracle sandwich): fail loudly here rather than ship a silent regression
    by = {(r["family"], r["criterion"]): r["phases_mean"] for r in rows}
    for fam in ("gnm", "rmat"):
        weak = by[(fam, "instatic|outstatic")]
        strong = by[(fam, "in|out")]
        oracle = by[(fam, "oracle")]
        assert strong < weak, (
            f"{fam}: in|out phases {strong} not < instatic|outstatic {weak}")
        assert oracle <= strong and oracle <= weak, fam
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (n~256) instead of n~2048")
    ap.add_argument("--sources", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_criteria.json")
    a = ap.parse_args()
    use_compile_cache()
    run(a.tiny, a.sources, a.seed, a.out)
