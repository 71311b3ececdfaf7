"""Engine-portfolio benchmark: substrate delta-stepping + measured routing.

Measures and asserts, in-bench, the two contracts DESIGN.md Sec. 12
promises for the phase-policy / portfolio layer:

  * **substrate delta vs host baseline** — the ``"delta"`` policy on the
    batched stepper (B lanes, fused weight-gated relax megakernel per
    phase) against the legacy host-scheduled ``run_delta`` loop solving
    the same sources sequentially. Phase *counts* are identical by
    construction (same light/heavy round structure), so the qps ratio IS
    the per-phase wall ratio. Asserted: substrate qps >= legacy qps on
    every family (batch amortisation makes this a wide margin).
  * **portfolio >= every fixed engine** — :func:`measure_portfolio`
    records every candidate policy x layout per graph family, then a
    mixed gnm+rmat query trace is costed from those measured entries:
    the portfolio routes each family to its measured-best engine, a
    fixed engine serves both families with one configuration. Asserted:
    the portfolio's projected trace wall <= every fixed engine's (the
    router is the per-family argmax over the same measurements — the
    assertion pins that the routing, key schema and entry plumbing
    actually deliver that optimum). A real served run through
    :class:`PortfolioBackend` is also timed and reported.

    PYTHONPATH=src python -m benchmarks.bench_portfolio [--tiny]
        [--out BENCH_portfolio.json]
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import timed
from repro.compile_cache import use_compile_cache
from repro.core.delta_stepping import run_delta
from repro.core.static_engine import run_phased_static_batch
from repro.graphs import kronecker, uniform_gnp
from repro.kernels.config import TuningLedger
from repro.serving import (
    DEFAULT_CANDIDATES,
    ContinuousBatcher,
    PortfolioBackend,
    graph_family,
    measure_portfolio,
    pick_engine,
)


def families(tiny: bool) -> dict:
    if tiny:
        return {
            "gnm": uniform_gnp(256, 10.0 / 256, seed=7),
            "rmat": kronecker(8, seed=7),
        }
    return {
        "gnm": uniform_gnp(2048, 10.0 / 2048, seed=7),
        "rmat": kronecker(11, seed=7),
    }


# ---------------------------------------------------------------------------
# substrate delta vs the host-scheduled legacy loop
# ---------------------------------------------------------------------------


def bench_delta_vs_legacy(name: str, g, lanes: int, reps: int) -> dict:
    sources = ((np.arange(lanes, dtype=np.int64) * 7919) % g.n).astype(np.int32)

    def substrate():
        # degree-sliced adjacency: the substrate's strong layout (bit-
        # identical distances either way; padded ELL pays max-degree
        # padding on skewed families, which the portfolio would never
        # route to)
        return jax.block_until_ready(
            run_phased_static_batch(
                g, sources, criterion="delta", layout="sliced"
            ).dist
        )

    def legacy():
        for s in sources:
            jax.block_until_ready(run_delta(g, int(s)).dist)

    substrate()  # compile warmup (timed() has none)
    legacy()
    sub_wall, _ = timed(substrate, repeats=reps)
    leg_wall, _ = timed(legacy, repeats=reps)

    # phase-count parity: the substrate schedule is the same light/heavy
    # round structure, so per-lane phases must equal the legacy loop's
    sub = run_phased_static_batch(g, sources, criterion="delta",
                                  layout="sliced")
    legs = [run_delta(g, int(s)) for s in sources]
    sub_phases = np.asarray(sub.phases)
    leg_phases = np.asarray([int(r.phases) for r in legs])
    assert np.array_equal(sub_phases, leg_phases), (
        f"{name}: substrate phase counts {sub_phases.tolist()} != "
        f"legacy {leg_phases.tolist()}"
    )
    for i, r in enumerate(legs):
        assert np.array_equal(np.asarray(r.dist), np.asarray(sub.dist[i])), (
            f"{name}: lane {i} dist mismatch vs legacy"
        )

    total_phases = int(leg_phases.sum())
    rec = {
        "lanes": lanes,
        "phases": total_phases,
        "substrate_wall_s": sub_wall,
        "legacy_wall_s": leg_wall,
        "substrate_qps": lanes / sub_wall,
        "legacy_qps": lanes / leg_wall,
        "substrate_per_phase_s": sub_wall / total_phases,
        "legacy_per_phase_s": leg_wall / total_phases,
        "speedup": leg_wall / sub_wall,
    }
    assert rec["substrate_qps"] >= rec["legacy_qps"], (
        f"{name}: substrate delta ({rec['substrate_qps']:.2f} qps) lost to "
        f"the host-side baseline ({rec['legacy_qps']:.2f} qps)"
    )
    return rec


# ---------------------------------------------------------------------------
# portfolio vs every fixed engine on a mixed trace
# ---------------------------------------------------------------------------


def bench_portfolio(fams: dict, lanes: int, queries_per_family: int,
                    reps: int) -> dict:
    ledger = TuningLedger()
    measured: dict = {}
    for name, g in fams.items():
        entries = measure_portfolio(g, lanes=lanes, ledger=ledger,
                                    repeats=reps)
        measured[name] = {
            f"{policy}:{layout}": entry
            for (policy, layout), entry in entries.items()
        }

    # mixed-trace projection from the measured entries: Q queries per
    # family, served at each engine's measured qps on that family
    fixed_walls = {}
    for cand in DEFAULT_CANDIDATES:
        key = f"{cand.ledger_policy}:{cand.layout}"
        fixed_walls[key] = sum(
            queries_per_family / measured[name][key]["qps"] for name in fams
        )
    routed = {name: pick_engine(graph_family(g), lanes, ledger=ledger)
              for name, g in fams.items()}
    portfolio_wall = sum(
        queries_per_family
        / measured[name][f"{c.ledger_policy}:{c.layout}"]["qps"]
        for name, c in routed.items()
    )
    best_fixed = min(fixed_walls.values())
    assert portfolio_wall <= best_fixed * (1 + 1e-9), (
        f"portfolio projected wall {portfolio_wall:.4f}s worse than best "
        f"fixed engine {best_fixed:.4f}s"
    )

    # and one real served run through the router (reported, not ranked:
    # scheduler overhead rides on top of the projected engine walls)
    served = {}
    for name, g in fams.items():
        backend = PortfolioBackend(g, lanes_hint=lanes, ledger=ledger)
        rng = np.random.default_rng(23)
        srcs = rng.integers(0, g.n, size=queries_per_family)

        def serve(g=g, backend=backend, srcs=srcs):
            server = ContinuousBatcher(g, lanes=lanes, backend=backend)
            for s in srcs:
                server.submit(int(s))
            done = server.drain(max_steps=100_000)
            assert len(done) == len(srcs)

        serve()  # warmup
        wall, _ = timed(serve, repeats=max(1, reps - 1))
        served[name] = {
            "engine": f"{routed[name].ledger_policy}:{routed[name].layout}",
            "wall_s": wall,
            "qps": queries_per_family / wall,
        }

    return {
        "measured": measured,
        "routed": {n: f"{c.ledger_policy}:{c.layout}" for n, c in routed.items()},
        "fixed_trace_wall_s": fixed_walls,
        "portfolio_trace_wall_s": portfolio_wall,
        "served": served,
    }


# ---------------------------------------------------------------------------


def run(tiny: bool = False, reps: int | None = None,
        out_json: str | None = "BENCH_portfolio.json") -> dict:
    reps = reps if reps is not None else (2 if tiny else 5)
    lanes = 8
    fams = families(tiny)
    report: dict = {"config": {"tiny": tiny, "reps": reps, "lanes": lanes,
                               "n": {k: g.n for k, g in fams.items()}}}

    print(f"# substrate delta vs legacy host loop (B={lanes}, reps={reps})")
    report["delta_vs_legacy"] = {}
    for name, g in fams.items():
        rec = bench_delta_vs_legacy(name, g, lanes, reps)
        report["delta_vs_legacy"][name] = rec
        print(f"delta,{name},substrate_qps,{rec['substrate_qps']:.2f},"
              f"legacy_qps,{rec['legacy_qps']:.2f},"
              f"speedup,{rec['speedup']:.2f}x")

    print("# portfolio vs fixed engines (mixed gnm+rmat trace)")
    pf = bench_portfolio(fams, lanes, queries_per_family=2 * lanes, reps=reps)
    report["portfolio"] = pf
    for name, eng in pf["routed"].items():
        print(f"portfolio,routed,{name},{eng}")
    for key, wall in sorted(pf["fixed_trace_wall_s"].items(),
                            key=lambda kv: kv[1]):
        print(f"portfolio,fixed,{key},{wall:.4f}s")
    print(f"portfolio,projected,{pf['portfolio_trace_wall_s']:.4f}s")
    for name, rec in pf["served"].items():
        print(f"portfolio,served,{name},{rec['engine']},{rec['wall_s']:.4f}s")

    if out_json:
        with open(out_json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {out_json}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (n~256) instead of n~2048")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--out", default="BENCH_portfolio.json")
    a = ap.parse_args()
    use_compile_cache()
    run(a.tiny, a.reps, a.out)
