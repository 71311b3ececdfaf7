"""Target-aware s->t benchmark: early-exit lanes + cache-served answers.

Measures and asserts, in-bench, the four contracts DESIGN.md Sec. 13
promises for point-to-point queries:

  * **early exit beats the full solve** — on every family, target lanes
    (``run_phased_static(..., target=t)``) spend strictly fewer engine
    phases in total than full solves of the same sources, and never more
    on any single pair (the lane stops the phase its target settles).
  * **bit-exactness everywhere** — for every policy x layout the engine
    portfolio routes between, the target lane's ``dist[t]`` and the
    bidirectional ``run_point_to_point`` answer are bitwise equal to the
    full-solve ``run_phased_static`` row. Goal-directed pruning and the
    meeting bound are allowed to skip work, never to change the answer.
  * **cache-served point traffic** — a point query against a source whose
    full solve is cached completes as a zero-phase hit; over a served
    trace the engine trip counter does not move at all, and the p50 s->t
    latency is asserted >= 2x better than full-solve serving of the same
    trace on a cold server.
  * **bidirectional unreachability certificate** — on a family extended
    with vertices outside the source component, the backward lane's
    exhaustion answers ``inf`` in fewer forward phases than the full
    flood the forward-only early exit would degenerate to.

    PYTHONPATH=src python -m benchmarks.bench_p2p [--tiny]
        [--out BENCH_p2p.json]
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.graph import from_coo
from repro.core.static_engine import run_phased_static
from repro.graphs import kronecker, uniform_gnp
from repro.serving import ContinuousBatcher, DistCache, run_point_to_point


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


ENGINES = (
    ("instatic|outstatic", "padded"),
    ("instatic|outstatic", "sliced"),
    ("in|out", "padded"),
    ("in|out", "sliced"),
    ("delta", "padded"),
    ("delta", "sliced"),
)


def _pairs(g, n_sources: int, targets_per_source: int, seed: int):
    rng = np.random.default_rng(seed)
    sources = (np.arange(n_sources, dtype=np.int64) * 7919) % g.n
    return [
        (int(s), int(t))
        for s in sources
        for t in rng.integers(0, g.n, size=targets_per_source)
    ]


# ---------------------------------------------------------------------------
# early-exit phase counts vs the full solve
# ---------------------------------------------------------------------------


def bench_phases(name: str, g, pairs) -> dict:
    full = {}
    for s in sorted({s for s, _ in pairs}):
        r = run_phased_static(g, s)
        full[s] = (int(r.phases), np.asarray(r.dist))
    point_total = full_total = 0
    per_pair = []
    for s, t in pairs:
        full_phases, ref = full[s]
        r = run_phased_static(g, s, target=t)
        phases = int(r.phases)
        assert np.asarray(r.dist)[t] == ref[t], (
            f"{name}: target lane dist[{t}] differs from the full solve"
        )
        assert phases <= full_phases, (
            f"{name}: s->t ({s},{t}) took {phases} phases, full solve "
            f"{full_phases} — the target lane must never run longer"
        )
        point_total += phases
        full_total += full_phases
        per_pair.append({"s": s, "t": t, "point": phases, "full": full_phases})
    assert point_total < full_total, (
        f"{name}: early exit saved no phases over {len(pairs)} pairs "
        f"({point_total} vs {full_total})"
    )
    return {
        "pairs": len(per_pair),
        "point_phases": point_total,
        "full_phases": full_total,
        "phase_ratio": point_total / full_total,
        "per_pair": per_pair,
    }


# ---------------------------------------------------------------------------
# bit-exactness across every routed engine
# ---------------------------------------------------------------------------


def bench_exactness(name: str, g, pairs) -> dict:
    checks = 0
    for policy, layout in ENGINES:
        refs = {}
        for s, t in pairs:
            if s not in refs:
                refs[s] = np.asarray(
                    run_phased_static(g, s, criterion=policy,
                                      layout=layout).dist
                )
            ref = float(refs[s][t])
            lane = run_phased_static(g, s, criterion=policy, layout=layout,
                                     target=t)
            got = float(np.asarray(lane.dist)[t])
            assert got == ref, (
                f"{name}: {policy}/{layout} target lane dist[{t}] = {got} "
                f"!= full solve {ref}"
            )
            bi = run_point_to_point(g, s, t, policy=policy, layout=layout)
            assert bi.distance == ref, (
                f"{name}: {policy}/{layout} bidirectional answer "
                f"{bi.distance} != full solve {ref}"
            )
            checks += 2
    return {"engines": [f"{p}:{lay}" for p, lay in ENGINES],
            "checks": checks}


# ---------------------------------------------------------------------------
# served traffic: cached point answers vs full-solve serving
# ---------------------------------------------------------------------------


def _p50(reqs) -> float:
    return float(np.percentile([r.latency for r in reqs], 50))


def bench_served(name: str, g, pairs, lanes: int) -> dict:
    sources = sorted({s for s, _ in pairs})

    def serve_point_cached():
        server = ContinuousBatcher(g, lanes=lanes, cache=DistCache(),
                                   point_queries=True)
        for s in sources:  # warm the cache with full solves
            server.submit(s)
        server.drain(max_steps=100_000)
        trips_before = server.metrics.engine_trips
        reqs = [server.submit(s, target=t) for s, t in pairs]
        server.drain(max_steps=100_000)
        # the tentpole's serving contract: every point query against a
        # warmed source is answered from the cached full row without the
        # engine moving at all
        assert all(r.cache_hit and r.phases == 0 for r in reqs), (
            f"{name}: point query missed the warmed cache"
        )
        assert server.metrics.engine_trips == trips_before, (
            f"{name}: cache-served point traffic launched engine trips"
        )
        return reqs

    def serve_full_cold():
        server = ContinuousBatcher(g, lanes=lanes)
        reqs = [server.submit(s) for s, _ in pairs]
        server.drain(max_steps=100_000)
        return reqs

    def serve_point_lanes():
        server = ContinuousBatcher(g, lanes=lanes, point_queries=True)
        reqs = [server.submit(s, target=t) for s, t in pairs]
        server.drain(max_steps=100_000)
        return reqs

    for fn in (serve_point_cached, serve_full_cold, serve_point_lanes):
        fn()  # compile warmup: latencies must not include jit time
    cached = serve_point_cached()
    full = serve_full_cold()
    point = serve_point_lanes()
    rec = {
        "queries": len(pairs),
        "lanes": lanes,
        "cached_point_p50_s": _p50(cached),
        "full_solve_p50_s": _p50(full),
        "point_lane_p50_s": _p50(point),
        "point_lane_phases_mean": float(
            np.mean([r.phases for r in point])
        ),
        "full_solve_phases_mean": float(
            np.mean([r.phases for r in full])
        ),
    }
    rec["served_speedup"] = rec["full_solve_p50_s"] / rec["cached_point_p50_s"]
    assert rec["served_speedup"] >= 2.0, (
        f"{name}: cache-served p50 {rec['cached_point_p50_s']:.6f}s is not "
        f">= 2x better than full-solve serving {rec['full_solve_p50_s']:.6f}s"
    )
    return rec


# ---------------------------------------------------------------------------
# bidirectional unreachability certificate
# ---------------------------------------------------------------------------


def bench_unreachable(name: str, g) -> dict:
    # extend the family graph with 4 vertices no edge touches: unreachable
    # targets whose forward-only early exit would flood the whole component
    src = np.asarray(g.src, np.int64)
    dst = np.asarray(g.dst, np.int64)
    w = np.asarray(g.w, np.float32)
    gx = from_coo(src, dst, w, g.n + 4)
    full = run_phased_static(gx, 0)
    full_phases = int(full.phases)
    assert float(np.asarray(full.dist)[g.n]) == float("inf")
    r = run_point_to_point(gx, 0, g.n, phases_per_chunk=4)
    assert r.distance == float("inf"), (
        f"{name}: unreachable target answered {r.distance}"
    )
    assert r.unreachable_certified, (
        f"{name}: backward lane failed to certify unreachability"
    )
    assert r.phases_forward < full_phases, (
        f"{name}: certificate saved no forward phases "
        f"({r.phases_forward} vs {full_phases})"
    )
    return {
        "full_phases": full_phases,
        "forward_phases": r.phases_forward,
        "backward_phases": r.phases_backward,
    }


# ---------------------------------------------------------------------------


def run(tiny: bool = False, out_json: str | None = "BENCH_p2p.json") -> dict:
    lanes = 8
    fams = families(tiny)
    n_sources = 4 if tiny else 8
    targets_per_source = 3 if tiny else 4
    report: dict = {"config": {"tiny": tiny, "lanes": lanes,
                               "n": {k: g.n for k, g in fams.items()}}}

    for name, g in fams.items():
        pairs = _pairs(g, n_sources, targets_per_source, seed=23)
        print(f"# {name} (n={g.n}, {len(pairs)} s->t pairs)")
        ph = bench_phases(name, g, pairs)
        print(f"p2p,{name},phases,point,{ph['point_phases']},"
              f"full,{ph['full_phases']},ratio,{ph['phase_ratio']:.3f}")
        ex = bench_exactness(name, g, pairs[: len(pairs) // 2 or 1])
        print(f"p2p,{name},exactness,checks,{ex['checks']}")
        sv = bench_served(name, g, pairs, lanes)
        print(f"p2p,{name},served,cached_p50,{sv['cached_point_p50_s']:.6f}s,"
              f"full_p50,{sv['full_solve_p50_s']:.6f}s,"
              f"speedup,{sv['served_speedup']:.1f}x")
        un = bench_unreachable(name, g)
        print(f"p2p,{name},unreachable,forward,{un['forward_phases']},"
              f"full,{un['full_phases']}")
        report[name] = {"phases": ph, "exactness": ex, "served": sv,
                        "unreachable": un}

    if out_json:
        with open(out_json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {out_json}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (n~256) instead of n~2048")
    ap.add_argument("--out", default="BENCH_p2p.json")
    a = ap.parse_args()
    use_compile_cache()
    run(a.tiny, a.out)
