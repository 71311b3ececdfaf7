"""Observability-layer benchmark: overhead budget + attribution parity.

Measures and asserts, in-bench, the three contracts DESIGN.md Sec. 11
promises for the ``repro.obs`` layer:

  * **overhead** — per-phase wall of the ``in|out`` stepper hot loop in
    three configurations: bare (no obs anywhere), obs *disabled* (a
    disabled tracer + throwaway registry plumbed through the serving-style
    call path), and telemetry *enabled* (full fringe/relax/attribution
    rings recorded on device). Asserted: disabled is indistinguishable
    from bare (<= 2% — same compiled program, the None ring fields select
    the untraced code path), and enabled costs <= 5% (three extra int32
    scatter writes per phase against full adjacency scans).
  * **attribution parity** — for every engine x criterion combination the
    per-criterion settle attribution sums *exactly* (integer equality) to
    ``settled_per_phase``, phase by phase, lane by lane: the first-true
    claiming is a partition of the settled set. Engines: padded and
    degree-sliced layouts of the batched stepper.
  * **trace round-trip** — a trace captured from an obs-enabled
    ``ContinuousBatcher`` run validates (``validate_events``), survives
    export -> ``python -m repro.obs validate`` -> ``export`` unchanged in
    event count, and the registry snapshot renders through both JSON and
    Prometheus exposition.

    PYTHONPATH=src python -m benchmarks.bench_obs [--tiny]
        [--out BENCH_obs.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.graph import to_ell_in, to_ell_in_sliced
from repro.core.oracle import dijkstra_numpy
from repro.core.static_engine import (
    init_batch_state,
    lanes_active,
    run_phased_static_batch,
    step_batch,
)
from repro.graphs import uniform_gnp
from repro.obs import Observability
from repro.obs.telemetry import attribution_terms, phase_telemetry
from repro.obs.timer import now
from repro.serving import ContinuousBatcher, DistCache

CRITERIA = ["instatic|outstatic", "in|out", "insimple|outsimple", "dijk",
            "oracle"]


# ---------------------------------------------------------------------------
# overhead
# ---------------------------------------------------------------------------


def bench_overhead(n: int, reps: int) -> dict:
    g = uniform_gnp(n, 8.0 / n, seed=7)
    ell = to_ell_in(g)
    srcs = np.asarray([0, 1, 2, 3], np.int32)
    obs_off = Observability.disabled()

    def make_solve(telemetry: bool, trace_len: int, tracer=None):
        def solve():
            if tracer is not None:
                # the no-op span a disabled-obs caller leaves plumbed in
                with tracer.span("solve"):
                    pass
            state = init_batch_state(g, srcs, criterion="in|out",
                                     trace_len=trace_len, telemetry=telemetry)
            while lanes_active(state).any():
                state = step_batch(g, state, 1 << 30, ell=ell)
            return state

        return solve

    # bare: the pre-obs configuration (no rings beyond the always-on
    # settled trace, no tracer/registry anywhere near the loop);
    # disabled: a disabled tracer plumbed through — the contract is that
    # this is the *same compiled program* (None ring fields);
    # enabled: full telemetry rings recorded on device each phase.
    configs = {
        "bare": make_solve(False, 1),
        "disabled": make_solve(False, 1, tracer=obs_off.tracer),
        "enabled": make_solve(True, g.n + 1),
    }
    phases = {}
    for name, solve in configs.items():  # compile / warm each program once
        phases[name] = int(np.asarray(solve().phases).max())
    # interleave the configurations round-robin so clock drift and CPU
    # scheduling hit all three equally — back-to-back blocks at sub-ms
    # scale systematically favour whichever ran last
    walls: dict[str, list[float]] = {name: [] for name in configs}
    for _ in range(reps):
        for name, solve in configs.items():
            t0 = now()
            jax.block_until_ready(solve().dist)
            walls[name].append(now() - t0)
    pp = {name: float(np.median(ws)) / phases[name]
          for name, ws in walls.items()}
    return {
        "n": n,
        "reps": reps,
        "per_phase_bare_s": pp["bare"],
        "per_phase_obs_disabled_s": pp["disabled"],
        "per_phase_telemetry_s": pp["enabled"],
        "disabled_overhead": pp["disabled"] / pp["bare"] - 1.0,
        "enabled_overhead": pp["enabled"] / pp["bare"] - 1.0,
    }


# ---------------------------------------------------------------------------
# attribution parity
# ---------------------------------------------------------------------------


def bench_attribution(n: int) -> dict:
    g = uniform_gnp(n, 8.0 / n, seed=11)
    srcs = [0, n // 3, n // 2]
    engines = {
        "stepper-padded": {"ell": to_ell_in(g)},
        "stepper-sliced": {"ell": to_ell_in_sliced(g)},
    }
    out: dict = {}
    for ename, ekw in engines.items():
        for crit in CRITERIA:
            kw = dict(ekw)
            if "oracle" in crit:
                kw["dist_true"] = np.stack(
                    [dijkstra_numpy(g, s) for s in srcs]
                ).astype(np.float32)
            res = run_phased_static_batch(
                g, srcs, criterion=crit, trace_len=g.n + 1, telemetry=True,
                **kw,
            )
            attr = np.asarray(res.settle_attribution)
            sp = np.asarray(res.settled_per_phase)
            exact = bool(np.array_equal(attr.sum(axis=2), sp))
            assert exact, (
                f"{ename} x {crit}: attribution does not sum to "
                f"settled_per_phase (max |diff| "
                f"{np.abs(attr.sum(axis=2) - sp).max()})"
            )
            terms = attribution_terms(crit)
            out[f"{ename}:{crit}"] = {
                "exact": exact,
                "settled_total": int(sp.sum()),
                "by_term": {
                    t: int(attr[..., k].sum()) for k, t in enumerate(terms)
                },
            }
    return out


# ---------------------------------------------------------------------------
# trace round-trip
# ---------------------------------------------------------------------------


def bench_trace_roundtrip(n: int) -> dict:
    g = uniform_gnp(n, 8.0 / n, seed=13)
    obs = Observability.enabled()
    server = ContinuousBatcher(g, lanes=4, phases_per_step=8,
                               cache=DistCache(capacity=64), obs=obs)
    rng = np.random.default_rng(17)
    for s in rng.integers(0, g.n, size=12):
        server.submit(int(s))
    done = server.drain()
    # fold stepper phase telemetry into the same registry/tracer
    res = run_phased_static_batch(g, [0, 1], criterion="in|out",
                                  trace_len=g.n + 1, telemetry=True)
    state = init_batch_state(g, [0, 1], criterion="in|out",
                             trace_len=g.n + 1, telemetry=True)
    while lanes_active(state).any():
        state = step_batch(g, state, 1 << 30)
    from repro.obs import publish_phase_telemetry, trace_phase_telemetry

    recs = phase_telemetry(state)
    publish_phase_telemetry(recs, obs.registry)
    trace_phase_telemetry(recs, obs.tracer)

    from repro.obs.tracer import validate_events, validate_trace_file

    errors = validate_events(obs.tracer.events())
    assert not errors, errors

    tmp = tempfile.mkdtemp(prefix="bench_obs_")
    trace_path = os.path.join(tmp, "trace.json")
    obs.tracer.export(trace_path)
    assert validate_trace_file(trace_path) == []

    # round-trip through the CLI: validate, export (normalise), re-validate
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    rt_path = os.path.join(tmp, "trace_rt.json")
    for args in (["validate", trace_path],
                 ["export", trace_path, "-o", rt_path],
                 ["validate", rt_path]):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.obs", *args],
            capture_output=True, text=True, env=env,
        )
        assert proc.returncode == 0, (args, proc.stdout, proc.stderr)
    with open(trace_path) as f:
        n_orig = len(json.load(f)["traceEvents"])
    with open(rt_path) as f:
        n_rt = len(json.load(f)["traceEvents"])
    assert n_rt == n_orig, (n_orig, n_rt)

    # both expositions render
    snap = obs.registry.snapshot()
    json.dumps(snap)
    prom = obs.registry.to_prometheus()
    assert "serving_latency_s" in prom and "engine_phase_fringe" in prom
    return {
        "events": n_orig,
        "requests": len(done),
        "registry_metrics": len(obs.registry),
        "cli_roundtrip_ok": True,
    }


# ---------------------------------------------------------------------------


def run(tiny: bool = False, reps: int | None = None,
        out_json: str | None = "BENCH_obs.json") -> dict:
    n = 300 if tiny else 1500
    reps = reps if reps is not None else (3 if tiny else 5)
    report: dict = {"config": {"n": n, "reps": reps, "tiny": tiny}}

    print(f"# obs overhead (in|out stepper, n={n}, B=4, reps={reps})")
    ov = bench_overhead(n, reps)
    report["overhead"] = ov
    print(f"overhead,bare_s,{ov['per_phase_bare_s']:.3e}")
    print(f"overhead,disabled_s,{ov['per_phase_obs_disabled_s']:.3e},"
          f"{ov['disabled_overhead']*100:+.2f}%")
    print(f"overhead,telemetry_s,{ov['per_phase_telemetry_s']:.3e},"
          f"{ov['enabled_overhead']*100:+.2f}%")
    # the acceptance budget: disabled ~ 0, enabled <= 5%. Medians over
    # `reps` interleaved drained solves; the 2% disabled allowance is timer
    # noise on a bit-identical program. At --tiny scale a phase is ~0.5 ms
    # and shared-CI scheduling jitter dwarfs the effect being measured, so
    # the smoke run only guards against gross regressions (>25%).
    dis_budget, en_budget = (0.25, 0.25) if tiny else (0.02, 0.05)
    assert ov["disabled_overhead"] <= dis_budget, ov
    assert ov["enabled_overhead"] <= en_budget, ov

    print("# attribution parity (engine x criterion)")
    at = bench_attribution(max(200, n // 3))
    report["attribution"] = at
    for key, rec in at.items():
        by = " ".join(f"{t}={c}" for t, c in rec["by_term"].items())
        print(f"attribution,{key},exact={rec['exact']},{by}")

    print("# trace round-trip")
    rt = bench_trace_roundtrip(max(150, n // 5))
    report["trace_roundtrip"] = rt
    print(f"trace,events,{rt['events']}")
    print(f"trace,cli_roundtrip_ok,{rt['cli_roundtrip_ok']}")

    if out_json:
        with open(out_json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {out_json}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (n~300) instead of n~1500")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--out", default="BENCH_obs.json")
    a = ap.parse_args()
    use_compile_cache()
    run(a.tiny, a.reps, a.out)
