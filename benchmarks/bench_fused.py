"""Single-scan phase benchmark: fused scans, sliced ELL, tuned execution.

Measures, on the production engine (``run_phased_static``), what PR 5's
three changes buy and asserts the wins in-bench (like ``bench_criteria``):

  * **layout** — per-phase wall of the static-pair criterion on padded vs
    degree-sliced ELL. Asserted: sliced is >= 2x faster per phase on rmat
    (the padded layout pays the hub width on every row; measured wins are
    ~5-35x, so the gate has wide noise margin).
  * **single-scan phase structure** — adjacency scans per phase by
    criterion, fused vs the composed pre-PR pipeline (one kernel pass per
    dynamic key). Asserted *deterministically* from the criterion plan:
    ``in|out`` collapses 4 adjacency passes to 2 (one in-scan megakernel,
    one out-scan megakernel), ``insimple|outsimple`` 3 to 2.
  * **dynamic-criterion wall** — per-phase wall of ``in|out`` vs
    ``instatic|outstatic``. Asserted: (a) per-phase wall of ``in|out`` in
    the new configuration is at most the *static pair's* per-phase wall on
    the pre-PR padded layout on rmat — the strengthened criterion now costs
    less per phase than the weak one used to, so its phase-count win
    finally shows up on the wall clock; (b) against the seed baselines
    recorded by PR 4's BENCH_criteria.json (gnm 714us, rmat 53.7ms
    per ``in|out`` phase at the same sizes), the new per-phase wall is
    >= 1.4x / >= 5x better (measured ~2x / ~50x). The per-phase overhead
    *ratio* vs the static pair is recorded per family; its structural floor
    is the scan ratio (3 launches vs 2 -> 1.5x) in the launch-bound regime
    and the gather-volume ratio (~4x) where gather work dominates —
    DESIGN.md Sec. 9 prices both regimes.
  * **fused vs composed kernels** — wall of the fused megakernel vs the
    composed ``ell_relax`` + ``ell_key_min`` calls on identical inputs,
    plus a bit-equality assert.
  * **parity** — every engine x criterion x layout combination bit-exact
    per row vs ``run_phased``, including the forced-8-device sharded path
    (subprocess) with its settled-trace ring.

    PYTHONPATH=src python -m benchmarks.bench_fused [--tiny]
        [--out BENCH_fused.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.obs.timer import now

from repro.core import criteria as C
from repro.core import run_phased
from repro.core.graph import (
    to_ell_in,
    to_ell_in_sliced,
    to_ell_out,
    to_ell_out_sliced,
)
from repro.core.static_engine import run_phased_static
from repro.graphs import kronecker, uniform_gnp

CRITERIA = ["instatic|outstatic", "in|out"]

# Seed baselines: PR 4's committed BENCH_criteria.json per-phase walls for
# in|out at the same graph configs (gnm n=2048, rmat kronecker(11)), on the
# composed pre-single-scan engine. The full-size run asserts against them.
SEED_PP_INOUT = {"gnm": 714e-6, "rmat": 53.7e-3}
SEED_IMPROVEMENT = {"gnm": 1.4, "rmat": 5.0}


def scans_per_phase(criterion: str, fused: bool) -> int:
    """Adjacency scans per phase: the deterministic structural metric.

    Fused: one in-scan (relax, plus every in-side dynamic key riding it)
    plus one out-scan (all out-side keys, dependent included). Composed
    (the pre-PR pipeline): the relax pass plus one full pass per dynamic
    key.
    """
    plan = C.plan_for(criterion)
    if fused:
        return 1 + (1 if (plan.out_scan_keys or plan.out_scan_dep) else 0)
    return 1 + len(plan.keys)


def _families(tiny: bool):
    if tiny:
        # rmat stays at scale 9: the sliced-vs-padded gate needs real degree
        # skew, and scale 8's hub width is small enough that the margin
        # would ride on timing noise
        return {
            "gnm": lambda: uniform_gnp(256, 10 / 256, seed=7),
            "rmat": lambda: kronecker(9, seed=7),
        }
    return {
        "gnm": lambda: uniform_gnp(2048, 10 / 2048, seed=7),
        "rmat": lambda: kronecker(11, seed=7),
    }


def _pp(g, ell, ell_out, crit, srcs, reps):
    """Median-of-sources median per-phase wall of a full solve."""
    pps = []
    for s in srcs:
        solve = lambda: run_phased_static(  # noqa: E731
            g, s, ell=ell, ell_out=ell_out, criterion=crit, trace_len=1
        )
        ph = int(solve().phases)  # also compiles
        walls = []
        for _ in range(reps):
            t0 = now()
            jax.block_until_ready(solve().dist)
            walls.append(now() - t0)
        pps.append(float(np.median(walls)) / ph)
    return float(np.median(pps))


def _views(g):
    return {
        "padded": (to_ell_in(g), to_ell_out(g)),
        "sliced": (to_ell_in_sliced(g), to_ell_out_sliced(g)),
    }


def _kernel_micro(g, reps):
    """Fused megakernel vs composed relax+key_min on identical inputs."""
    from repro.kernels import ops as kops
    from repro.kernels.ell_key_min import ell_key_min_batch
    from repro.kernels.ell_relax import ell_relax_batch
    from repro.kernels.ell_relax_keys import ell_relax_keys_batch

    cols, ws = to_ell_in(g)
    rng = np.random.default_rng(0)
    d = jnp.asarray(rng.uniform(0, 5, (1, g.n)).astype(np.float32))
    ga = jnp.asarray(rng.uniform(0, 5, (1, 1, g.n)).astype(np.float32))
    gb = jnp.full_like(ga, np.inf)
    gc = jnp.where(jnp.asarray(rng.random(ga.shape) < 0.5), 0.0, np.inf)

    def fused():
        return ell_relax_keys_batch(d, ga, gb, gc, cols, ws,
                                    block_rows=4096, interpret=True)

    def composed():
        upd = ell_relax_batch(kops.pad_lane_batch(d), cols, ws,
                              block_rows=4096, interpret=True)
        fin = jnp.where(jnp.isfinite(upd), 0.0, jnp.inf)
        gate = jnp.minimum(ga[0], jnp.minimum(gb[0], gc[0] + fin))
        key = ell_key_min_batch(kops.pad_lane_batch(gate), cols, ws,
                                block_rows=4096, interpret=True)
        return upd, key

    fu, fk = fused()
    cu, ck = composed()
    np.testing.assert_array_equal(np.asarray(fu), np.asarray(cu))
    np.testing.assert_array_equal(np.asarray(fk[0]), np.asarray(ck))

    def med(fn):
        jax.block_until_ready(fn()[0])
        walls = []
        for _ in range(reps):
            t0 = now()
            jax.block_until_ready(fn()[0])
            walls.append(now() - t0)
        return float(np.median(walls))

    return {"fused_s": med(fused), "composed_s": med(composed)}


def _static_parity(g, fam):
    """Engine x criterion x layout bit-parity vs run_phased."""
    src = g.n // 3
    for crit in CRITERIA:
        gen = run_phased(g, src, crit)
        for layout, (ell, ell_out) in _views(g).items():
            for pallas in (True, False):
                r = run_phased_static(g, src, ell=ell, ell_out=ell_out,
                                      criterion=crit, use_pallas=pallas,
                                      trace_len=1)
                tag = f"{fam}:{crit}:{layout}:pallas={pallas}"
                np.testing.assert_array_equal(
                    np.asarray(r.dist), np.asarray(gen.dist), err_msg=tag)
                assert int(r.phases) == int(gen.phases), tag
                assert int(r.sum_fringe) == int(gen.sum_fringe), tag
                assert int(r.relax_edges) == int(gen.relax_edges), tag


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.core import run_phased
from repro.core.distributed import run_sharded_batch
from repro.graphs import uniform_gnp
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(tp=2)
g = uniform_gnp(180, 8 / 180, seed=5)
srcs = np.asarray([3, 0, 91, 179], np.int32)
for crit in ("instatic|outstatic", "in|out"):
    res = run_sharded_batch(g, mesh, ("data", "model"), srcs, criterion=crit,
                            trace_len=g.n + 1)
    for i, s in enumerate(srcs):
        gen = run_phased(g, int(s), crit, trace_len=g.n + 1)
        np.testing.assert_array_equal(np.asarray(res.dist[i]),
                                      np.asarray(gen.dist), err_msg=f"{crit}:{s}")
        assert int(res.phases[i]) == int(gen.phases), (crit, int(s))
        p = int(gen.phases)
        np.testing.assert_array_equal(
            np.asarray(res.settled_per_phase[i])[:p],
            np.asarray(gen.settled_per_phase)[:p], err_msg=f"{crit}:{s}")
print("SHARDED-FUSED-PARITY-PASS")
"""


def _sharded_parity():
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("XLA_FLAGS", None)
    # the parent may hold the accelerator: the child's fake devices are CPU
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED-FUSED-PARITY-PASS" in out.stdout, out.stdout + out.stderr


def run(tiny: bool = False, reps: int | None = None,
        out_json: str | None = "BENCH_fused.json"):
    reps = reps if reps is not None else (5 if tiny else 9)
    report: dict = {
        "config": {"tiny": bool(tiny), "reps": reps,
                   "backend": jax.default_backend()},
        "scans_per_phase": {},
        "families": {},
    }
    print(f"backend={jax.default_backend()} tiny={tiny}")

    # --- deterministic tentpole structure: 4 adjacency passes -> 2
    for crit, want_fused, want_composed in (
        ("in|out", 2, 4), ("insimple|outsimple", 2, 3),
        ("instatic|outstatic", 1, 1),
    ):
        f, c = scans_per_phase(crit, True), scans_per_phase(crit, False)
        report["scans_per_phase"][crit] = {"fused": f, "composed": c}
        assert (f, c) == (want_fused, want_composed), (crit, f, c)
        print(f"scans/phase {crit:20} fused={f} composed={c}")

    for fam, make in _families(tiny).items():
        g = make()
        views = _views(g)
        srcs = [3, g.n // 2, g.n - 5]
        rows: dict = {"n": int(g.n)}
        for crit in CRITERIA:
            for layout, (ell, ell_out) in views.items():
                pp = _pp(g, ell, ell_out, crit, srcs, reps)
                rows[f"pp_{crit}_{layout}"] = pp
                print(f"{fam:5} {crit:20} {layout:6} per-phase="
                      f"{pp * 1e6:9.1f}us")
        rows["kernel_micro"] = _kernel_micro(g, reps)
        rows["ratio_dynamic_padded"] = (
            rows["pp_in|out_padded"] / rows["pp_instatic|outstatic_padded"]
        )
        rows["ratio_dynamic_sliced"] = (
            rows["pp_in|out_sliced"] / rows["pp_instatic|outstatic_sliced"]
        )
        rows["sliced_speedup_static"] = (
            rows["pp_instatic|outstatic_padded"]
            / rows["pp_instatic|outstatic_sliced"]
        )
        report["families"][fam] = rows
        _static_parity(g, fam)
        print(f"{fam:5} parity OK; sliced static speedup "
              f"{rows['sliced_speedup_static']:.1f}x; dynamic ratio "
              f"padded {rows['ratio_dynamic_padded']:.2f} / sliced "
              f"{rows['ratio_dynamic_sliced']:.2f}")

    _sharded_parity()
    print("sharded (8-device) parity OK")

    # --- wall asserts (wide noise margins; see module docstring) ---
    rmat = report["families"]["rmat"]
    gnm = report["families"]["gnm"]
    # sliced ELL pays off where degree skew exists
    assert rmat["sliced_speedup_static"] >= 2.0, rmat["sliced_speedup_static"]
    # the strengthened criterion on the new layout now costs LESS per phase
    # than the weak static pair on the old layout
    assert (rmat["pp_in|out_sliced"]
            <= rmat["pp_instatic|outstatic_padded"]), rmat
    if not tiny:
        # absolute per-phase walls vs the seed engine (same graph configs)
        for fam, seed in SEED_PP_INOUT.items():
            best = min(report["families"][fam]["pp_in|out_padded"],
                       report["families"][fam]["pp_in|out_sliced"])
            need = seed / SEED_IMPROVEMENT[fam]
            assert best <= need, (fam, best, seed)
            report["families"][fam]["seed_pp_inout"] = seed
            report["families"][fam]["seed_improvement"] = seed / best
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
    ap.add_argument("--out", default="BENCH_fused.json")
    a = ap.parse_args()
    use_compile_cache()
    run(a.tiny, a.reps, a.out)
