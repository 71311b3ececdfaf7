"""Chip smoke test: serve SSSP queries on a TPU and check every answer.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded serving path on four chips

One chip: a road-network-sized grid (``grid_road(512, 512)``, n=262,144,
about 1.07 M arcs — the vertex count of the DIMACS 9th Challenge
``USA-road-d.NY`` instance) is served through ``ContinuousBatcher`` with
8 lanes, a ``DistCache`` and a point-capable ``StaticBackend``: 24 full-row
and 8 s->t queries in two waves, arranged so that cache hits, coalescing
and early-exiting s->t lanes all occur. Every full row must pass
``verify_row`` (the exact f32 relax fixed point), four rows must match
``dijkstra_numpy`` to rtol=1e-5, and every s->t answer must equal, bit for
bit, the entry of the full row for its source.

``--chips 4``: the same graph and the full-row traffic on its first 8
sources through ``ContinuousBatcher(ShardedBackend(...))`` on a mesh over
four chips; every row must pass ``verify_row`` and be bit-equal to the
one-device solve on device 0.

Lines before the last are informational. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only after every check passed.
The script exits non-zero without it when JAX finds no TPU, when a
``REPRO_*`` override is set, or when any check fails.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import dijkstra_numpy  # noqa: E402
from repro.core.static_engine import run_phased_static_batch  # noqa: E402
from repro.graphs import grid_road  # noqa: E402
from repro.kernels import config as kcfg  # noqa: E402
from repro.obs import timer  # noqa: E402
from repro.serving import (  # noqa: E402
    ContinuousBatcher,
    DistCache,
    ShardedBackend,
    StaticBackend,
    verify_row,
)

GRID = (512, 512)
LANES = 8
N_DIJKSTRA = 4
# the sharded phase solves every row again on one device, so it serves the
# first 8 sources only: one lane-fill of engine work on each side
SHARDED_SOURCES = 8

# Two waves of (source index, target index or None) requests over 16 seeded
# sources and 8 seeded targets. Wave 1 holds all the engine work, so lanes
# refill as queries finish: its s->t queries come first and take their own
# early-exiting lanes (a point lane is never coalesced onto), a repeat of a
# queued full row coalesces, and an s->t query behind its source's full row
# coalesces onto that lane. Wave 2 is answered from the cache.
WAVES = (
    ((12, 0), (13, 1), (14, 2), (15, 3),
     (0, None), (0, None), (1, None), (1, 4), (2, None), (2, 5), (3, None),
     (4, None), (5, None), (5, None), (6, None), (7, None), (8, None),
     (9, None), (10, None), (11, None), (12, None), (13, None), (14, None),
     (15, None)),
    ((3, None), (4, None), (6, None), (7, None), (8, None), (9, None),
     (10, 6), (11, 7)),
)


class SmokeFailure(AssertionError):
    """A served answer disagreed with its reference."""


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def make_traffic(n: int, seed: int, full_only: bool = False,
                 sources: int = 16):
    """The :data:`WAVES` pattern over seeded vertex ids: a list of waves,
    each a list of ``(source, target or None)`` requests, keeping those on
    the first ``sources`` sources (and only full rows if ``full_only``)."""
    picks = np.random.default_rng(seed).choice(n, size=24, replace=False)
    src = [int(v) for v in picks[:16]]
    tgt = [int(v) for v in picks[16:]]
    return [[(src[i], None if j is None else tgt[j]) for i, j in wave
             if i < sources and not (full_only and j is not None)]
            for wave in WAVES]


def _bits(x) -> int:
    return int(np.float32(x).view(np.uint32))


def serve(backend, waves, lanes: int = LANES):
    """Serve ``waves`` through a ``ContinuousBatcher`` over ``backend``, one
    wave drained before the next arrives; returns (requests, seconds per
    wave)."""
    server = ContinuousBatcher(
        backend.g, lanes=lanes, backend=backend, cache=DistCache(capacity=64),
        point_queries=getattr(backend, "point_queries", False))
    reqs, wave_s = [], []
    for wave in waves:
        t0 = timer.now()
        reqs += [server.submit(s, target=t) for s, t in wave]
        server.drain(max_steps=100_000)
        wave_s.append(timer.now() - t0)
    return reqs, wave_s


def check_answers(g, reqs, n_dijkstra: int = N_DIJKSTRA) -> dict:
    """Check every served answer; raises :class:`SmokeFailure` on the first
    disagreement. Returns the full rows by source."""
    bad = [r for r in reqs if r.outcome != "ok"]
    if bad:
        raise SmokeFailure(f"{len(bad)} requests not ok: "
                           f"{[(r.req_id, r.outcome, r.fail_reason) for r in bad]}")
    full = {}
    for r in reqs:
        if r.target is not None:
            continue
        if r.source not in full:
            reason = verify_row(g, r.dist, r.source)
            if reason is not None:
                raise SmokeFailure(f"row of source {r.source}: {reason}")
            full[r.source] = r.dist
        elif not np.array_equal(full[r.source], r.dist):
            raise SmokeFailure(f"two full rows of source {r.source} differ")
    for s in list(full)[:n_dijkstra]:
        ref = dijkstra_numpy(g, s)
        got = np.asarray(full[s], np.float64)
        fin = np.isfinite(ref)
        if not (np.array_equal(np.isfinite(got), fin)
                and np.allclose(got[fin], ref[fin], rtol=1e-5, atol=0.0)):
            raise SmokeFailure(f"row of source {s} disagrees with Dijkstra")
    for r in reqs:
        if r.target is None:
            continue
        if r.source not in full:
            raise SmokeFailure(f"s->t source {r.source} has no full row")
        if _bits(r.distance) != _bits(full[r.source][r.target]):
            raise SmokeFailure(
                f"s->t ({r.source}, {r.target}) = {r.distance!r}, full row "
                f"has {full[r.source][r.target]!r}")
    return full


def _route_counts(reqs) -> dict:
    engine = [r for r in reqs if not r.cache_hit and not r.coalesced]
    return {
        "requests": len(reqs),
        "cache_hits": sum(r.cache_hit for r in reqs),
        "coalesced": sum(r.coalesced for r in reqs),
        "engine_full": sum(r.target is None for r in engine),
        "engine_point": sum(r.target is not None for r in engine),
        "phases_full": [r.phases for r in engine if r.target is None],
        "phases_point": [r.phases for r in engine if r.target is not None],
    }


def serve_and_check(g, waves, lanes: int = LANES) -> dict:
    """The one-chip phase: full-row and s->t traffic through a point-capable
    ``StaticBackend``; every answer checked. Returns the route counts."""
    reqs, wave_s = serve(StaticBackend(g, point_queries=True), waves, lanes)
    check_answers(g, reqs)
    stats = _route_counts(reqs)
    if not (stats["cache_hits"] and stats["coalesced"]
            and stats["engine_point"]):
        raise SmokeFailure(f"traffic missed a serving route: {stats}")
    stats["wave_s"] = wave_s
    return stats


def serve_sharded_and_check(g, waves, devices, lanes: int = LANES) -> dict:
    """The multi-chip phase: full-row traffic through a ``ShardedBackend`` on
    a mesh over ``devices``; every row verified and bit-equal to the
    one-device solve on ``devices[0]``. Returns the route counts.

    The one-device rows come from ``run_phased_static_batch``, whose rows are
    bit-equal to ``run_phased_static``'s: one batched solve instead of one
    per source."""
    mesh = jax.make_mesh((len(devices),), ("v",), devices=devices)
    reqs, wave_s = serve(ShardedBackend(g, mesh, ("v",)), waves, lanes)
    full = check_answers(g, reqs)
    # before the one-device solve adds to device 0's peak
    served_peaks = [_peak_bytes(d) for d in devices]
    t0 = timer.now()
    with jax.default_device(devices[0]):
        ref = np.asarray(run_phased_static_batch(g, list(full)).dist)
    reference_s = timer.now() - t0
    for (s, got), want in zip(full.items(), ref):
        if not np.array_equal(got.view(np.uint32), want.view(np.uint32)):
            raise SmokeFailure(f"sharded row of source {s} differs from the "
                               "one-device solve")
    stats = _route_counts(reqs)
    stats["wave_s"] = wave_s
    stats["one_device_s"] = reference_s
    stats["peak_bytes_after_serving"] = served_peaks
    return stats


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: static serving path; 4: sharded serving path "
                         "and its one-device comparison only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    overrides = kcfg.env_overrides()
    if overrides:
        print(f"chip_smoke: refusing to run with overrides set: "
              f"{sorted(overrides)}", file=sys.stderr)
        return 2

    cache_dir = use_compile_cache()
    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    info(f"jax {jax.__version__}, jaxlib {_version('jaxlib')}, "
         f"libtpu {_version('libtpu')}")
    info(f"devices: {len(devices)} x {devices[0].device_kind}")
    info(f"compile cache: {cache_dir}")

    t0 = timer.now()
    g = grid_road(*GRID, seed=args.seed)
    info(f"graph grid_road{GRID}: n={g.n}, arcs={int(g.src.shape[0])}, "
         f"built in {timer.now() - t0} s")
    used = devices[:args.chips]
    if args.chips == 1:
        stats = serve_and_check(g, make_traffic(g.n, args.seed))
    else:
        stats = serve_sharded_and_check(
            g, make_traffic(g.n, args.seed, full_only=True,
                            sources=SHARDED_SOURCES), used)
    wave_s = stats.pop("wave_s")
    info(f"routes: {stats}")
    info(f"wall seconds per wave: {wave_s}")
    info(f"backend compile seconds: {compile_s[0]}")
    for d in used:
        info(f"device {d.id} peak_bytes_in_use: {_peak_bytes(d)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
