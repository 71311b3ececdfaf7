"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

The run builds the cell's graph on the host, its vertices relabelled by
``--seed`` (``perfbench/graphs.py``), serves it through the program's
``ContinuousBatcher`` over the backend that the cell's ``chips`` picks
(:func:`make_backend`), warms the cell's programs, offers the cell's
traffic for ``--seconds`` of wall time, waits for the answers due, and
compares every answer with the benchmark's own reference. ``--trace 1``
profiles the window and reports the cell's per-layer metrics; ``--trace 0``
its end-to-end metrics; per-layer readers take the device's ops from the
trace and the program's own spans and scopes from it too
(``perfbench/program_trace.py``).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit).
The run exits non-zero without that line when JAX finds no TPU or fewer
chips than the cell asks for, when the device kind has no entry in the
peaks table, or when a ``REPRO_*`` override is set.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench.spec import load_cell  # noqa: E402

TRACE_DIR = ROOT / ".perfbench_traces"


class NoChip(RuntimeError):
    """The machine cannot run this cell."""


def check_machine(cell, devices, peaks) -> None:
    overrides = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if overrides:
        raise NoChip(f"refusing to run with overrides set: {overrides}")
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips; JAX found "
                     f"{len(devices)}")
    if devices[0].device_kind not in peaks:
        raise NoChip(f"no peaks for device kind {devices[0].device_kind!r}")


# What ShardedBackend serves of a configuration key its constructor does not
# take, and why another value is refused. Once it takes the key, the
# configuration's value goes through to it.
SHARDED_FIXED = {
    "layout": ("coo", "ShardedBackend serves its COO push with one exchange "
                      "per phase, layout 'coo', only"),
    "point_queries": (False, "ShardedBackend does not support s->t target "
                             "lanes; serve point queries through a "
                             "point-capable StaticBackend"),
}
SHARDED_AXIS = "v"


def make_backend(g, cfg, chips: int):
    """The cell's backend over graph ``g``: ``StaticBackend`` on one chip;
    on more, ``ShardedBackend`` over a 1-D mesh of the first ``chips``
    devices. The configuration's ``layout``, ``criterion`` and
    ``point_queries`` reach either unchanged."""
    import jax

    from repro.serving import ShardedBackend, StaticBackend

    kw = {"layout": cfg["layout"], "criterion": cfg["criterion"],
          "point_queries": bool(cfg["point_queries"])}
    if chips == 1:
        return StaticBackend(g, **kw)
    takes = inspect.signature(ShardedBackend).parameters
    for key, (only, why) in SHARDED_FIXED.items():
        if key not in takes and kw.pop(key) != only:
            raise ValueError(f"{why}; the configuration asks for "
                             f"{key}={cfg[key]!r}")
    mesh = jax.make_mesh((chips,), (SHARDED_AXIS,),
                         devices=jax.devices()[:chips])
    return ShardedBackend(g, mesh, (SHARDED_AXIS,), **kw)


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             peak: dict, trace_dir: Path | None = None, wrap_backend=None,
             substitute=None):
    """One run of ``cell``; returns (result, checks, info) dicts.

    A run passes neither hook. ``wrap_backend(backend)`` lets a test put a
    broken backend under the served path; ``substitute(answers, ref)``
    replaces the delivered answers before the comparison, which is how the
    control puts the reference in the program's place."""
    import jax
    import numpy as np

    from perfbench import graphs, layers, program_trace, reference, serve
    from perfbench import trace as trace_mod
    from perfbench.traffic import Traffic
    from repro.compile_cache import use_compile_cache
    from repro.core.graph import from_coo
    from repro.obs import timer
    from repro.serving import ContinuousBatcher, DistCache

    use_compile_cache()
    # every program of the run, however quick to compile, is kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = [0]

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    cfg = cell.config
    t_imports = timer.now() - t_start
    src, dst, w, n, labels = graphs.build(cfg, seed)
    g = from_coo(src, dst, w, n)
    inner = make_backend(g, cfg, cell.chips)
    if wrap_backend is not None:
        inner = wrap_backend(inner)
    backend = serve.TimedBackend(inner)
    traffic = Traffic(cell.traffic, n, np.bincount(src, minlength=n),
                      cfg["structure_seed"], labels)
    t_graph = timer.now() - t_start

    def make_server(**kw):
        return ContinuousBatcher(
            g, lanes=int(cfg["lanes"]), backend=backend,
            cache=DistCache(capacity=int(cell.traffic["cache_rows"])),
            point_queries=bool(cfg["point_queries"]), retain_completed=0,
            **kw)

    serve.warm_up(make_server)
    server = make_server()
    t_warm = timer.now() - t_start
    # what set-up built lives to the end of the run: keep it out of the
    # collector's full scans, which would stall the window's host loop
    gc.collect()
    gc.freeze()
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    compiles_before = compiles[0]
    gc_pauses = serve.GcPauses()
    setup_s = timer.now() - t_start
    with gc_pauses:
        window = serve.run_window(server, backend, traffic, seconds,
                                  timer.now)
    if trace:
        jax.profiler.stop_trace()
    serve.drain(server, window, timer.now)
    drain_end = timer.now()
    gc.unfreeze()
    compiles_in_window = compiles[0] - compiles_before
    used = jax.devices()[:cell.chips]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
    answers = [(r.source, r.target, r.dist) for r in window.requests
               if r.outcome == "ok"]
    server.state = None  # free the program's device state before the check
    del server
    t_ref = timer.now()
    ref = reference.Reference(src, dst, w, n)
    if substitute is not None:
        answers = substitute(answers, ref)
    rows64 = ref.rows(s for s, _, _ in answers)
    checks = reference.compare(answers, ref, rows64)
    checks["unanswered"] = window.unanswered
    ref_s = timer.now() - t_ref
    summary = program = program_trace_s = trace_read_s = None
    if trace:
        t_read = timer.now()
        xplane = program_trace.load(trace_mod.find_xplane(trace_dir))
        summary = trace_mod.summarize_file(xplane, chips=cell.chips)
        t_program = timer.now()
        program = program_trace.read_file(xplane, chips=cell.chips)
        program_trace_s = timer.now() - t_program
        trace_read_s = timer.now() - t_read
        del xplane
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = layers.Run(setup_s=setup_s, window=window, drain_end=drain_end,
                     n=n, lanes=int(cfg["lanes"]), trace=summary,
                     ref_rows=rows64, reference=ref, peak=peak,
                     chips=cell.chips, program=program)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    limits = cfg["limits"]
    correct = all(checks[k] <= limits[k] for k in limits)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": max(mem)}
    result = {"correct": correct, "attempted": len(window.requests),
              "failed": window.unanswered, "metrics": metrics,
              "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    lat = window.lateness_s
    info = {
        "graph": {"n": n, "arcs": int(src.size)},
        "chips": cell.chips,
        "memory_peak_bytes_by_chip": mem,
        "setup_s_at": {"imports": t_imports, "graph_and_layout": t_graph,
                       "warm_up": t_warm, "window_start": setup_s},
        "window_s": window.seconds,
        "answered_in_window": window.answered_in_window,
        "chunks": window.chunks, "trips": window.trips,
        "compiles_in_window": compiles_in_window,
        "generator_late_s": {"mean": float(np.mean(lat)) if lat else 0.0,
                             "max": float(np.max(lat)) if lat else 0.0},
        "gc_in_window": gc_pauses.summary(),
        "reference_s": ref_s,
        "program_trace_s": program_trace_s,
        "trace_read_s": trace_read_s,
        "distinct_rows_checked": len(rows64),
    }
    return result, checks, info


def main(argv=None, root: Path = ROOT) -> int:
    """The benchmark's command, over the ``BENCHMARK.json`` under ``root``
    (a test's own tree may hold cells that the shipped one has not)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root)

    import jax

    from perfbench.peaks import PEAKS

    t_jax = time.perf_counter() - T_START
    devices = jax.devices()
    t_devices = time.perf_counter() - T_START
    try:
        check_machine(cell, devices, PEAKS)
    except NoChip as e:
        print(f"perfbench: {e}; nothing was run", file=sys.stderr)
        return 2
    result, checks, info = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_START,
        PEAKS[devices[0].device_kind],
        trace_dir=TRACE_DIR / f"{args.workload}-{args.seed}")
    info["setup_s_at"] = {"jax_imported": t_jax, "devices_found": t_devices,
                          **info["setup_s_at"]}
    print(f"info: {json.dumps(info)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
