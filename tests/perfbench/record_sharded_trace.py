"""Record the small four-chip TPU trace that the collective tests read.

    python3 tests/perfbench/record_sharded_trace.py OUT_DIR

Run on a host with four chips: it serves the test-only cell
``tiny-g500-sharded`` (a scale-8 Graph500 graph block-partitioned over the
four chips, layout ``coo``) through ``ContinuousBatcher`` over the backend
that the harness builds for it, for a few chunks under ``jax.profiler``
with the benchmark's host spans. It writes the trace under ``OUT_DIR`` and
prints each plane's lines and event counts, the collective ops found, and
the summary, so the layout the reduction relies on can be checked by eye.
"""
import sys
from collections import Counter
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out_dir: str) -> int:
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from perfbench import graphs, serve, trace
    from perfbench.run import make_backend
    from perfbench.spec import load_cell
    from repro.core.graph import from_coo
    from repro.serving import ContinuousBatcher

    cell = load_cell("tiny-g500-sharded", HERE / "data")
    if len(jax.devices()) < cell.chips:
        print(f"needs {cell.chips} devices; JAX found {len(jax.devices())}",
              file=sys.stderr)
        return 2
    src, dst, w, n, _ = graphs.build(cell.config, 7)
    g = from_coo(src, dst, w, n)
    backend = serve.TimedBackend(make_backend(g, cell.config, cell.chips))
    server = ContinuousBatcher(g, lanes=int(cell.config["lanes"]),
                               backend=backend, phases_per_step=8)
    for s in (0, 1):  # compiles outside the trace, fresh and sharded state
        server.submit(s)
        server.step()
    server.drain()
    jax.profiler.start_trace(out_dir)
    with TraceAnnotation("bench.window"):
        for s in (1, 2, 3, 4, 5, 6):
            with TraceAnnotation("bench.arrivals"):
                server.submit(s * 37 % n)
            with TraceAnnotation("bench.step"):
                server.step()
        with TraceAnnotation("bench.drain"):
            server.drain()
    jax.profiler.stop_trace()
    path = trace.find_xplane(out_dir)
    for plane in ProfileData.from_file(path).planes:
        print(plane.name, [(ln.name, len(list(ln.events)))
                           for ln in plane.lines])
    devices, _ = trace.read_xplane(path)
    for dev, events in sorted(devices.items()):
        kinds = Counter(trace.collective_kind(name) for _, _, name in events)
        kinds.pop(None, None)
        print(dev, dict(kinds))
    print(path, Path(path).stat().st_size, "bytes")
    print(trace.summarize_file(path, chips=cell.chips))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
