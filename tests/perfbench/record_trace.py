"""Record the small TPU trace that the trace-reduction tests read.

    python3 tests/perfbench/record_trace.py OUT_DIR

Run on one chip: it serves a 16 x 16 grid through ``ContinuousBatcher``
for a few chunks under ``jax.profiler`` with the benchmark's host spans,
writes the trace under ``OUT_DIR`` and prints each plane's lines and event
counts, so the layout the reduction relies on can be checked by eye.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out_dir: str) -> int:
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from perfbench import graphs, serve, trace
    from repro.core.graph import from_coo
    from repro.serving import ContinuousBatcher, StaticBackend

    cfg = {"generator": "grid_road", "structure_seed": 7, "side": 16,
           "diag_frac": 0.05}
    src, dst, w, n, _ = graphs.build(cfg, 7)
    g = from_coo(src, dst, w, n)
    backend = serve.TimedBackend(StaticBackend(g))
    server = ContinuousBatcher(g, lanes=4, backend=backend, phases_per_step=8)
    server.submit(0)
    server.drain()  # compiles outside the trace
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
    print(path, trace.summarize_file(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
