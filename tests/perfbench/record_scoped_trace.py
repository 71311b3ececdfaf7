"""Record the small TPU trace that the program-trace tests read.

    python3 tests/perfbench/record_scoped_trace.py OUT_DIR

Run on one chip: it serves a scale-11 Graph500 graph through
``ContinuousBatcher`` (4 lanes, the default chunk of 32 phases) over the
degree-sliced layout (so ``sssp.relax`` holds the slice gathers and their
merge) for a few chunks under
``jax.profiler``, with the benchmark's host spans around the program's own
``serving.*`` spans, writes the trace under ``OUT_DIR`` and prints each
plane's lines and event counts and the program's numbers of the window.

Give it an empty ``JAX_COMPILATION_CACHE_DIR`` (or none): the profiler names
an op's scope only for a program compiled in the process that traces it.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out_dir: str) -> int:
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from perfbench import graphs, program_trace, serve, trace
    from repro.core.graph import from_coo
    from repro.serving import ContinuousBatcher, StaticBackend

    cfg = {"generator": "graph500", "structure_seed": 20261016, "scale": 11,
           "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
    src, dst, w, n, _ = graphs.build(cfg, 7)
    g = from_coo(src, dst, w, n)
    backend = serve.TimedBackend(StaticBackend(g, layout="sliced"))
    server = ContinuousBatcher(g, lanes=4, backend=backend)
    roots = [int(v) for v in src[:: max(1, src.size // 8)][:8]]
    server.submit(roots[0])
    server.drain()  # compiles outside the trace
    jax.profiler.start_trace(out_dir)
    with TraceAnnotation("bench.window"):
        for s in roots[1:7]:
            with TraceAnnotation("bench.arrivals"):
                server.submit(s)
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
    print(program_trace.read_file(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
