"""Traced runs of a cell, read for the program's own spans and scopes.

    python3 tests/perfbench/run_program_trace.py --workload NAME --seconds S SEED...

Each seed runs the cell as ``perfbench/run.py --trace 1`` does, and keeps
what the harness's ``perfbench/program_trace.py`` read of the trace before
the harness deleted it. It
prints one JSON line per seed: the cell's traced result (per-layer metrics,
``correct``), the queries answered per second of the traced window, and the
program's numbers (device time by ``sssp.*`` scope, idle time by innermost
``serving.*`` span, host time per round, chunk gaps). Last it prints the
host cost of one ``Tracer.span`` with no trace active. Run it on one chip.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SPAN_COST_CALLS = 200_000


def span_cost_us() -> float:
    """Host microseconds of one disabled-tracer span: the profiler
    annotation alone, with no trace being captured."""
    from repro.obs import NULL_TRACER, timer

    t0 = timer.now()
    for _ in range(SPAN_COST_CALLS):
        with NULL_TRACER.span("serving.round"):
            pass
    return 1e6 * (timer.now() - t0) / SPAN_COST_CALLS


def main(argv=None) -> int:
    import jax

    from perfbench import program_trace
    from perfbench.peaks import PEAKS
    from perfbench.run import TRACE_DIR, NoChip, check_machine, run_cell
    from perfbench.spec import load_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = jax.devices()
    try:
        check_machine(cell, devices, PEAKS)
    except NoChip as e:
        print(f"run_program_trace: {e}", file=sys.stderr)
        return 2
    read_file = program_trace.read_file
    read = {}

    def read_and_keep(path, *a, **kw):
        read["program"] = read_file(path, *a, **kw)
        with open(path, "rb") as f:
            meta = program_trace.op_metadata(f.read())
        read["op_metadata"] = {
            plane: {"entries": len(e),
                    "with_tf_op": sum(t is not None for _, _, t in e)}
            for plane, e in meta.items()}
        return read["program"]

    program_trace.read_file = read_and_keep
    for seed in args.seeds:
        result, _, info = run_cell(
            cell, seed, args.seconds, True, time.perf_counter(),
            PEAKS[devices[0].device_kind],
            trace_dir=TRACE_DIR / f"{args.workload}-{seed}")
        pt = read.pop("program")
        trips = info["trips"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": result["correct"],
            "traced_queries_per_s": info["answered_in_window"]
            / info["window_s"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "program": {
                "relax_device_ms_per_phase":
                    program_trace.relax_device_ms_per_phase(pt, trips),
                "criteria_device_ms_per_phase":
                    program_trace.criteria_device_ms_per_phase(pt, trips),
                "chunk_gap_ms": program_trace.chunk_gap_ms(pt),
                "round_host_ms_per_chunk":
                    program_trace.round_host_ms_per_chunk(pt),
                "round_host_ms_max": program_trace.round_host_ms_max(pt),
                "idle_by_program_span": pt.idle_by_program_span,
                "device_s_by_scope": pt.device_s_by_scope,
                "inherited_s": pt.inherited_s,
                "busy_s": pt.busy_s, "window_s": pt.window_s,
                "step_runs": pt.step_runs, "dispatches": pt.dispatches,
                "rounds": len(pt.round_host_s),
                "launch_s": pt.launch_s, "return_s": pt.return_s,
                "clock_offset_s": pt.clock_offset_s,
                "op_metadata": read.pop("op_metadata"),
                "name_collisions": pt.name_collisions,
            },
            "info": {k: info[k] for k in ("chunks", "trips", "window_s",
                                          "answered_in_window",
                                          "compiles_in_window",
                                          "gc_in_window", "setup_s_at")},
            "breakdown": result.get("breakdown"),
        }), flush=True)
    print(json.dumps({"span_cost_us": span_cost_us(),
                      "device": devices[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
