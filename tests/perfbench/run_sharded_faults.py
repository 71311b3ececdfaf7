"""Runs of the test-only four-chip cell ``tiny-g500-sharded`` on four host
devices: once sound, then once with each fault that a sharded cell can
have planted under the served path. One JSON line per run.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 tests/perfbench/run_sharded_faults.py

``tests/perfbench/test_perfbench_sharded.py`` runs it in a subprocess: the
device count has to be set before JAX is imported.
"""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

FAULTS = ("state_unchanged", "answer_altered", "half_lanes_dropped",
          "exchange_left_out")


def leave_out_exchange():
    """Each chip keeps its own block of the phase's candidate distances and
    receives none of the others' (the step is compiled anew)."""
    import jax

    from repro.core import distributed

    def local_block(contrib, axes, n_loc, schedule):
        idx = jax.lax.axis_index(axes)
        return jax.lax.dynamic_slice_in_dim(contrib, idx * n_loc, n_loc,
                                            axis=1)

    distributed._exchange_min_batch = local_block
    distributed._SHARDED_STEP_CACHE.clear()


def main() -> int:
    from perfbench import serve
    from perfbench.peaks import PEAKS
    from perfbench.run import run_cell
    from perfbench.spec import load_cell
    from test_perfbench_harness import BIG_SEED, DATA, _Broken

    cell = load_cell("tiny-g500-sharded", DATA)
    for mode in ("sound",) + FAULTS:
        seen = []

        def wrap(backend, mode=mode, seen=seen):
            seen.append(backend)
            if mode in ("sound", "exchange_left_out"):
                return backend
            return _Broken(backend, mode)

        if mode == "exchange_left_out":
            leave_out_exchange()
        if mode != "sound":
            serve.DRAIN_LIMIT_S = 1.0
        result, checks, info = run_cell(
            cell, BIG_SEED, 0.5, False, time.perf_counter(),
            PEAKS["TPU v5 lite"], wrap_backend=wrap)
        inner = seen[0]
        print(json.dumps({
            "mode": mode, "correct": result["correct"], "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "backend": type(inner).__name__,
            "mesh_devices": int(inner.mesh.devices.size),
            "chips": info["chips"],
            "memory_peak_bytes_by_chip": info["memory_peak_bytes_by_chip"],
            "compiles_in_window": info["compiles_in_window"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
