"""Time the forms of one bucket's gather-min on the benchmark's graph.

Each bucket of the degree-sliced ELL of Graph500 scale 16 (the graph of
``perfbench/configs/graph500-s16.json``) is gathered and row-min'd for 8
serving lanes in two forms:

- ``whole``: one gather of the bucket's R·D slots, then the row-min
  (``kref.ell_gather_min_batch_ref``, the form before row tiles);
- ``tiles_<k>K``: ``ops._gather_min_tiled`` with its slab bytes set to k·1024
  slots of 512 B (8 lanes padded to 128) for the compile only, a loop over
  row tiles that each gather and row-min.

(A third, lane-dense form, one scalar gather from the flattened source with
indices ``cols + b·(n+1)``, ran 20-36x slower than the whole form on a
TPU v5e and was dropped.)

Every form's rows are checked bit for bit against ``whole``. Times are the
median, over repeats, of the host clock over back-to-back calls that end in
``block_until_ready``; on the chip they are device time plus a dispatch
each. Also timed: the whole
``gather_min_batch_sliced`` (every bucket and the merge) at each tile size.

    PYTHONPATH=src python -m benchmarks.bench_gather_forms \
        [--out chiprun_out/gather_forms.json]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from perfbench.graphs import build
from repro.core.graph import from_coo, to_ell_in_sliced
from repro.kernels import ops
from repro.kernels import ref as kref

ROOT = Path(__file__).resolve().parents[1]
TILES_K = (8, 16, 32, 64)
LANES = 8
SLOT_BYTES = 128 * 4  # 8 lanes padded to 128


def graph500_s16():
    cfg = json.loads(
        (ROOT / "perfbench" / "configs" / "graph500-s16.json").read_text())
    src, dst, w, n, _ = build(cfg, seed=0)
    return from_coo(src, dst, w, n)


def tiled_gather_min(vecs, cols, ws):
    return ops._gather_min_tiled(ops._pad_idx(vecs, vecs.shape[-1] + 1),
                                 cols, ws)


def compiled(fn, args, tile_slots=None):
    # a new function each time: JAX caches a trace by function, and the
    # patched constant is read only while tracing
    def call(*a):
        return fn(*a)

    if tile_slots is None:
        return jax.jit(call).lower(*args).compile()
    with mock.patch.object(ops, "_TILE_SLAB_BYTES", tile_slots * SLOT_BYTES):
        return jax.jit(call).lower(*args).compile()


def time_ms(fn, args, calls: int, reps: int) -> float:
    def back_to_back():
        out = None
        for _ in range(calls):
            out = fn(*args)
        return jax.block_until_ready(out)

    back_to_back()
    return timed(back_to_back, repeats=reps)[0] / calls * 1e3


def run(calls: int, reps: int, seed: int):
    g = graph500_s16()
    ell = to_ell_in_sliced(g)
    rng = np.random.default_rng(seed)
    d = rng.random((1, LANES, g.n), dtype=np.float32)
    d[rng.random(d.shape) < 0.5] = np.inf
    vecs = jnp.asarray(d)
    rows = []
    for s in ell.slices:
        r, width = s.cols.shape
        args = (vecs, s.cols, s.ws)
        want = np.asarray(kref.ell_gather_min_batch_ref(*args))
        forms = {"whole": compiled(kref.ell_gather_min_batch_ref, args)}
        for k in TILES_K:
            forms[f"tiles_{k}K"] = compiled(tiled_gather_min, args, k * 1024)
        for name, fn in forms.items():
            got = np.asarray(fn(*args))
            row = {"bucket": width, "rows": r, "slots": r * width,
                   "form": name, "bit_identical": bool(np.array_equal(got, want)),
                   "ms": time_ms(fn, args, calls, reps),
                   "temp_bytes": fn.memory_analysis().temp_size_in_bytes}
            rows.append(row)
            print(json.dumps(row), flush=True)

    def sliced(vecs, ell):
        return ops.gather_min_batch_sliced(vecs, ell)

    args = (vecs, ell)
    for k in (None,) + TILES_K:
        slots = 2**40 if k is None else k * 1024
        fn = compiled(sliced, args, slots)
        row = {"bucket": "all+merge", "form": "whole" if k is None else f"tiles_{k}K",
               "ms": time_ms(fn, args, calls, reps),
               "temp_bytes": fn.memory_analysis().temp_size_in_bytes}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147483901)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind}))
    rows = run(a.calls, a.reps, a.seed)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(
            {"device_kind": dev.device_kind, "rows": rows}, indent=1))


if __name__ == "__main__":
    main()
