"""Time the benchmark's reference on the host: ``Reference`` built over a
Graph500 graph of each scale asked for, then ``rows`` of uniform roots of
nonzero degree, solved in-process and in the worker pool. One JSON line per
scale. Nothing here touches JAX or a device.

    python3 tests/perfbench/time_reference.py --scales 16 18 20 --roots 32

``perfbench/reference.py``'s ``POOL_ARCS`` is set from where the pool
starts to win.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import graphs, reference  # noqa: E402

CONFIG = ROOT / "perfbench" / "configs" / "graph500-s16.json"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scales", type=int, nargs="+", default=[16, 18, 20])
    ap.add_argument("--roots", type=int, default=32)
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    args = ap.parse_args(argv)
    base = json.loads(CONFIG.read_text())
    pool_arcs = reference.POOL_ARCS
    for scale in args.scales:
        src, dst, w, n, _ = graphs.build({**base, "scale": scale}, args.seed)
        t = time.perf_counter()
        ref = reference.Reference(src, dst, w, n)
        build_s = time.perf_counter() - t
        rng = graphs.rng_for(args.seed, 7)
        roots = rng.choice(np.flatnonzero(np.bincount(src, minlength=n)),
                           args.roots, replace=False)
        times, rows = {}, {}
        for how, arcs in (("in_process", 1 << 62), ("pool", 0)):
            reference.POOL_ARCS = arcs
            t = time.perf_counter()
            rows[how] = ref.rows(roots)
            times[how] = time.perf_counter() - t
        reference.POOL_ARCS = pool_arcs
        same = all(np.array_equal(rows["pool"][s], rows["in_process"][s])
                   for s in rows["pool"])
        print(json.dumps({"scale": scale, "arcs": int(ref.csr.nnz),
                          "roots": args.roots, "reference_s": build_s,
                          "rows_in_process_s": times["in_process"],
                          "rows_pool_s": times["pool"],
                          "workers": reference.ROW_WORKERS,
                          "rows_identical": same}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
