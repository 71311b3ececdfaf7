"""The four-chip baseline: copies of ``graph500-s16`` at scales 16 and 18,
block-partitioned over four chips and served through ``ShardedBackend``
(layout ``coo``) by the benchmark's own harness. Not cells of the shipped
``BENCHMARK.json``; they sit in ``tests/perfbench/sharded_baseline/``.

    python3 tests/perfbench/run_sharded_baseline.py \\
        --workload g500-s18-coo4-cold --seed N --seconds 51 --trace 0|1

Arguments, output and exit codes are those of ``perfbench/run.py``. It
needs a host with four TPU chips.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from perfbench import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(root=HERE / "sharded_baseline"))
