"""The control of the comparison that decides ``correct``, at a cell's size.

    python3 tests/perfbench/run_control.py --workload NAME --seconds S SEED...

Each seed runs the cell as ``perfbench/run.py`` does, then puts the
reference computed in bfloat16 (every sum rounded to bfloat16, the
precision below the configuration's float32) in the program's place: every
answer of the window is replaced by the control's row for its source before
the comparison. It prints, per seed, the compared numbers beside their
limits; the control has to come out as not correct. Run it on the chip
(it needs one, like the benchmark); the benchmark's own runs never run it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def bf16_answers(answers, ref):
    """Every answer replaced by the control row of its source."""
    rows = ref.bf16_rows(s for s, _, _ in answers)
    return [(s, t, rows[s]) for s, t, _ in answers]


def main(argv=None) -> int:
    import jax

    from perfbench.peaks import PEAKS
    from perfbench.run import NoChip, check_machine, run_cell
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
        print(f"run_control: {e}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        result, _, info = run_cell(
            cell, seed, args.seconds, False, time.perf_counter(),
            PEAKS[devices[0].device_kind], substitute=bf16_answers)
        print(json.dumps({"control": "bf16", "workload": args.workload,
                          "seed": seed, "correct": result["correct"],
                          "checks": result["checks"],
                          "rows": info["distinct_rows_checked"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
