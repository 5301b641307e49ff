"""Run one cell of the benchmark on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``knnbench/harness.py``).  With
``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy time over the traced
window and the trace's breakdown.  The numbers the run compared, each
beside its limit, are the last lines on standard error and the last key of
the line.  Without a TPU, or with fewer chips than the cell asks for, the
run exits with code 2 and prints no line; it never measures on the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    from knnbench import harness

    cell = harness.load_cell(args.workload, ROOT)
    import repro.api  # noqa: F401  (the system under test must be there)

    harness.use_compile_cache(ROOT)
    try:
        devices = harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices=devices, t_start=T_START)
    print(harness.finish(cell, out, devices, bool(args.trace)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
