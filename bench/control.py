"""Read the comparison's numbers for the program, the witness and the control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 6

For each seed, in one process: the cell's set-up and a short window at its
own load, as a benchmark run makes them; then, on the rows the seed draws,
the comparison's numbers for the program's answers (the lower readings),
for the plain reference on the device in float32 (a second witness, which
must agree with the host brute force) and for the same reference in
bfloat16 put in the program's place (the control, which must fail).  One
line of JSON per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from knnbench import checks, harness

    cell = harness.load_cell(args.workload, ROOT)
    harness.use_compile_cache(ROOT)
    try:
        devices = harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"no control: {e}", file=sys.stderr)
        return 2
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    k = int(cell.config["service"]["k"])
    for seed in (int(s) for s in args.seeds.split(",")):
        driver = cell.driver()
        st = driver.setup(cell, seed, devices, log)
        driver.window(st, args.seconds, lambda n: contextlib.nullcontext())
        driver.release(st)
        out = dict(seed=seed)
        for name, fn in (
                ("program", lambda: checks.compare(
                    driver.answers(st, seed), k)[0]),
                ("witness_f32", lambda: checks.control(
                    driver.answers(st, seed), k, "float32")),
                ("control_bf16", lambda: checks.control(
                    driver.answers(st, seed), k, "bfloat16"))):
            t0 = time.perf_counter()
            got = fn()
            out[name] = {n: v for n, (v, _) in got.items()}
            out[name + "_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
