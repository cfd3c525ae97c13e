"""Readings the limits of a cell's compared numbers are set from.

    python benchmark/calibrate.py --workload <cell> --seeds 12 --first_seed 1000

One process, one cell's set-up, many seeds: for each seed the program's
first three steps through ``train()`` (the timed path's own call and
feed, no window) against the plain reference, the control (the reference
in bfloat16 in the program's place) and the planted faults. Prints one
JSON line a seed. Not part of a benchmark run.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first_seed", type=int, default=1000)
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = p.parse_args(argv)

    from benchmark import check, harness

    prep = harness.Prepared(args.manifest, args.workload, T_START)
    limits = prep.cfg["limits"]
    for i in range(args.seeds):
        # large seeds too: the driver's pass 2**31
        seed = args.first_seed + i * 178_956_971
        t0 = time.time()
        hook = prep.drive(seed, 0.0, first_steps_only=True)
        numbers = prep.compare(hook)
        ok, _ = check.verdict(numbers, limits)
        line = {"seed": seed, "correct": ok, "program": numbers}
        line.update(harness.calibration_numbers(prep, hook))
        line["seconds"] = round(time.time() - t0, 2)
        print(json.dumps(line), flush=True)
    prep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
