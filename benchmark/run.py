"""One run of one cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the contract's result as the last line of standard output, and the
numbers compared beside their limits as the last lines of standard error.
Exits non-zero, with no result, where JAX finds no accelerator or fewer
chips than the cell asks for, or where the program is not in the tree.
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
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--keep_trace", default="", help=(
        "directory to leave the profiler capture in (default: under "
        ".data/, overwritten by the next traced run)"))
    args = p.parse_args(argv)

    from benchmark import harness

    try:
        result = harness.run_cell(
            args.manifest, args.workload, args.seed, args.seconds,
            bool(args.trace), T_START, keep_trace=args.keep_trace or None,
        )
    except harness.NoChip as e:
        print(f"benchmark: {e}; no result", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for entry in result.pop("stall_journal", ()):
        print("stall " + json.dumps(entry), file=sys.stderr)
    for name, row in result["compared"].items():
        print(f"compared {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (prefetch workers, samplers) must not
    # hold the exit: everything the run produced is printed by now
    os._exit(code)
