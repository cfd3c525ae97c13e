"""The store mechanism's planted faults, read by a cell's own numbers.

    python benchmark/configs/scalable_sage_reddit_faults.py \
        --workload reddit_scalable_device_train

``benchmark/calibrate.py``'s loop (one process, one set-up, many seeds;
for each seed the first three steps of the timed ``train()`` call
against the plain reference, the bfloat16 control and the half batch)
with the faults a historical store can have and a GraphSAGE step cannot:
for each name of the reference's ``FAULTS`` the reference with that one
rule broken, put in the program's place as the control is, against the
same two references. One JSON line a seed; ``twice`` counts the roots a
captured step drew more than once (``first_duplicate_kept`` changes
nothing where it is 0). Not part of a benchmark run.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
# calibrate.py's stride from 1000 and from just under 2**31: the driver's
# seeds pass 2**31
SEEDS = [first + i * 178_956_971
         for first in (1000, 2_147_483_000) for i in range(6)]


class _Planted:
    """The reference module with ``fault`` in every ``train_steps``."""

    def __init__(self, ref, fault):
        self._ref, self._fault = ref, fault

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def train_steps(self, *args, **kw):
        return self._ref.train_steps(*args, fault=self._fault, **kw)


def fault_numbers(prep, hook) -> dict:
    """fault -> the cell's numbers with the faulty reference in the
    program's place. After ``prep.compare(hook)``, which leaves the two
    references beside what was captured."""
    import jax.numpy as jnp

    from benchmark import check

    return {
        "fault_" + fault: check.compare(
            prep.cfg, prep.spec, _Planted(prep.ref, fault), hook.captured,
            dtype=jnp.float32)
        for fault in prep.ref.FAULTS
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default=",".join(map(str, SEEDS)),
                   help="by value, comma-separated")
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = p.parse_args(argv)

    from benchmark import check, harness

    prep = harness.Prepared(args.manifest, args.workload, T_START)
    limits = prep.cfg["limits"]
    for seed in map(int, args.seeds.split(",")):
        t0 = time.time()
        hook = prep.drive(seed, 0.0, first_steps_only=True)
        numbers = prep.compare(hook)
        line = {"seed": seed, "correct": check.verdict(numbers, limits)[0],
                "twice": len(hook.captured["end"][prep.ref.TWICE]),
                "program": numbers}
        line.update(harness.calibration_numbers(prep, hook))
        line.update(fault_numbers(prep, hook))
        line["fails"] = {
            k: [n for n, v in check.verdict(line[k], limits)[1].items()
                if not v["value"] <= v["limit"]]
            for k in line if k.startswith(("control_", "fault_"))}
        line["seconds"] = round(time.time() - t0, 2)
        print(json.dumps(line), flush=True)
    prep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
