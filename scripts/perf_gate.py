#!/usr/bin/env python3
"""Control-flow regression gate over the CPU smoke benches.

Runs the cheap smoke benches (`bench.py --smoke`,
`scripts/remote_bench.py --smoke`) with JAX_PLATFORMS=cpu, compares
each against the BEST prior smoke round recorded in
``evidence/perf_gate/history.jsonl``, and prints a verdict; every run
appends to that history. These are XLA-CPU numbers on a toy graph: they
catch a host-path or remote-client slowdown in this container and say
nothing about the chip.

Warn-only by default — verify.sh calls it so a silent throughput
regression is at least SHOUTED before it reaches a PR — `--strict`
exits nonzero on a regression beyond ``--tolerance`` (default 25%,
sized for this container's run-to-run noise).

Usage:
    python scripts/perf_gate.py                 # run smokes + verdict
    python scripts/perf_gate.py --strict        # same, exit 1 on regress
    python scripts/perf_gate.py --skip-bench    # remote smoke only
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HISTORY = os.path.join(REPO, "evidence", "perf_gate", "history.jsonl")


def _last_json_line(text: str) -> dict | None:
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def run_smoke_bench(timeout_s: float) -> dict | None:
    """bench.py --smoke headline (tiny host-path-only config)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
            capture_output=True, text=True, timeout=timeout_s, env=env,
            cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        print(f"perf_gate: bench.py --smoke timed out ({timeout_s}s)",
              file=sys.stderr)
        return None
    return _last_json_line(proc.stdout)


def run_smoke_remote(timeout_s: float) -> dict | None:
    """remote_bench --smoke headline (2-shard remote client path)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "remote_bench.py"), "--smoke"],
            capture_output=True, text=True, timeout=timeout_s, env=env,
            cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        print(f"perf_gate: remote_bench --smoke timed out ({timeout_s}s)",
              file=sys.stderr)
        return None
    return _last_json_line(proc.stdout)


def load_history(path: str = HISTORY) -> list:
    if not os.path.exists(path):
        return []
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    pass
    return rows


def append_history(record: dict, path: str = HISTORY) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")


def verdict(current: dict, history: list, tolerance: float) -> list:
    """Compare {config: edges/s} against the best prior history entry
    per config. Returns [(config, status, detail)], status in
    {"ok", "regression", "baseline"}."""
    out = []
    for config, value in sorted(current.items()):
        if value is None:
            out.append((config, "baseline",
                        "smoke run failed; nothing recorded"))
            continue
        prior = [h["values"].get(config) for h in history
                 if h.get("values", {}).get(config)]
        if not prior:
            out.append((config, "baseline",
                        f"{value:,.0f} edges/s (first smoke round — "
                        "baseline recorded)"))
            continue
        best = max(prior)
        floor = best * (1.0 - tolerance)
        ratio = value / best
        detail = (f"{value:,.0f} edges/s vs best prior {best:,.0f} "
                  f"({ratio:.2f}x, floor {floor:,.0f})")
        out.append((config,
                    "regression" if value < floor else "ok", detail))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on a regression verdict (default: warn)")
    ap.add_argument("--strict-configs", default="", metavar="A,B", help=(
        "enforce (exit 1) regressions only for these config names "
        "(e.g. bench_smoke); others stay warn-only. The verify.sh "
        "middle ground: the host-only bench config is stable enough "
        "to gate on, the remote configs drown in 1-core container "
        "noise"))
    ap.add_argument("--tolerance", type=float, default=0.25, help=(
        "allowed fractional drop below the best prior smoke round "
        "before the verdict says regression (container noise floor)"))
    ap.add_argument("--skip-bench", action="store_true",
                    help="skip bench.py --smoke (remote smoke only)")
    ap.add_argument("--skip-remote", action="store_true",
                    help="skip remote_bench --smoke")
    ap.add_argument("--timeout", type=float, default=420.0,
                    help="per-smoke subprocess timeout, seconds")
    ap.add_argument("--no-record", action="store_true",
                    help="don't append this run to the smoke history")
    ap.add_argument("--history", default=HISTORY, help=argparse.SUPPRESS)
    args = ap.parse_args()

    current: dict = {}
    if not args.skip_bench:
        head = run_smoke_bench(args.timeout)
        current["bench_smoke"] = head.get("value") if head else None
    if not args.skip_remote:
        head = run_smoke_remote(args.timeout)
        current["remote_smoke"] = head.get("value") if head else None
    if not current:
        print("perf_gate: both smokes skipped; nothing to judge")
        return 0

    history = load_history(args.history)
    results = verdict(current, history, args.tolerance)
    if not args.no_record and any(v for v in current.values()):
        append_history(
            {"unix": int(time.time()),
             "values": {k: v for k, v in current.items() if v}},
            args.history,
        )

    strict_configs = {c.strip() for c in args.strict_configs.split(",")
                      if c.strip()}
    print("== perf gate verdict (smoke-to-smoke, "
          f"tolerance {args.tolerance:.0%}) ==")
    regressed = False
    enforced = False
    for config, status, detail in results:
        tag = {"ok": "OK", "regression": "REGRESSION",
               "baseline": "BASELINE"}[status]
        gating = args.strict or config in strict_configs
        print(f"  {config:14s} {tag:10s} {detail}"
              + ("" if gating or status != "regression"
                 else " [warn-only config]"))
        regressed |= status == "regression"
        enforced |= status == "regression" and gating
    if regressed:
        print("perf_gate: REGRESSION "
              + ("(enforced: failing)" if enforced
                 else "(warn-only; pass --strict or --strict-configs "
                      "to enforce)"))
        return 1 if enforced else 0
    print("perf_gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
