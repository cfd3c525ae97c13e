"""On-chip batch-scaling sweep for the device-sampling train step.

PERF.md establishes that the step at reference-recipe dims is
LATENCY-bound (~0.13 ms empty-scan floor, MFU ~1%). This sweep measures
the complement: where the batch-size curve leaves the latency corner
and what MFU/HBM utilization the design reaches when allowed to batch
up — the throughput-optimal operating point (the reference's recipes
fix batch at 512/1000 because its host sampler is the bottleneck;
reference examples/sage.py:80-98, sage_reddit.py:80-97 — on TPU the
sampler is on-device, so the operating point is free to move).

One JSON line per (config, batch) point with step wall ms, edges/s, and
the XLA cost-model roofline (MFU / HBM util) — appended to
.bench_bank/sweep.jsonl the moment each point completes, so a config
killed at its deadline keeps every completed point. Each config runs in
its own subprocess, one after another (a chip belongs to one process;
the parent never touches the backend), on the platform the environment
says: nothing falls back to CPU, and a failed point or a failed kernel
A/B fails the exit code.

    python scripts/batch_sweep.py [--configs ppi,reddit]
        [--batches 512,2048,8192,32768]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_spec = importlib.util.spec_from_file_location(
    "bench_lib", os.path.join(_REPO, "bench.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def sweep_config(name: str, batches, out_path: str) -> None:
    """All batch points for one config in this process (the graph build
    and feature-table upload are shared across points; each point's line
    is banked the moment it exists)."""
    import jax

    import euler_tpu
    from euler_tpu import train as train_lib
    from euler_tpu.datasets import build_synthetic
    from euler_tpu.models import SupervisedGraphSage

    def _bank_line(line: dict) -> None:
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)

    cfg = bench.CONFIGS[name]
    platform = jax.devices()[0].platform
    if cfg.get("powerlaw"):
        # heavy-tail config sweeps only against a FINISHED cache: the
        # ~2 GB build belongs to scripts/reddit_heavytail.py --full
        from euler_tpu.datasets import (
            REDDIT_HEAVYTAIL, heavytail_cache_dir, powerlaw_cache_ready,
        )

        cfg = {**cfg, **REDDIT_HEAVYTAIL}
        cache = heavytail_cache_dir()
        if not powerlaw_cache_ready(cache, **REDDIT_HEAVYTAIL):
            raise RuntimeError(
                "heavytail cache absent/stale; build with "
                "scripts/reddit_heavytail.py --full first"
            )
    else:
        cache = os.environ.get(
            "EULER_TPU_BENCH_CACHE", "/tmp/euler_tpu_bench"
        ) + "_" + cfg.get("cache_as", name)
        build_synthetic(
            cache,
            num_nodes=cfg["num_nodes"],
            avg_degree=cfg["avg_degree"],
            feature_dim=cfg["feature_dim"],
            label_dim=cfg["label_dim"],
            multilabel=cfg["multilabel"],
        )
    graph = euler_tpu.Graph(directory=cache)
    fanouts = list(cfg["fanouts"])
    edges_per_root = fanouts[0] + fanouts[0] * (
        fanouts[1] if len(fanouts) > 1 else 0
    )
    opt = train_lib.get_optimizer("adam", cfg["lr"])

    device_kind = jax.devices()[0].device_kind
    for batch in batches:
        point = {"config": name, "batch": int(batch),
                 "fanouts": fanouts, "dim": cfg["dim"],
                 "platform": platform, "device_kind": device_kind}
        model = SupervisedGraphSage(
            label_idx=0,
            label_dim=cfg["label_dim"],
            metapath=[[0]] * len(fanouts),
            fanouts=fanouts,
            dim=cfg["dim"],
            feature_idx=1,
            feature_dim=cfg["feature_dim"],
            max_id=cfg["num_nodes"] - 1,
            device_features=True,
            device_sampling=True,
            feature_dtype=cfg.get("feature_dtype"),
        )
        if cfg.get("alias_sampling"):
            model.set_sampling_options(alias=True)
        state = model.init_state(
            jax.random.PRNGKey(0), graph,
            graph.sample_node(batch, -1), opt,
        )
        chunk_steps = 50
        scan = jax.jit(
            train_lib.make_scan_train(model, opt, chunk_steps, batch),
            donate_argnums=(0,),
        )
        point["pallas_kernel"] = bench.kernel_in_program(scan, state, 0)
        state, l0 = scan(state, 0)  # compile + warmup
        jax.block_until_ready(l0)
        chunks = 6
        t0 = time.perf_counter()
        last = None
        for c in range(1, chunks + 1):
            state, last = scan(state, c)
        jax.block_until_ready(last)
        dt = time.perf_counter() - t0
        step_ms = dt / (chunks * chunk_steps) * 1e3
        bogus = bench._implausible(step_ms, last)
        if bogus:
            raise RuntimeError(
                f"{name} batch {batch}: measurement rejected: {bogus}"
            )
        sps = chunks * chunk_steps / dt
        point["step_wall_ms"] = round(step_ms, 4)
        point["steps_per_sec"] = round(sps, 2)
        point["edges_per_sec"] = round(edges_per_root * batch * sps, 1)
        point["final_loss"] = round(float(np.asarray(last)[-1]), 4)
        point["roofline"] = bench._roofline(
            scan.lower(state, 0).compile(), step_ms
        )
        del state
        if point["pallas_kernel"]:
            # per-point kernel A/B: does the fused draw still matter
            # off the latency corner? Shared helper (bench.kernel_ab)
            # so the env-toggle protocol cannot fork; the main state
            # is freed first — two full states resident would double
            # peak HBM at the big batch points.
            point.update(bench.kernel_ab(
                model, opt, graph, batch, chunk_steps,
                point["steps_per_sec"], chunks=2,
            ))
        _bank_line(point)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--configs", default="ppi,reddit")
    ap.add_argument("--batches", default="512,2048,8192,32768")
    ap.add_argument("--out", default=os.path.join(
        _REPO, ".bench_bank", "sweep.jsonl"
    ))
    ap.add_argument("--run-one", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-config subprocess deadline (s). Default: "
                    "1600 s (900 s plus 700 s for the per-point kernel "
                    "A/B's second init+compile); reddit_heavytail "
                    "2400 s — one alias upload plus a compile per batch "
                    "point, no A/B on the alias path")
    args = ap.parse_args()
    batches = [int(b) for b in args.batches.split(",") if b.strip()]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    if args.run_one:
        from euler_tpu.parallel import enable_compile_cache

        enable_compile_cache()
        sweep_config(args.run_one, batches, args.out)
        return

    import signal
    import subprocess

    caps = {"reddit_heavytail": 2400.0}
    failed = False
    for name in [n.strip() for n in args.configs.split(",") if n.strip()]:
        deadline = (
            args.deadline if args.deadline is not None
            else caps.get(name, 1600.0)
        )
        cmd = [
            sys.executable, "-u", os.path.abspath(__file__),
            "--run-one", name, "--batches", args.batches,
            "--out", args.out,
        ]
        proc = subprocess.Popen(cmd, start_new_session=True)
        try:
            failed |= proc.wait(timeout=deadline) != 0
        except subprocess.TimeoutExpired:
            failed = True
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                proc.kill()
            proc.wait()
            print(json.dumps({
                "config": name,
                "error": f"sweep subprocess killed at {deadline:.0f}s; "
                "completed points are banked",
            }), flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
