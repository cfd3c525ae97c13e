#!/usr/bin/env python3
"""Remote-path benchmark: edges/s + counter ledger over a local 2-shard
cluster, before/after the hot-path optimizations.

The ROADMAP's scaling story — shard the graph, serve millions of users —
had no PERF.md row until this script: the single-chip device path is
measured to death while the remote client was never timed at all. This
drives the workload the remote client actually serves in training (a
2-hop fanout + a dense-feature batch over the fanout frontier, the
model.sample shape) against REAL shard services on localhost, twice:

  baseline   coalesce=0, feature_cache_mb=0 — the pre-PR wire shape
             (every duplicate id re-sent, every feature row refetched)
  optimized  defaults — persistent dispatcher + duplicate-id coalescing
             + client-side feature-row cache

and reports edges/s for both plus the counter ledger
(ids_deduped / cache_hits / cache_misses / rpc_chunks, FAULTS.md) and
the ids-on-wire accounting
(ids_on_wire = ids_requested - ids_deduped - cache_hits).

The graph is synthetic power-law (hub-heavy, the Reddit shape): hubs
carry most edge mass, so the fanout frontier is dominated by duplicate
hub ids — exactly the regime the optimizations target. Localhost TCP
understates the win of cutting wire BYTES (loopback bandwidth is free);
the dedup win measured here is mostly serialization + server lookup
work, so treat the edges/s ratio as a floor for real networks.

Usage:
    python scripts/remote_bench.py             # full run, JSON to stdout
    python scripts/remote_bench.py --smoke     # small/fast (verify.sh)

Subprocess shards by default (one OS process per shard, like the chaos
soak) so server CPU is not attributed to the client loop; --inproc uses
in-process services (faster startup, used by --smoke).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NUM_SHARDS = 2


def _launch_shards_subproc(data: str, reg: str):
    """One OS process per shard (the chaos-soak launcher shape)."""
    import socket
    import subprocess

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "euler_tpu.graph.service",
             "--data_dir", data, "--shard_idx", str(s),
             "--shard_num", str(NUM_SHARDS), "--registry", reg],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
        )
        for s in range(NUM_SHARDS)
    ]
    deadline = time.monotonic() + 90.0
    for s in range(NUM_SHARDS):
        while True:
            entry = next(
                (f for f in os.listdir(reg) if f.startswith(f"{s}#")), None
            )
            if entry is not None:
                host, port = entry.split("#", 1)[1].rsplit("_", 1)
                try:
                    with socket.create_connection((host, int(port)), 1.0):
                        break
                except OSError:
                    pass
            if time.monotonic() > deadline:
                raise TimeoutError(f"shard {s} never registered in {reg}")
            time.sleep(0.1)
    return procs


def _launch_shards_inproc(data: str, reg: str):
    from euler_tpu.graph.service import GraphService

    return [
        GraphService(data, s, NUM_SHARDS, registry=reg)
        for s in range(NUM_SHARDS)
    ]


def run_workload(graph, steps: int, batch: int, fanouts, feature_dim: int,
                 seed: int = 5):
    """The training-shaped remote workload: per step draw roots, run the
    2-hop fanout, fetch dense features for the full frontier (roots +
    both hops — what model.sample feeds the encoder). Returns (edges/s,
    wall s, ids_requested) where ids_requested counts every id a
    pre-dedup client would put on the wire."""
    from euler_tpu.graph import native
    from euler_tpu.telemetry import record_phase

    f1, f2 = fanouts
    edges_per_step = batch * (f1 + f1 * f2)
    native.lib().eg_seed(seed)
    requested = 0
    t0 = time.perf_counter()
    for _ in range(steps):
        t_step = time.perf_counter()
        roots = graph.sample_node(batch, -1)
        hop_ids, _, _ = graph.sample_fanout(roots, [[0, 1], [0, 1]], [f1, f2])
        requested += batch + batch * f1  # fanout hop inputs
        frontier = np.concatenate(hop_ids)
        graph.get_dense_feature(frontier, [0], [feature_dim])
        requested += len(frontier)
        # step-phase profiler hooks ride the measured loop so the
        # telemetry on/off A/B prices them too (the <2% overhead
        # contract now covers the profiler, not just the RPC histograms)
        dur_us = (time.perf_counter() - t_step) * 1e6
        record_phase("sample", dur_us)
        record_phase("step", dur_us)
    dt = time.perf_counter() - t0
    return edges_per_step * steps / dt, dt, requested


def bench_config(reg: str, steps: int, batch: int, fanouts,
                 feature_dim: int, label: str, **graph_kwargs):
    """One measured client configuration against the running cluster:
    returns {edges_per_sec, wall_s, ids_requested, ids_on_wire,
    counters} for `steps` workload iterations (after one untimed warmup
    step that pays dial/compile costs)."""
    import euler_tpu
    from euler_tpu.graph import native

    g = euler_tpu.Graph(mode="remote", registry=reg, **graph_kwargs)
    try:
        run_workload(g, 1, batch, fanouts, feature_dim)  # warm dials/cache
        native.reset_counters()
        eps, dt, requested = run_workload(g, steps, batch, fanouts,
                                          feature_dim)
        ctr = native.counters()
    finally:
        g.close()
    # the PR-3 identity extended by PR 9: neighbor-cache hits are ids
    # served locally too (a hub hop sampled from the cached slice never
    # reaches the wire)
    on_wire = (requested - ctr["ids_deduped"] - ctr["cache_hits"]
               - ctr["nbr_cache_hits"])
    return {
        "label": label,
        "edges_per_sec": round(eps, 1),
        "wall_s": round(dt, 3),
        "ids_requested": requested,
        "ids_on_wire": on_wire,
        "counters": {k: v for k, v in ctr.items() if v},
    }


def depth_sweep(reg: str, steps: int, batch: int, fanouts,
                feature_dim: int, depths=(0, 1, 2, 4)) -> dict:
    """Per-depth input-stall measurement of the async step pipeline
    (PERF.md "Pipelined sampling"): the train.py sampler_depth= shape —
    step k's simulated device compute overlapping step k+1..k+depth's
    whole-step sampling through the engine's completion queue
    (eg_remote_sample_async). depth 0 is the sync before-picture: the
    consumer IS the sampler, so its measured input_stall equals the full
    sample latency. Each arm reports the measured mean consumer stall,
    whether it clears the ROADMAP item-1 threshold (stall < 5% of the
    device step), edges/s, and the counter ledger — the depth-1-vs-2 A/B
    is the PERF.md evidence row."""
    import euler_tpu
    from euler_tpu.graph import native
    from euler_tpu.parallel import pipeline
    from euler_tpu.telemetry import (
        phase_hists,
        record_phase,
        set_telemetry,
        telemetry_reset,
    )

    f1, f2 = fanouts
    edges_per_step = batch * (f1 + f1 * f2)
    # the input_stall histogram IS this measurement — make sure a
    # preceding kill-switch A/B arm didn't leave recording off
    set_telemetry(True)
    g = euler_tpu.Graph(mode="remote", registry=reg)
    try:
        # Calibrate the simulated device step to the measured sync
        # sample time: "hidden" must be a real race between sampling and
        # compute, not a foregone conclusion against a huge device step.
        native.lib().eg_seed(11)
        t0 = time.perf_counter()
        calib = 3
        for _ in range(calib):
            roots = g.sample_node(batch, -1)
            hop_ids, _, _ = g.sample_fanout(
                roots, [[0, 1], [0, 1]], [f1, f2]
            )
            g.get_dense_feature(
                np.concatenate(hop_ids), [0], [feature_dim]
            )
        device_s = max(0.002, (time.perf_counter() - t0) / calib)

        def start_fn(step):
            roots = g.sample_node(batch, -1)
            return roots, g.sample_fanout_async(
                roots, [[0, 1], [0, 1]], [f1, f2]
            )

        def finish_fn(step, pending):
            roots, h = pending
            if h is None:  # async pool exhausted: degrade to sync
                hop_ids, _, _ = g.sample_fanout(
                    roots, [[0, 1], [0, 1]], [f1, f2]
                )
            else:
                hop_ids, _, _ = h.take()
            g.get_dense_feature(
                np.concatenate(hop_ids), [0], [feature_dim]
            )
            return hop_ids

        rows = []
        for depth in depths:
            native.lib().eg_seed(17)
            native.reset_counters()
            telemetry_reset()
            t0 = time.perf_counter()
            if depth == 0:
                for s in range(steps):
                    t_w = time.perf_counter()
                    roots = g.sample_node(batch, -1)
                    hop_ids, _, _ = g.sample_fanout(
                        roots, [[0, 1], [0, 1]], [f1, f2]
                    )
                    g.get_dense_feature(
                        np.concatenate(hop_ids), [0], [feature_dim]
                    )
                    if s > 0:  # steady state only (see below)
                        record_phase(
                            "input_stall",
                            (time.perf_counter() - t_w) * 1e6,
                        )
                    time.sleep(device_s)
            else:
                first = True
                for _ in pipeline(start_fn, finish_fn, steps,
                                  depth=depth):
                    if first:
                        # step 0's stall is the pipeline fill (nothing
                        # was in flight yet) — every depth pays it
                        # identically, so drop it and measure the
                        # steady-state stall the depth actually buys
                        telemetry_reset()
                        first = False
                    time.sleep(device_s)  # simulated device step
            dt = time.perf_counter() - t0
            ctr = native.counters()
            stall_h = phase_hists().get("input_stall")
            stall_ms = (
                stall_h["sum_us"] / stall_h["count"] / 1000.0
                if stall_h and stall_h["count"] else 0.0
            )
            rows.append({
                "sampler_depth": depth,
                "input_stall_ms": round(stall_ms, 3),
                "sampling_hidden_by_prefetch": bool(
                    stall_ms < 0.05 * device_s * 1e3
                ),
                "edges_per_sec": round(edges_per_step * steps / dt, 1),
                "wall_s": round(dt, 3),
                "counters": {
                    k: v for k, v in ctr.items()
                    if v and (k.startswith("async")
                              or k in ("rpc_chunks", "rpc_errors",
                                       "ids_deduped", "cache_hits",
                                       "nbr_cache_hits",
                                       "prefetch_produced"))
                },
            })
        return {"device_step_ms": round(device_s * 1e3, 2), "rows": rows}
    finally:
        g.close()


def heat_ab_paired(reg: str, pairs: int, steps: int, batch: int, fanouts,
                   feature_dim: int) -> dict:
    """Paired interleaved heat on/off measurement on ONE client against
    the running cluster: per pair, both arms run back-to-back (order
    alternating), and the per-pair relative wall difference is the
    sample. Single-shot A/B draws scatter +-4pp on the 1-core container
    (box drift between configs lands entirely in the difference);
    pairing cancels the drift, so the median here is the number the <2%
    overhead contract is judged on (PERF.md "Data-plane heat")."""
    import statistics

    import euler_tpu
    from euler_tpu.heat import set_heat

    g = euler_tpu.Graph(mode="remote", registry=reg)
    try:
        run_workload(g, 2, batch, fanouts, feature_dim)  # warm
        diffs = []
        for pair in range(pairs):
            walls = {}
            arms = [True, False] if pair % 2 == 0 else [False, True]
            for flag in arms:
                set_heat(flag)
                t0 = time.perf_counter()
                run_workload(g, steps, batch, fanouts, feature_dim)
                walls[flag] = time.perf_counter() - t0
            diffs.append(
                (walls[True] - walls[False]) / walls[False] * 100.0
            )
        diffs.sort()
        return {
            "pairs": pairs,
            "steps_per_arm": steps,
            "median_overhead_pct": round(statistics.median(diffs), 2),
            "mean_overhead_pct": round(statistics.mean(diffs), 2),
            "sem_pct": round(
                statistics.stdev(diffs) / len(diffs) ** 0.5, 2
            ) if len(diffs) > 1 else 0.0,
        }
    finally:
        set_heat(True)
        g.close()


def devprof_ab_paired(pairs: int, steps: int) -> dict:
    """Paired interleaved devprof on/off measurement of the device-plane
    hooks on the training hot path: a Watched jit step (recompile
    attribution) plus the per-batch h2d/d2h byte census, exactly the
    instrumentation train.py runs every step. The step is a fixed
    4-layer matmul sized to a real train step (~0.5-1 ms on this CPU
    image) — NOT the smoke graph's toy dims, where a ~10 us dispatch
    would read any fixed per-step hook cost as a huge percentage. Same
    pairing rationale as heat_ab_paired above — per pair both arms run
    back-to-back with alternating order so box drift cancels, and the
    median relative wall difference is the number the <2% overhead
    contract is judged on (OBSERVABILITY.md "Device plane")."""
    import statistics

    import jax
    import jax.numpy as jnp

    from euler_tpu import devprof

    devprof.install()

    def _step(w, x):
        h = x
        for _ in range(4):
            h = jnp.tanh(h @ w)
        return h.sum()

    step = devprof.watch(jax.jit(_step), name="devprof_ab_step")
    w = jnp.ones((128, 128), jnp.float32)
    x = jnp.ones((256, 128), jnp.float32)
    jax.block_until_ready(step(w, x))  # warm: compile priced outside arms
    diffs = []
    try:
        # settle pass (untimed): one full arm's worth of dispatches so
        # allocator/dispatch caches reach steady state before pair 0 —
        # a cold first arm otherwise lands entirely in its difference
        for _ in range(steps):
            out = step(w, x)
        jax.block_until_ready(out)
        for pair in range(pairs):
            walls = {}
            arms = [True, False] if pair % 2 == 0 else [False, True]
            for flag in arms:
                devprof.set_devprof(flag)
                t0 = time.perf_counter()
                for _ in range(steps):
                    devprof.count_h2d((w, x))
                    out = step(w, x)
                    devprof.count_d2h(out)
                jax.block_until_ready(out)
                walls[flag] = time.perf_counter() - t0
            diffs.append(
                (walls[True] - walls[False]) / walls[False] * 100.0
            )
    finally:
        devprof.set_devprof(True)
    diffs.sort()
    return {
        "pairs": pairs,
        "steps_per_arm": steps,
        "median_overhead_pct": round(statistics.median(diffs), 2),
        "mean_overhead_pct": round(statistics.mean(diffs), 2),
        "sem_pct": round(
            statistics.stdev(diffs) / len(diffs) ** 0.5, 2
        ) if len(diffs) > 1 else 0.0,
    }


def run_remote_bench(smoke: bool = False, inproc: bool | None = None,
                     steps: int | None = None) -> dict:
    """Full before/after measurement; returns one result dict
    (metric/value/unit/vs_baseline/detail)."""
    import shutil
    import tempfile

    from tests.fixture_graph import build_powerlaw_fixture

    if smoke:
        num_nodes, avg_degree, feature_dim = 300, 10, 16
        batch, fanouts = 32, (5, 5)
        steps = steps or 4
        if inproc is None:
            inproc = True
    else:
        num_nodes, avg_degree, feature_dim = 20000, 30, 64
        batch, fanouts = 512, (10, 10)
        steps = steps or 20
        if inproc is None:
            inproc = False

    tmp = tempfile.mkdtemp(prefix="euler_remote_bench_")
    data = os.path.join(tmp, "data")
    reg = os.path.join(tmp, "reg")
    os.makedirs(data)
    os.makedirs(reg)
    procs = []
    try:
        build_powerlaw_fixture(data, num_nodes, avg_degree, feature_dim)
        procs = (_launch_shards_inproc if inproc else
                 _launch_shards_subproc)(data, reg)

        # BASELINE: the pre-PR wire shape (dedup + BOTH caches off; the
        # dispatcher still runs — thread spawn/join cannot be re-added)
        before = bench_config(
            reg, steps, batch, fanouts, feature_dim, "baseline",
            coalesce=False, feature_cache_mb=0, neighbor_cache_mb=0,
        )
        # OPTIMIZED: defaults (coalesce on, cache on, telemetry on)
        after = bench_config(
            reg, steps, batch, fanouts, feature_dim, "optimized",
            telemetry=True,
        )
        # TELEMETRY A/B: the optimized path with BOTH observability
        # kill-switches thrown — telemetry (histograms/spans/phases)
        # AND the blackbox flight recorder — so the <2% overhead
        # contract (PERF.md "Telemetry overhead") prices every recorder
        # on the hot path, eg_blackbox's ring writes included. The
        # config keys are process-global, so the client AND the
        # in-process shards all stop recording; re-enabled in the
        # finally below.
        tel_off = bench_config(
            reg, steps, batch, fanouts, feature_dim, "telemetry_off",
            telemetry=False, blackbox=False,
        )
        telemetry_overhead_pct = round(
            (tel_off["edges_per_sec"] - after["edges_per_sec"])
            / tel_off["edges_per_sec"] * 100.0, 2,
        ) if tel_off["edges_per_sec"] > 0 else 0.0
        # HEAT A/B: the optimized path with ONLY the data-plane heat
        # profiler off (telemetry/blackbox stay on), so the sketch +
        # top-K + fan-out recording is priced on its own under the same
        # <2% contract (PERF.md "Data-plane heat"). heat= is
        # process-global, so the in-process shards stop feeding too;
        # re-enabled in the finally below.
        heat_off = bench_config(
            reg, steps, batch, fanouts, feature_dim, "heat_off",
            heat=False,
        )
        heat_overhead_pct = round(
            (heat_off["edges_per_sec"] - after["edges_per_sec"])
            / heat_off["edges_per_sec"] * 100.0, 2,
        ) if heat_off["edges_per_sec"] > 0 else 0.0
        # the statistically sound form: paired interleaved arms cancel
        # the box drift a single-shot config comparison cannot
        heat_ab = heat_ab_paired(
            reg, pairs=3 if smoke else 10, steps=max(2, steps // 2),
            batch=batch, fanouts=fanouts, feature_dim=feature_dim,
        )
        # DEVPROF A/B: the device-plane hooks priced the same paired way,
        # on the jit-dispatch hot path they actually ride (the remote
        # sampling loop above never crosses a jit boundary, so a config
        # A/B there would price nothing).
        devprof_ab = devprof_ab_paired(
            pairs=3 if smoke else 10,
            steps=50 if smoke else 200,
        )
        # ASYNC DEPTH SWEEP: sampler_depth in {1,2,4} vs the sync
        # before-picture (depth 0) — the pipelined-sampling evidence
        # (PERF.md "Pipelined sampling", ROADMAP item 1)
        sweep = depth_sweep(
            reg, steps=max(4, steps // 2), batch=batch, fanouts=fanouts,
            feature_dim=feature_dim,
        )
        depth2 = next(
            (r for r in sweep["rows"] if r["sampler_depth"] == 2), None
        )
        reduction = (
            after["ids_requested"] / after["ids_on_wire"]
            if after["ids_on_wire"] > 0 else float("inf")
        )
        value = after["edges_per_sec"]
        return {
            "metric": "remote_edges/sec",
            "value": value,
            "unit": "edges/s",
            "vs_baseline": round(value / 2_000_000.0, 3),
            "detail": {
                "config": "remote",
                "cluster": f"{NUM_SHARDS} shards, localhost, "
                           f"{'in-process' if inproc else 'subprocess'}",
                "graph": {
                    "num_nodes": num_nodes, "avg_degree": avg_degree,
                    "feature_dim": feature_dim, "powerlaw_alpha": 1.1,
                },
                "workload": {
                    "batch": batch, "fanouts": list(fanouts),
                    "steps": steps,
                },
                "before": before,
                "after": after,
                "telemetry_off": tel_off,
                "telemetry_overhead_pct": telemetry_overhead_pct,
                "heat_off": heat_off,
                "heat_overhead_pct": heat_overhead_pct,
                "heat_ab": heat_ab,
                "devprof_ab": devprof_ab,
                "sampler_depth_sweep": sweep,
                # the measured depth-2 stall vs the (simulated,
                # sample-time calibrated) device step, judged at 5% of it
                "breakdown": {
                    "device_step_ms": sweep["device_step_ms"],
                    "sampler_depth": 2,
                    "input_stall_ms": (
                        depth2["input_stall_ms"] if depth2 else None
                    ),
                    "sampling_hidden_by_prefetch": bool(
                        depth2 and depth2["sampling_hidden_by_prefetch"]
                    ),
                },
                "speedup": round(
                    after["edges_per_sec"] / before["edges_per_sec"], 3
                ),
                "ids_on_wire_reduction": round(reduction, 2),
            },
        }
    finally:
        from euler_tpu.blackbox import set_blackbox
        from euler_tpu.heat import set_heat
        from euler_tpu.telemetry import set_telemetry

        set_telemetry(True)  # the kill-switch A/Bs are process-global
        set_blackbox(True)
        set_heat(True)
        for p in procs:
            if hasattr(p, "stop"):
                p.stop()
            elif p.poll() is None:
                p.kill()
                p.wait()
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small graph, few steps, in-process shards "
                    "(the verify.sh gate)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--inproc", action="store_true", default=None,
                    help="in-process shard services instead of "
                    "subprocesses")
    args = ap.parse_args()
    result = run_remote_bench(smoke=args.smoke, inproc=args.inproc,
                              steps=args.steps)
    print(json.dumps(result), flush=True)
    detail = result["detail"]
    if args.smoke:
        # the smoke gate's contract: the optimized path must demonstrably
        # coalesce — a silent dedup regression fails verify, not PERF.md
        assert detail["ids_on_wire_reduction"] >= 2.0, detail
        assert detail["after"]["counters"].get("ids_deduped", 0) > 0, detail
        # and the async pipeline must demonstrably run (submits on the
        # ledger) — hidden-ness is judged on the full run, not smoke
        d2 = next(r for r in detail["sampler_depth_sweep"]["rows"]
                  if r["sampler_depth"] == 2)
        assert d2["counters"].get("async_submits", 0) > 0, d2
    return 0


if __name__ == "__main__":
    sys.exit(main())
