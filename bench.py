"""Headline benchmark: supervised GraphSAGE throughput on one TPU chip.

Mirrors the reference's flagship recipes on synthetic graphs at the real
datasets' scale (the real data is not downloadable in this zero-egress
environment; the synthetic graphs match node count / degree / feature and
label dims, making the sampling + compute cost representative):

  ppi     reference examples/sage.py:80-98 — batch 512, fanouts [10,10],
          dim 256, Adam 0.01 on a 56944-node, 50-feature, 121-label graph
          (constants from reference tf_euler/python/ppi_main.py:24-33).
  reddit  reference examples/sage_reddit.py:80-97 — batch 1000, fanouts
          [4,4], dim 64, Adam 0.03 on a 232965-node, 602-feature,
          41-class graph (reference tf_euler/python/reddit_main.py:24-34),
          exercising the device-resident feature table at real dims.
  reddit_heavytail  the same recipe on a power-law graph at real
          Reddit's EDGE budget (~114.6M directed edges, mean degree
          ~490, heavy tail — datasets.build_powerlaw), device sampling
          via the EXACT flat-CSR alias sampler (reference semantics:
          CompactNode samples over ALL neighbors,
          euler/core/compact_node.cc:42-101; the padded slab is
          max_degree-truncated or unbuildable at these degrees). Not in
          the default config list: the first build writes a ~1.9 GB
          graph (cached; EULER_TPU_HEAVYTAIL_CACHE overrides the
          location, default <repo>/.data/reddit_ht — shared with
          scripts/reddit_heavytail.py --full). Opt in with
          --configs reddit_heavytail.

Prints one JSON line per config; with the default config list the LAST
line is always the headline
  {"metric": "edges/sec/chip", "value": N, "unit": "edges/s",
   "vs_baseline": r, "detail": {...}}
where "edges" counts sampled neighbor draws consumed per step
(batch * (f1 + f1*f2)), the standard GNN throughput metric, and
vs_baseline divides by BASELINE_TARGET = 2e6 edges/s/chip — the
BASELINE.md north-star proxy (2x an assumed 1M edges/s for the
reference's 8xV100-era distributed setup; the reference repo publishes
no number, see BASELINE.md).

Process contract:
- The platform is what the environment says. Nothing probes for a TPU
  and nothing downgrades to CPU: a backend that fails to initialize, an
  unknown device_kind, a failed device-sampling phase or kernel A/B all
  fail the config, and a failed config fails the exit code. An explicit
  JAX_PLATFORMS=cpu run (--smoke, scripts/perf_gate.py) is legal for
  control flow and says "platform": "cpu" on every line it prints.
- A chip belongs to one process at a time, so the parent never
  initializes a JAX backend: EVERY config's measurement runs in its OWN
  subprocess, one after another, each with a wall-time cap, and writes
  its JSON result to <repo>/.bench_bank/<config>.json (override
  EULER_TPU_BENCH_BANK) — the host-path number first, before the
  device-sampling section starts, so a config killed at its cap still
  reports what it measured, marked with an "error".

detail.breakdown reports the step-time split measured directly:
host-sample ms/batch (graph engine time inside prefetch workers),
device-step ms (blocking step on a resident batch), pipelined wall
ms/step, and the input stall (wall - device) — pipelined wall close to
device-step means the prefetch pipeline hides host sampling, the design
claim of euler_tpu/parallel/prefetch.py. A JAX profiler trace of the
measured window is saved to EULER_TPU_PROFILE_DIR (default
/tmp/euler_tpu_bench_trace) when tracing is available.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BASELINE_TARGET = 2_000_000.0  # edges/s/chip; see module docstring

# Gate every throughput number on physical plausibility before it can
# become the headline: an empty-body scan step alone measured 0.133 ms on
# a v5e (PERF.md step anatomy), so a train step under 30 us was not
# executed, whatever the host clock says.
MIN_CREDIBLE_STEP_MS = 0.03


def _implausible(step_ms: float, loss) -> str | None:
    """Non-None (reason) when a measured step time or loss cannot be a
    real execution; callers must drop the number from the headline."""
    if step_ms < MIN_CREDIBLE_STEP_MS:
        return (
            f"step {step_ms * 1e3:.1f}us < {MIN_CREDIBLE_STEP_MS * 1e3:.0f}us"
            " floor: dispatches are not executing"
        )
    if loss is not None and not np.isfinite(float(np.asarray(loss).ravel()[-1])):
        return "non-finite loss: execution produced garbage"
    return None

CONFIGS = {
    "ppi": dict(
        num_nodes=56944, avg_degree=15, feature_dim=50, label_dim=121,
        multilabel=True, batch=512, fanouts=(10, 10), dim=256, lr=0.01,
        warmup=5, measure=30,
    ),
    "reddit": dict(
        num_nodes=232965, avg_degree=50, feature_dim=602, label_dim=41,
        multilabel=False, batch=1000, fanouts=(4, 4), dim=64, lr=0.03,
        warmup=3, measure=15,
    ),
    # the same recipe with a bfloat16 feature table: Reddit's 602-dim
    # rows are the wide-gather case the reduced-precision table exists
    # for (the feature gathers are the post-kernel bottleneck, PERF.md
    # step anatomy) — compare against the reddit line for the f32/bf16
    # A/B. Reference analog: PS-side feature storage,
    # tf_euler/python/utils/embedding.py:22-67.
    "reddit_bf16": dict(
        num_nodes=232965, avg_degree=50, feature_dim=602, label_dim=41,
        multilabel=False, batch=1000, fanouts=(4, 4), dim=64, lr=0.03,
        warmup=3, measure=15, feature_dtype="bfloat16",
        cache_as="reddit",  # identical graph: share the on-disk cache
    ),
    # real-degree Reddit: power-law out/in-degrees at the real edge
    # budget (unique-fill + Gumbel-top-k hub rows land the achieved
    # count <1% under num_edges; measured 0.8% under at this recipe).
    # Graph-shape params come from datasets.REDDIT_HEAVYTAIL at run
    # time (run_config merges them in), the single source also used by
    # scripts/reddit_heavytail.py --full, so the two share a cache by
    # construction.
    "reddit_heavytail": dict(
        batch=1000, fanouts=(4, 4), dim=64, lr=0.03,
        warmup=3, measure=15, powerlaw=True, alias_sampling=True,
    ),
    # Tiny host-path-only config for the perf-regression gate
    # (scripts/perf_gate.py; verify.sh): small enough to finish in a
    # couple of minutes on CPU, big enough that the sampling + compute
    # pipeline is real. host_only skips the device-sampling /
    # kernel-A/B sections. Not comparable to the full configs above —
    # the gate compares smoke-to-smoke across rounds.
    "smoke": dict(
        num_nodes=3000, avg_degree=8, feature_dim=16, label_dim=4,
        multilabel=True, batch=128, fanouts=(5, 5), dim=32, lr=0.01,
        warmup=2, measure=8, host_only=True,
    ),
    # The sharded REMOTE path (scripts/remote_bench.py): edges/s of a
    # 2-hop fanout + feature batch against a local 2-shard cluster,
    # before/after the dedup + cache + dispatcher optimizations, with
    # the ids-on-wire counter ledger. No model training, no TPU — this
    # measures the remote client, the ROADMAP's serve-millions tier.
    # Not in the default list (the single-chip configs are the
    # headline); opt in with --configs remote.
    "remote": dict(remote=True),
}

def kernel_in_program(jitted, *args) -> bool:
    """True when the program ``jitted`` lowers to for ``args`` holds a
    Mosaic custom call — the compiled Pallas draw kernel itself, not
    merely packed slabs that could have fed it. ONE copy of the
    detection, shared with scripts/batch_sweep.py."""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def kernel_ab(model, opt, graph, batch_size: int, chunk_steps: int,
              kernel_steps_per_sec: float, chunks: int = 4,
              put=None) -> dict:
    """Measure the SAME config with the Pallas kernel forced off and
    return {xla_path_steps_per_sec, kernel_step_speedup}; a failure or
    an implausible measurement raises. Shared by run_config's headline
    A/B and the batch sweep's per-point A/B — the env-toggle
    save/run/restore protocol must not fork. Caller must free its own
    kernel-path state first: this uploads a second full state (slabs +
    params + opt).

    put: optional sharding for the XLA-path state (run_config passes
    its replicated mesh sharding). The kernel-path measurement places
    state_ds on `rep`; without the matching device_put here a
    multi-chip mesh would compare different placements."""
    import jax

    from euler_tpu import train as train_lib

    out = {}
    prior = os.environ.get("EULER_TPU_PALLAS_SAMPLING")
    os.environ["EULER_TPU_PALLAS_SAMPLING"] = "0"
    try:
        state_x = model.init_state(
            jax.random.PRNGKey(0), graph,
            graph.sample_node(batch_size, -1), opt,
        )
        if put is not None:
            state_x = jax.device_put(state_x, put)
        scan_x = jax.jit(
            train_lib.make_scan_train(model, opt, chunk_steps, batch_size),
            donate_argnums=(0,),
        )
        state_x, lx = scan_x(state_x, 0)
        jax.block_until_ready(lx)
        t0 = time.perf_counter()
        for c in range(1, chunks + 1):
            state_x, lx = scan_x(state_x, c)
        jax.block_until_ready(lx)
        x_dt = time.perf_counter() - t0
        x_ms = x_dt / (chunks * chunk_steps) * 1e3
        bogus = _implausible(x_ms, lx)
        if bogus:
            raise RuntimeError(f"kernel A/B measurement rejected: {bogus}")
        x_sps = chunks * chunk_steps / x_dt
        out["xla_path_steps_per_sec"] = round(x_sps, 2)
        out["kernel_step_speedup"] = round(kernel_steps_per_sec / x_sps, 3)
        del state_x
    finally:
        if prior is None:
            os.environ.pop("EULER_TPU_PALLAS_SAMPLING", None)
        else:
            os.environ["EULER_TPU_PALLAS_SAMPLING"] = prior
    return out


def _failure_line(name: str, error: str) -> dict:
    """The driver-parseable headline shape for a run that produced no
    measurement (shared by the per-config except path and the watchdog so
    the schema cannot drift between them)."""
    return {
        "metric": (
            "edges/sec/chip" if name == "ppi" else f"{name}_edges/sec/chip"
        ),
        "value": 0.0,
        "unit": "edges/s",
        "vs_baseline": 0.0,
        "error": error,
    }


# bf16 peak FLOP/s and HBM bytes/s of one chip, keyed by a substring of
# jax.devices()[0].device_kind (public TPU spec sheets; v5e: Google Cloud
# documentation "TPU v5e"). A device that is not here is an error, not a
# default.
CHIP_PEAKS = {
    "v5 lite": (197e12, 819e9),
    "v5litepod": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v6 lite": (918e12, 1640e9),  # device_kind "TPU v6 lite"
    "v6e": (918e12, 1640e9),
    "v4": (275e12, 1228e9),
}


def _chip_peaks(device_kind: str):
    """(peak_flops, peak_hbm_bytes_per_s) for a device_kind; raises on
    one CHIP_PEAKS does not list."""
    kind = device_kind.lower()
    for k, peaks in CHIP_PEAKS.items():
        if k in kind:
            return peaks
    raise ValueError(
        f"unknown device_kind {device_kind!r}: add its spec-sheet peaks "
        "to bench.CHIP_PEAKS"
    )


def _roofline(compiled, step_time_ms: float):
    """Achieved-vs-peak utilization from XLA's compile-time cost model:
    {flops_per_step, hbm_bytes_per_step, achieved_tflops,
    achieved_hbm_gbps, mfu, hbm_util}. The numbers are ANALYTICAL
    (operand/output byte counts and op FLOPs from cost_analysis(), not
    hardware counters) — right order of magnitude for a roofline
    statement, not a profiler replacement. Scan/while bodies are counted
    once by the cost model, so a scanned dispatch is already per-step.
    On a CPU backend only the two counts are returned: a rate or a
    utilization is a device metric."""
    import jax

    ca = compiled.cost_analysis()
    out = {
        "flops_per_step": round(float(ca.get("flops", 0.0)), 1),
        "hbm_bytes_per_step": round(float(ca.get("bytes accessed", 0.0)), 1),
        "source": "xla_cost_analysis",
    }
    dev = jax.devices()[0]
    t = step_time_ms / 1e3
    if dev.platform == "cpu" or t <= 0:
        return out
    peak_f, peak_b = _chip_peaks(dev.device_kind)
    flops, byts = out["flops_per_step"], out["hbm_bytes_per_step"]
    out["achieved_tflops"] = round(flops / t / 1e12, 4)
    out["achieved_hbm_gbps"] = round(byts / t / 1e9, 2)
    out["mfu"] = round(flops / t / peak_f, 5)
    out["hbm_util"] = round(byts / t / peak_b, 5)
    return out


def _timed(fn, out_list):
    """Wrap fn to append its wall duration (ms) to out_list (thread-safe:
    list.append is atomic)."""

    def wrapper(*args):
        t0 = time.perf_counter()
        result = fn(*args)
        out_list.append((time.perf_counter() - t0) * 1e3)
        return result

    return wrapper


def run_config(name: str, cfg: dict, trace_dir: str | None, bank=None):
    """Train supervised GraphSAGE at cfg's scale, measuring pipelined
    throughput plus the host/device step-time split. Returns the result
    JSON dict. ``bank``, when given, is called with the host-path-only
    result BEFORE the device-sampling section starts (and callers bank
    the final dict themselves) — a config killed at its cap then loses
    the device-sampling delta, not the whole config. Any failure of the
    device-sampling section or the kernel A/B raises."""
    if cfg.get("remote"):
        # the remote-client benchmark: no jax, no model — delegate to
        # scripts/remote_bench.py (one measurement implementation shared
        # with the verify.sh smoke gate, so the two cannot drift)
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "remote_bench",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "remote_bench.py"),
        )
        remote_bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(remote_bench)
        return remote_bench.run_remote_bench()
    import jax

    import euler_tpu
    from euler_tpu import train as train_lib
    from euler_tpu.datasets import build_synthetic
    from euler_tpu.models import SupervisedGraphSage
    from euler_tpu.parallel import (
        batch_sharding,
        make_mesh,
        prefetch,
        replicated_sharding,
        shard_batch,
    )

    if cfg.get("powerlaw"):
        # graph shape from the one authoritative constant (shared with
        # scripts/reddit_heavytail.py; a drifted copy here would
        # silently invalidate the ~2 GB cache and measure a different
        # graph than PERF.md describes)
        from euler_tpu.datasets import REDDIT_HEAVYTAIL

        cfg = {**cfg, **REDDIT_HEAVYTAIL}

    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind
    warmup, measure = cfg["warmup"], cfg["measure"]
    batch_size, fanouts, dim = cfg["batch"], list(cfg["fanouts"]), cfg["dim"]

    if cfg.get("powerlaw"):
        from euler_tpu.datasets import build_powerlaw, heavytail_cache_dir

        cache = heavytail_cache_dir()
        build_powerlaw(
            cache,
            num_nodes=cfg["num_nodes"],
            num_edges=cfg["num_edges"],
            feature_dim=cfg["feature_dim"],
            label_dim=cfg["label_dim"],
            alpha=cfg["alpha"],
            multilabel=cfg["multilabel"],
            progress_every=50000,
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

    model = SupervisedGraphSage(
        label_idx=0,
        label_dim=cfg["label_dim"],
        metapath=[[0]] * len(fanouts),
        fanouts=fanouts,
        dim=dim,
        feature_idx=1,
        feature_dim=cfg["feature_dim"],
        max_id=cfg["num_nodes"] - 1,
        device_features=True,
        feature_dtype=cfg.get("feature_dtype"),
    )

    mesh = make_mesh()
    n_chips = len(mesh.devices.reshape(-1))
    opt = train_lib.get_optimizer("adam", cfg["lr"])
    state = model.init_state(
        jax.random.PRNGKey(0), graph, graph.sample_node(batch_size, -1), opt
    )
    rep = replicated_sharding(mesh)
    state = jax.device_put(state, rep)
    step_fn = jax.jit(
        model.make_train_step(opt),
        in_shardings=(rep, batch_sharding(mesh)),
        out_shardings=(rep, rep, rep),
        donate_argnums=(0,),
    )

    sample_ms: list[float] = []
    sample_fn = _timed(
        lambda: model.sample(graph, graph.sample_node(batch_size, -1)),
        sample_ms,
    )

    def make_batch(step):
        # H2D transfer in the prefetch worker: upload of batch k+1
        # overlaps device compute of step k
        return shard_batch(sample_fn(), mesh)

    from euler_tpu.telemetry import phase_hists, telemetry_reset

    tracing = False
    it = prefetch(make_batch, warmup + measure, depth=3, num_threads=4)
    losses = []
    last_batch = None
    for i, batch in enumerate(it):
        if i == warmup:
            jax.block_until_ready(state)
            sample_ms.clear()  # keep only measured-window samples
            telemetry_reset()  # measured-window phase hists only
            if trace_dir:
                try:
                    jax.profiler.start_trace(trace_dir)
                    tracing = True
                except Exception as e:
                    trace_dir = f"unavailable: {e}"
            t0 = time.perf_counter()
        state, loss, metric = step_fn(state, batch)
        losses.append(loss)
        last_batch = batch
    jax.block_until_ready(losses[-1])
    dt = time.perf_counter() - t0
    if tracing:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            trace_dir = f"unavailable: {e}"

    # Pure device step time: blocking steps on an already-resident batch —
    # no sampling or H2D in the timed region.
    device_times = []
    for _ in range(5):
        t1 = time.perf_counter()
        state, loss, metric = step_fn(state, last_batch)
        jax.block_until_ready(loss)
        device_times.append(time.perf_counter() - t1)
    device_step_ms = float(np.median(device_times)) * 1e3
    # achieved-vs-peak for the host-path device step (lower() hits the
    # jit cache — no recompile; donation is irrelevant, nothing executes)
    host_roofline = _roofline(
        step_fn.lower(state, last_batch).compile(), device_step_ms
    )

    step_wall_ms = dt / measure * 1e3
    host_sample_ms = float(np.mean(sample_ms)) if sample_ms else 0.0
    # Prefer the DIRECTLY measured consumer stall (the prefetch
    # pipeline's input_stall phase histogram over the measured window)
    # over the wall-minus-device derivation — the derived number folds
    # in host bookkeeping that is not input starvation.
    stall_h = phase_hists().get("input_stall")
    measured_stall_ms = (
        stall_h["sum_us"] / stall_h["count"] / 1000.0
        if stall_h and stall_h["count"] else None
    )
    edges_per_step = batch_size * (
        fanouts[0] + fanouts[0] * (fanouts[1] if len(fanouts) > 1 else 0)
    )
    sps = measure / dt
    edges_per_sec = edges_per_step * sps / n_chips

    host_bogus = _implausible(step_wall_ms, losses[-1])
    if host_bogus:
        # the host-path window is this metric's floor; if even it is
        # fake, the whole config's numbers are untrustworthy — and there
        # is no point burning the device-sampling window on it
        return {
            **_failure_line(name, f"measurement rejected: {host_bogus}"),
            "detail": {"config": name, "platform": platform,
                       "device_kind": device_kind, "chips": n_chips},
        }

    def _mk_result(ds: dict) -> dict:
        e_s, s_s = edges_per_sec, sps
        if ds.get("edges_per_sec", 0) > e_s:
            e_s, s_s = ds["edges_per_sec"], ds["steps_per_sec"]
        return {
            "metric": (
                f"{name}_edges/sec/chip" if name != "ppi" else "edges/sec/chip"
            ),
            "value": round(e_s, 1),
            "unit": "edges/s",
            "vs_baseline": round(e_s / BASELINE_TARGET, 3),
            "detail": {
                "config": name,
                "steps_per_sec": round(s_s, 2),
                "batch": batch_size,
                "fanouts": fanouts,
                "dim": dim,
                "chips": n_chips,
                "platform": platform,
                "device_kind": device_kind,
                "final_loss": round(float(np.asarray(losses[-1])), 4),
                "device_sampling": ds,
                "host_path_edges_per_sec": round(
                    edges_per_step * (measure / dt) / n_chips, 1
                ),
                "breakdown": {
                    "host_sample_ms_per_batch": round(host_sample_ms, 2),
                    "device_step_ms": round(device_step_ms, 2),
                    "pipelined_step_wall_ms": round(step_wall_ms, 2),
                    "input_stall_ms": round(
                        measured_stall_ms
                        if measured_stall_ms is not None
                        else max(0.0, step_wall_ms - device_step_ms), 2
                    ),
                    # this path runs a LOCAL graph: the async completion
                    # queue (sampler_depth, remote-only) never engages —
                    # the remote per-depth sweep lives in
                    # scripts/remote_bench.py (PERF.md "Pipelined
                    # sampling")
                    "sampler_depth": 0,
                    # hidden = the measured consumer stall is noise
                    # relative to the device step (< 5% of it) — the
                    # ROADMAP item-1 acceptance threshold, replacing the
                    # old wall<1.2x-device heuristic that a slow host
                    # tail could fail even with zero input starvation
                    "sampling_hidden_by_prefetch": bool(
                        (measured_stall_ms
                         if measured_stall_ms is not None
                         else max(0.0, step_wall_ms - device_step_ms))
                        < 0.05 * device_step_ms
                    ),
                    # achieved vs peak (mfu / hbm_util) — the denominator
                    # for "is the step actually fast"; see PERF.md
                    "roofline": host_roofline,
                },
                "trace_dir": trace_dir,
            },
        }

    if bank is not None:
        partial = _mk_result({})
        partial["detail"]["banked"] = "host_path_only"
        bank(partial)

    # Device-sampling path: adjacency in HBM, roots + fanout sampled
    # inside the jitted step, lax.scan chaining CHUNK steps per dispatch
    # (euler_tpu/graph/device.py + train.make_scan_train). This is the
    # framework's intended fast path for graphs that fit in HBM; the
    # host-path numbers above remain in the breakdown for comparison.
    ds = {}
    if cfg.get("host_only"):
        return _mk_result(ds)
    model_ds = SupervisedGraphSage(
        label_idx=0,
        label_dim=cfg["label_dim"],
        metapath=[[0]] * len(fanouts),
        fanouts=fanouts,
        dim=dim,
        feature_idx=1,
        feature_dim=cfg["feature_dim"],
        max_id=cfg["num_nodes"] - 1,
        device_features=True,
        device_sampling=True,
        feature_dtype=cfg.get("feature_dtype"),
    )
    if cfg.get("alias_sampling"):
        # exact flat-CSR alias sampler: the only buildable device
        # form at heavy-tail degrees (the slab's width would be the
        # max observed degree), and reference-exact at any degree
        model_ds.set_sampling_options(alias=True)
    t_up = time.perf_counter()
    state_ds = model_ds.init_state(
        jax.random.PRNGKey(0), graph,
        graph.sample_node(batch_size, -1), opt,
    )
    state_ds = jax.device_put(state_ds, rep)
    chunk_steps = 50
    scan = jax.jit(
        train_lib.make_scan_train(
            model_ds, opt, chunk_steps, batch_size
        ),
        donate_argnums=(0,),
    )
    # record whether the fused Pallas draw kernel is in the program —
    # on single-chip TPU it should be
    ds["pallas_kernel"] = kernel_in_program(scan, state_ds, 0)
    state_ds, l0 = scan(state_ds, 0)  # compile + warmup chunk
    jax.block_until_ready(l0)
    upload_s = time.perf_counter() - t_up
    chunks = 10

    def _param_digest(st):
        # cheap execution witness: Adam moves every param every step,
        # so a timed window that leaves this digest bit-identical
        # did not execute, whatever loss buffer came back
        leaf = jax.tree.leaves(st["params"])[0]
        return float(np.asarray(jax.device_get(leaf)).sum())

    pre_digest = _param_digest(state_ds)  # syncs pre-window
    t2 = time.perf_counter()
    last = None
    for seed_c in range(1, chunks + 1):
        state_ds, last = scan(state_ds, seed_c)
    jax.block_until_ready(last)
    ds_dt = time.perf_counter() - t2
    step_wall_ms_ds = ds_dt / (chunks * chunk_steps) * 1e3
    bogus = _implausible(step_wall_ms_ds, last)
    if not bogus and _param_digest(state_ds) == pre_digest:
        bogus = (
            "params bit-identical across the timed window: "
            "dispatches not executing"
        )
    if bogus:
        raise RuntimeError(
            f"device-sampling measurement rejected: {bogus}"
        )
    ds_sps = chunks * chunk_steps / ds_dt
    ds["steps_per_sec"] = round(ds_sps, 2)
    ds["edges_per_sec"] = round(edges_per_step * ds_sps / n_chips, 1)
    ds["step_wall_ms"] = round(step_wall_ms_ds, 4)
    ds["setup_s"] = round(upload_s, 2)
    ds["final_loss"] = round(float(np.asarray(last)[-1]), 4)
    # XLA's cost model counts a while/scan BODY ONCE (it does not
    # multiply by trip count) — verified: this dispatch's flops ~=
    # the single-step host path's — so the scanned dispatch needs
    # no chunk_steps division to be per-step
    ds["roofline"] = _roofline(
        scan.lower(state_ds, 0).compile(), ds["step_wall_ms"]
    )
    del state_ds

    # Kernel A/B on the headline config: rerun the same scanned loop
    # with the fused Pallas draw kernel forced off, so the recorded
    # JSON carries the kernel's step-level contribution (TPU only;
    # ppi only — Reddit's table setup is too slow to do twice).
    if (
        name == "ppi"
        and platform == "tpu"
        and ds.get("pallas_kernel")
    ):
        ds.update(kernel_ab(
            model_ds, opt, graph, batch_size, chunk_steps,
            ds["steps_per_sec"], chunks=4, put=rep,
        ))

    return _mk_result(ds)


# Per-config wall-time caps (seconds): the subprocess running a config
# is SIGKILLed at its cap, so one hung config can never eat the
# following configs' time. heavytail gets headroom for the graph load,
# the alias build and the 1.37 GB alias-table upload.
CONFIG_CAPS = {
    "smoke": 300.0,
    "ppi": 900.0,
    "reddit": 900.0,
    "reddit_bf16": 900.0,
    "reddit_heavytail": 1500.0,
    "remote": 900.0,
}


def _bank_write(path: str, obj: dict) -> None:
    """Atomic JSON write (tmp + rename): the parent may read the file
    right after killing the writer, and a torn half-written JSON would
    turn a banked partial result into nothing."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _run_one(name: str, bank_file: str, trace_dir: str | None) -> int:
    """Child mode: measure ONE config in this process — the only one
    that initializes a JAX backend, on whatever platform the
    environment says — and bank the result (host-path partial first,
    final overwrite) to bank_file. A failure is banked as the config's
    failure line AND returned as exit code 1. stdout stays JSON-free —
    the parent owns the driver-facing stream."""
    rc = 0
    try:
        from euler_tpu.parallel import enable_compile_cache

        enable_compile_cache()
        result = run_config(
            name, CONFIGS[name], trace_dir,
            bank=lambda obj: _bank_write(bank_file, obj),
        )
    except Exception as e:  # noqa: BLE001 — the process boundary
        import traceback

        traceback.print_exc()
        result = _failure_line(name, f"{type(e).__name__}: {e}")
        rc = 1
    result.setdefault("detail", {})["banked"] = "final"
    _bank_write(bank_file, result)
    return rc


def _spawn_config(name: str, timeout_s: float, bank_dir: str,
                  trace_dir: str | None):
    """Run one config in a killable subprocess; return (result,
    timed_out) where result is its banked JSON (final, or the mid-config
    host-path partial if the child died after banking it) or None when
    nothing was banked, and timed_out reports whether the child hit its
    deadline. The child is its own session so a SIGKILL reaps any
    grandchildren with it."""
    import signal
    import subprocess

    bank_file = os.path.join(bank_dir, f"{name}.json")
    try:
        os.remove(bank_file)  # stale banks must not pass as this run's
    except OSError:
        pass
    cmd = [
        sys.executable, "-u", os.path.abspath(__file__),
        "--run-one", name, "--bank-file", bank_file,
    ]
    if trace_dir:
        cmd += ["--trace-dir", trace_dir]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.DEVNULL, start_new_session=True,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    timed_out = False
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            proc.kill()
        proc.wait()
    result = None
    if os.path.exists(bank_file):
        try:
            with open(bank_file) as f:
                result = json.load(f)
        except ValueError:
            result = None
    if result is not None and result.get("detail", {}).get("banked") != "final":
        how = (
            f"killed at the {timeout_s:.0f}s config deadline"
            if timed_out else f"child exited rc={proc.returncode}"
        )
        result["error"] = (
            f"{how} mid-config; host-path partial measurement banked "
            "(device-sampling section lost)"
        )
    return result, timed_out


def default_configs() -> str:
    """No-flag config list: reddit,ppi — plus reddit_heavytail (the
    113.7M-edge exact-alias flagship) whenever its cache is already
    built with current params. Pure file check, no backend contact;
    an absent or stale cache is never rebuilt implicitly, so the
    rebuild cost cannot land on an unsuspecting bench window."""
    configs = "reddit,ppi"
    try:
        from euler_tpu.datasets import (
            REDDIT_HEAVYTAIL, heavytail_cache_dir, powerlaw_cache_ready,
        )

        if powerlaw_cache_ready(heavytail_cache_dir(), **REDDIT_HEAVYTAIL):
            configs = "reddit_heavytail," + configs
            print(json.dumps({"note": "reddit_heavytail cache ready; "
                              "added to default configs"}),
                  file=sys.stderr)
    except Exception:
        pass
    return configs


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--configs", default=None,
        help="comma list from %s; when ppi (the headline) is included it "
        "is always printed last. Default: reddit,ppi — plus "
        "reddit_heavytail (the 113.7M-edge exact-alias flagship) "
        "whenever its graph cache is already built with current params "
        "(the driver's no-flag run then covers it for free; an absent "
        "or stale cache is never rebuilt implicitly)" % sorted(CONFIGS),
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="run only the tiny host-path 'smoke' config (the "
        "scripts/perf_gate.py regression probe; smoke-to-smoke "
        "comparable across rounds, NOT comparable to the full configs)",
    )
    ap.add_argument(
        "--deadline", type=float, default=None,
        help="total wall budget in seconds (default: "
        "EULER_TPU_BENCH_DEADLINE, else 1200 s per config, at least 2400)",
    )
    # child-mode flags (internal: the parent spawns `--run-one <config>`)
    ap.add_argument("--run-one", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--bank-file", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--trace-dir", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.run_one:
        sys.exit(_run_one(args.run_one, args.bank_file, args.trace_dir))

    # None = not passed (take defaults); an explicit empty string stays
    # an explicit request to run nothing
    if args.smoke and args.configs is None:
        args.configs = "smoke"
    configs = (
        args.configs if args.configs is not None else default_configs()
    )
    names = [n.strip() for n in configs.split(",") if n.strip()]
    # headline last so the driver's last-line parse records it
    names.sort(key=lambda n: n == "ppi")

    bank_dir = os.environ.get(
        "EULER_TPU_BENCH_BANK",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_bank"),
    )
    os.makedirs(bank_dir, exist_ok=True)

    deadline = args.deadline
    if deadline is None and os.environ.get("EULER_TPU_BENCH_DEADLINE"):
        deadline = float(os.environ["EULER_TPU_BENCH_DEADLINE"])
    if deadline is None or deadline <= 0:
        # per-config budget with headroom; 2400 preserved for the
        # historical two-config default
        deadline = max(2400.0, 1200.0 * len(names))
    t_end = time.monotonic() + deadline

    def _watchdog_exit(config: str) -> None:
        # headline ("ppi") metric shape so the driver's last-line parse
        # always sees the contract, but the error names the config that
        # was actually on the clock
        print(json.dumps(_failure_line(
            "ppi",
            f"bench watchdog: exceeded {deadline:.0f}s during config "
            f"{config}",
        )), flush=True)
        sys.exit(2)

    trace_dir = os.environ.get(
        "EULER_TPU_PROFILE_DIR", "/tmp/euler_tpu_bench_trace"
    )
    history = os.path.join(bank_dir, "history.jsonl")

    def _emit(result: dict) -> dict:
        with open(history, "a") as f:
            f.write(json.dumps(
                {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 **result}
            ) + "\n")
        return result

    headline = None
    failed = False
    for name in names:
        remaining = t_end - time.monotonic()
        if remaining <= 0:
            _watchdog_exit(name)
        cap = CONFIG_CAPS.get(name, 900.0)
        result, _ = _spawn_config(
            name, min(cap, remaining), bank_dir,
            trace_dir if name == "ppi" else None,
        )
        if result is None:
            if time.monotonic() >= t_end:
                _watchdog_exit(name)
            result = _failure_line(
                name, "config subprocess produced no banked result"
            )
        failed |= "error" in result
        _emit(result)
        if name == "ppi":
            headline = result
        else:
            print(json.dumps(result), flush=True)
    if headline is not None:
        print(json.dumps(headline), flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
