"""ctypes bindings for libeuler_graph.so (built from graph/_native)."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libeuler_graph.so")

_lib = None
# The first caller builds and loads; a second thread that arrives
# meanwhile (devprof's sampler ticks a second after start-up) must wait
# for it, not start a second make over the same objects: the loser of
# that race dlopen()ed a half-written library ("file too short").
_lib_lock = threading.Lock()


def build_native(force: bool = False) -> str:
    """Bring libeuler_graph.so up to date with make and return its
    path. make owns staleness: a source newer than its object, a flavor
    switch, or objects compiled on another host (the Makefile's build
    marker — the tree is copied between machines with its ignored
    files) all rebuild, and an up-to-date tree costs one ~10 ms no-op.
    ``force`` rebuilds every object regardless (make -B)."""
    if os.environ.get("EG_NATIVE_LIB"):
        # explicit prebuilt library (scripts/sanitize.sh points this at
        # an instrumented side build): never rebuild, never second-guess
        return os.environ["EG_NATIVE_LIB"]
    marker = os.path.join(_NATIVE_DIR, ".flavor")
    flavor = "normal"
    if os.path.exists(marker):
        with open(marker) as f:
            flavor = (f.read().split() or ["normal"])[0]
    if flavor != "normal" and any(
        rt in os.environ.get("LD_PRELOAD", "")
        for rt in ("libtsan", "libasan")
    ):
        # the sanitizer runtime is preloaded: this IS the sanitizer test
        # run — keep the instrumented library (a plain make here would
        # rebuild normal and make the run pass vacuously)
        return _LIB_PATH
    subprocess.run(
        ["make", "-s", "-j"] + (["-B"] if force else []),
        cwd=_NATIVE_DIR, check=True, capture_output=True, text=True,
    )
    return _LIB_PATH


def _sig(fn, restype, argtypes) -> None:
    fn.restype = restype
    fn.argtypes = argtypes


def lib() -> ctypes.CDLL:
    """Load (building if needed) and return the native library singleton."""
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is None:
            _lib = _load()
    return _lib


def _load() -> ctypes.CDLL:
    L = ctypes.CDLL(build_native())
    c = ctypes
    p = c.c_void_p
    u64p = c.POINTER(c.c_uint64)
    i32p = c.POINTER(c.c_int32)
    f32p = c.POINTER(c.c_float)
    _sig(L.eg_last_error, c.c_char_p, [])
    _sig(L.eg_create, p, [])
    _sig(L.eg_destroy, None, [p])
    _sig(L.eg_load, c.c_int, [p, c.c_char_p, c.c_int, c.c_int])
    _sig(L.eg_load_files, c.c_int, [p, c.POINTER(c.c_char_p), c.c_int])
    _sig(L.eg_load_buffers, c.c_int,
         [p, c.POINTER(c.c_void_p), u64p, c.POINTER(c.c_char_p), c.c_int])
    _sig(L.eg_load_deltas, c.c_int, [p, c.c_char_p])
    _sig(L.eg_graph_epoch, c.c_uint64, [p])
    _sig(L.eg_seed, None, [c.c_uint64])
    _sig(L.eg_stat_count, c.c_int, [])
    _sig(L.eg_stat_name, c.c_char_p, [c.c_int])
    _sig(L.eg_stats_snapshot, None, [u64p, u64p, u64p])
    _sig(L.eg_stats_reset, None, [])
    _sig(L.eg_counter_count, c.c_int, [])
    _sig(L.eg_counter_name, c.c_char_p, [c.c_int])
    _sig(L.eg_counters_snapshot, None, [u64p])
    _sig(L.eg_counters_reset, None, [])
    # The per-step recorders are a few relaxed atomic adds: called
    # through PyDLL they keep the interpreter lock. Through CDLL every
    # call dropped it, and with prefetch workers waiting for it the
    # training thread queued behind them a dozen times a step (PERF.md
    # section 6, PR 27: 80 us a step on the v5e host).
    held = c.PyDLL(L._name)
    L._held = held
    for fn in ("eg_counter_add", "eg_phase_record", "eg_phase_gauge"):
        setattr(L, fn, getattr(held, fn))
    _sig(L.eg_counter_add, None, [c.c_int, c.c_uint64])
    _sig(L.eg_phase_record, None, [c.c_int, c.c_uint64])
    _sig(L.eg_phase_gauge, None, [c.c_int, c.c_uint64])
    _sig(L.eg_phase_tick, None, [c.c_int, c.c_int])
    _sig(L.eg_phase_ticks, c.c_int, [c.POINTER(c.c_int64)])
    _sig(L.eg_serve_record, None, [c.c_int, c.c_uint64])
    _sig(L.eg_serve_batch, None, [c.c_uint64])
    _sig(L.eg_devprof_set_mem, None, [c.c_int64, c.c_int64])
    _sig(L.eg_devprof_set_feature_table, None, [c.c_int64, c.c_int64])
    _sig(L.eg_devprof_set_store_table, None, [c.c_int64, c.c_int64])
    _sig(L.eg_devprof_set_alias_tables, None,
         [c.c_int64, c.c_int64, c.c_int64])
    _sig(L.eg_devprof_set_step_temp, None, [c.c_int64])
    _sig(L.eg_serve_slo_set, None,
         [c.c_uint64, c.c_uint64, c.c_uint64, c.c_uint64])
    _sig(L.eg_telemetry_enabled, c.c_int, [])
    _sig(L.eg_telemetry_set_enabled, None, [c.c_int])
    _sig(L.eg_telemetry_reset, None, [])
    _sig(L.eg_telemetry_set_slow_capacity, None, [c.c_int])
    _sig(L.eg_telemetry_json, c.c_int, [c.c_char_p, c.c_int])
    _sig(
        L.eg_telemetry_record_span,
        None,
        [c.c_int, c.c_int, c.c_int, c.c_int, c.c_uint64, c.c_uint64,
         c.c_uint64, c.c_uint64, c.c_uint64],
    )
    _sig(L.eg_telemetry_record_detail_span, None,
         [c.c_uint64, c.c_int64, c.c_char_p])
    _sig(L.eg_remote_ping, c.c_int, [p, c.c_int])
    _sig(L.eg_remote_scrape, c.c_int, [p, c.c_int, c.c_char_p, c.c_int])
    _sig(L.eg_remote_history, c.c_int, [p, c.c_int, c.c_char_p, c.c_int])
    _sig(L.eg_heat_enabled, c.c_int, [])
    _sig(L.eg_heat_set_enabled, None, [c.c_int])
    _sig(L.eg_heat_set_topk, None, [c.c_int])
    _sig(L.eg_heat_record, None, [c.c_int, c.c_int, u64p, c.c_int64])
    _sig(L.eg_heat_estimate, c.c_uint64, [c.c_int, c.c_uint64])
    _sig(L.eg_heat_json, c.c_int, [c.c_char_p, c.c_int])
    _sig(L.eg_heat_reset, None, [])
    _sig(L.eg_remote_heat, c.c_int, [p, c.c_int, c.c_char_p, c.c_int])
    _sig(L.eg_blackbox_enabled, c.c_int, [])
    _sig(L.eg_blackbox_set_enabled, None, [c.c_int])
    _sig(L.eg_blackbox_init, c.c_int, [c.c_char_p, c.c_int, c.c_int])
    _sig(L.eg_blackbox_stop_sampler, None, [])
    _sig(
        L.eg_blackbox_record,
        None,
        [c.c_int, c.c_int, c.c_int, c.c_uint64, c.c_uint64, c.c_int],
    )
    _sig(L.eg_blackbox_json, c.c_int, [c.c_char_p, c.c_int])
    _sig(L.eg_blackbox_history, c.c_int, [c.c_char_p, c.c_int])
    _sig(L.eg_blackbox_dump, c.c_int, [c.c_char_p])
    _sig(L.eg_blackbox_reset, None, [])
    _sig(L.eg_fault_config, c.c_int, [c.c_char_p, c.c_uint64])
    _sig(L.eg_fault_clear, None, [])
    _sig(L.eg_fault_count, c.c_int, [])
    _sig(L.eg_fault_name, c.c_char_p, [c.c_int])
    _sig(L.eg_fault_injected, None, [u64p])
    _sig(L.eg_remote_create, p, [c.c_char_p])
    _sig(L.eg_remote_shards, c.c_int, [p])
    _sig(L.eg_remote_partitions, c.c_int, [p])
    _sig(L.eg_remote_replica_count, c.c_int, [p, c.c_int])
    _sig(L.eg_remote_has_placement, c.c_int, [p])
    _sig(L.eg_remote_route, None, [p, u64p, c.c_int, i32p])
    _sig(L.eg_remote_strict_error, c.c_int, [p, c.c_char_p, c.c_int])
    _sig(L.eg_remote_epoch, c.c_uint64, [p, c.c_int])
    _sig(L.eg_remote_cache_gen, c.c_uint64, [p])
    _sig(L.eg_remote_load_delta, c.c_int64, [p, c.c_int, c.c_char_p])
    _sig(
        L.eg_remote_sample_async,
        c.c_int,
        [
            p, u64p, c.c_int, i32p, i32p, i32p, c.c_int, c.c_uint64,
            c.POINTER(u64p), c.POINTER(f32p), c.POINTER(i32p),
        ],
    )
    _sig(L.eg_remote_async_poll, c.c_int, [p, c.c_int])
    _sig(L.eg_remote_async_take, c.c_int, [p, c.c_int])
    _sig(
        L.eg_service_start,
        p,
        [c.c_char_p, c.c_int, c.c_int, c.c_char_p, c.c_int, c.c_char_p,
         c.c_char_p],
    )
    _sig(L.eg_service_port, c.c_int, [p])
    _sig(L.eg_service_drain, None, [p, c.c_int])
    _sig(L.eg_service_load_delta, c.c_int64, [p, c.c_char_p])
    _sig(L.eg_service_epoch, c.c_uint64, [p])
    _sig(L.eg_service_stop, None, [p])
    _sig(L.eg_registry_start, p, [c.c_char_p, c.c_int, c.c_int])
    _sig(L.eg_registry_port, c.c_int, [p])
    _sig(L.eg_registry_stop, None, [p])
    _sig(
        L.eg_registry_query,
        c.c_int,
        [c.c_char_p, c.c_int, c.c_int, c.c_char_p, c.c_int],
    )
    _sig(L.eg_num_nodes, c.c_int64, [p])
    _sig(L.eg_num_edges, c.c_int64, [p])
    _sig(L.eg_node_type_num, c.c_int32, [p])
    _sig(L.eg_edge_type_num, c.c_int32, [p])
    _sig(L.eg_feature_num, c.c_int32, [p, c.c_int])
    _sig(L.eg_type_weight_sums, None, [p, c.c_int, f32p])
    _sig(L.eg_sample_node, None, [p, c.c_int, c.c_int32, u64p])
    _sig(L.eg_sample_edge, None, [p, c.c_int, c.c_int32, u64p, u64p, i32p])
    _sig(L.eg_sample_node_with_src, None, [p, u64p, c.c_int, c.c_int, u64p])
    _sig(L.eg_get_node_type, None, [p, u64p, c.c_int, i32p])
    _sig(L.eg_get_node_weight, c.c_int, [p, u64p, c.c_int, f32p])
    _sig(
        L.eg_sample_neighbor,
        None,
        [p, u64p, c.c_int, i32p, c.c_int, c.c_int, c.c_uint64, u64p, f32p, i32p],
    )
    _sig(
        L.eg_sample_fanout,
        None,
        [
            p, u64p, c.c_int, i32p, i32p, i32p, c.c_int, c.c_uint64,
            c.POINTER(u64p), c.POINTER(f32p), c.POINTER(i32p),
        ],
    )
    _sig(
        L.eg_build_alias_csr,
        None,
        [c.POINTER(c.c_int64), c.c_int64, f32p, f32p, i32p],
    )
    _sig(L.eg_get_full_neighbor, p, [p, u64p, c.c_int, i32p, c.c_int, c.c_int])
    _sig(
        L.eg_get_top_k_neighbor,
        None,
        [p, u64p, c.c_int, i32p, c.c_int, c.c_int, c.c_uint64, u64p, f32p, i32p],
    )
    _sig(
        L.eg_random_walk,
        None,
        [p, u64p, c.c_int, i32p, i32p, c.c_int, c.c_float, c.c_float,
         c.c_uint64, u64p],
    )
    _sig(
        L.eg_get_dense_feature,
        None,
        [p, u64p, c.c_int, i32p, i32p, c.c_int, f32p],
    )
    _sig(
        L.eg_get_edge_dense_feature,
        None,
        [p, u64p, u64p, i32p, c.c_int, i32p, i32p, c.c_int, f32p],
    )
    _sig(L.eg_get_sparse_feature, p, [p, u64p, c.c_int, i32p, c.c_int])
    _sig(
        L.eg_get_edge_sparse_feature,
        p,
        [p, u64p, u64p, i32p, c.c_int, i32p, c.c_int],
    )
    _sig(L.eg_get_binary_feature, p, [p, u64p, c.c_int, i32p, c.c_int])
    _sig(
        L.eg_get_edge_binary_feature,
        p,
        [p, u64p, u64p, i32p, c.c_int, i32p, c.c_int],
    )
    _sig(L.eg_result_size, c.c_int64, [p, c.c_int, c.c_int])
    _sig(L.eg_result_copy, None, [p, c.c_int, c.c_int, p])
    _sig(L.eg_result_free, None, [p])
    return L


def stats() -> dict:
    """Snapshot of the native span-timer accumulators (process-global:
    embedded engine calls, remote client round-trips, and served shard
    requests all record here — see _native/eg_stats.h). Returns
    {op: {count, total_ms, avg_us, max_us}} for ops with count > 0."""
    import numpy as np

    L = lib()
    n = L.eg_stat_count()
    counts = np.zeros(n, dtype=np.uint64)
    total = np.zeros(n, dtype=np.uint64)
    mx = np.zeros(n, dtype=np.uint64)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    L.eg_stats_snapshot(
        counts.ctypes.data_as(u64p),
        total.ctypes.data_as(u64p),
        mx.ctypes.data_as(u64p),
    )
    out = {}
    for i in range(n):
        if counts[i] == 0:
            continue
        name = L.eg_stat_name(i).decode()
        out[name] = {
            "count": int(counts[i]),
            "total_ms": float(total[i]) / 1e6,
            "avg_us": float(total[i]) / float(counts[i]) / 1e3,
            "max_us": float(mx[i]) / 1e3,
        }
    return out


def stats_reset() -> None:
    """Zero the native span-timer accumulators."""
    lib().eg_stats_reset()


def counters() -> dict:
    """Snapshot of the native counters (process-global, see
    _native/eg_stats.h Counters). Failure side — how often the remote
    transport had to fight for an answer: {"dials_failed": n,
    "retries": n, "quarantines": n, "failovers": n, "calls_failed": n,
    "deadlines_exceeded": n, "frames_rejected": n, "rediscoveries": n,
    "heartbeat_misses": n, "rpc_errors": n}. Efficiency side — the
    remote hot path's communication-win ledger: {"ids_deduped": n,
    "cache_hits": n, "cache_misses": n, "rpc_chunks": n}
    (ids_on_wire = ids_requested - ids_deduped - cache_hits; see
    FAULTS.md for per-counter semantics). Snapshot-epoch side —
    the graph-refresh ledger: {"epoch_flips": n, "epoch_drains": n,
    "epoch_stale_hits_evicted": n, "delta_loads_failed": n} (flips ==
    drains once quiescent; see FAULTS.md). All keys always present (zero
    included), so dashboards and the chaos soak can diff snapshots
    without key existence checks."""
    L = lib()
    n = L.eg_counter_count()
    arr = (ctypes.c_uint64 * n)()
    L.eg_counters_snapshot(arr)
    return {L.eg_counter_name(i).decode(): int(arr[i]) for i in range(n)}


def reset_counters() -> None:
    """Zero the native failure/efficiency counters (process-global) —
    the clean-slate primitive tests and benches use instead of
    before/after delta arithmetic over :func:`counters` snapshots."""
    lib().eg_counters_reset()


# older spelling, kept so existing callers and muscle memory both work
counters_reset = reset_counters

_counter_ids: dict = {}


def counter_add(name: str, n: int = 1) -> None:
    """Bump one native counter by name (the prefetch pipeline's Python
    threads account into the same ledger the native transport uses, so
    one :func:`counters` snapshot or STATS scrape covers both).
    Raises KeyError on an unknown counter name."""
    if not _counter_ids:
        L = lib()
        # one update() of a finished dict: prefetch workers call this
        # concurrently, and a table filled entry by entry looks
        # non-empty — and incomplete — to the second caller
        _counter_ids.update({
            L.eg_counter_name(i).decode(): i
            for i in range(L.eg_counter_count())
        })
    lib().eg_counter_add(_counter_ids[name], n)


def fault_config(spec: str, seed: int = 0) -> None:
    """Install a process-global deterministic failpoint spec (FAULTS.md),
    e.g. ``recv_frame:err@0.5,dial:delay@200``. ``seed`` makes each
    failpoint's failure sequence replayable: the same seed fires the
    same pattern of faults at each point. Raises ValueError on a
    malformed spec (nothing installed). An empty spec clears."""
    rc = lib().eg_fault_config(spec.encode(), seed)
    if rc != 0:
        raise ValueError(lib().eg_last_error().decode())


def fault_clear() -> None:
    """Remove every installed failpoint (back to the zero-cost path)."""
    lib().eg_fault_clear()


def fault_injected() -> dict:
    """Injected-fault ledger: {failpoint: fires since its last config},
    all failpoints always present — the ground truth the failure
    counters are audited against in the chaos soak."""
    L = lib()
    n = L.eg_fault_count()
    arr = (ctypes.c_uint64 * n)()
    L.eg_fault_injected(arr)
    return {L.eg_fault_name(i).decode(): int(arr[i]) for i in range(n)}
