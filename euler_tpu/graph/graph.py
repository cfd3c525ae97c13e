"""Embedded graph client: a Python facade over the native engine returning
fixed-shape numpy arrays ready for the TPU input pipeline.

Role equivalent of the reference client stack in Local mode
(reference euler/client/graph.h:47 + local_graph.cc + the 17 custom TF ops in
tf_euler/ops and kernels) — but synchronous-batch instead of callback-async,
because the TPU design overlaps sampling with device compute through a
prefetch thread pool rather than through per-op async kernels. All ids are
int64 on the Python side (JAX-friendly); the native layer works in uint64 and
the bit patterns pass through unchanged (default ids like -1 wrap).
"""

from __future__ import annotations

import ctypes
import os
import threading

import numpy as np

from euler_tpu.graph.native import lib

# Feature-kind selectors of the C ABI (eg_capi.cc eg_feature_num).
NODE_U64, NODE_F32, NODE_BIN, EDGE_U64, EDGE_F32, EDGE_BIN = range(6)

_U64P = ctypes.POINTER(ctypes.c_uint64)
_F32P = ctypes.POINTER(ctypes.c_float)
_I32P = ctypes.POINTER(ctypes.c_int32)


def _ids(a) -> np.ndarray:
    """Accept any integer array-like; reinterpret int64 as uint64 bits."""
    arr = np.ascontiguousarray(np.asarray(a).reshape(-1))
    if arr.dtype == np.uint64:
        return arr
    return arr.astype(np.int64, copy=False).view(np.uint64)


def _i32(a) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(a, dtype=np.int32).reshape(-1))


def _ptr(a: np.ndarray, ty):
    return a.ctypes.data_as(ty)


def _default_u64(default_node: int) -> int:
    return int(np.int64(default_node).view(np.uint64))


def _partition_bytes(directory: str, shard_idx: int, shard_num: int) -> int:
    """Bytes of the ``.dat`` partitions the native loader parses from a
    local directory (its rule: remote_fs.in_shard); 0 where the
    directory cannot be listed (the load then says why)."""
    from euler_tpu.graph.remote_fs import in_shard

    try:
        return sum(
            os.path.getsize(os.path.join(directory, name))
            for name in os.listdir(directory)
            if in_shard(name, shard_idx, shard_num)
        )
    except OSError:
        return 0


def str2bool(v) -> bool:
    """ONE truthy-string rule for every bool that can arrive as text
    (config strings here, CLI flags in run_loop) — two parsers with
    different accepted spellings is how `stream=y` silently stages to
    disk while `--stream y` streams."""
    return str(v).lower() in ("1", "true", "yes", "y")


def parse_config(source: str) -> dict:
    """Parse a client config: a ``.ini``-style file of ``key = value``
    lines ('#'/';' comments, optional [sections] ignored) or an inline
    ``k=v;k=v`` string. Values that look numeric come back as ints.

    Role equivalent of the reference's GraphConfig loader
    (reference euler/client/graph_config.cc:33-56) plus the semicolon
    string form used across its C ABI (create_graph.cc:50-60).
    """
    # a path wins over the inline form when both could apply (paths may
    # legitimately contain '='; inline strings are never existing files)
    if os.path.exists(source) or "=" not in source:
        with open(source) as f:
            lines = f.read().splitlines()
    else:
        lines = source.split(";")
    out: dict = {}
    for line in lines:
        line = line.strip()
        if not line or line[0] in "#;[":
            continue
        if "=" not in line:
            raise ValueError(f"bad config line (want key=value): {line!r}")
        k, v = (s.strip() for s in line.split("=", 1))
        try:
            out[k] = int(v)
        except ValueError:
            out[k] = v
    return out


class Graph:
    """Graph client: embedded engine (mode='local') or sharded remote
    client (mode='remote').

    Mode selection mirrors the reference factory Graph::NewGraph
    (reference euler/client/graph.cc:157-185): local embeds the engine
    in-process; remote discovers shards from a ``registry`` (flat-file
    directory written by :class:`euler_tpu.graph.GraphService`, or
    ``tcp://host:port`` of a euler_tpu.graph.registry server) or an
    explicit ``shards`` list, routes ids shard(id) = (id % P) % S, and
    merges scatter/gather replies — all in native code (eg_remote.cc).

    Like the reference, the client also takes a config file or inline
    config string (``config=``: ``key = value`` lines or ``k=v;k=v``,
    graph_config.cc:33-56) with explicit kwargs taking precedence, and
    ``init="lazy"`` defers engine construction to first use
    (graph.cc:176-183).
    """

    def __init__(
        self,
        directory: str | None = None,
        files: list[str] | None = None,
        shard_idx: int | None = None,
        shard_num: int | None = None,
        mode: str | None = None,
        registry: str | None = None,
        shards: list[str] | list[list[str]] | None = None,
        retries: int | None = None,
        timeout_ms: int | None = None,
        quarantine_ms: int | None = None,
        rediscover_ms: int | None = None,
        backoff_ms: int | None = None,
        deadline_ms: int | None = None,
        fault: str | None = None,
        fault_seed: int | None = None,
        feature_cache_mb: int | None = None,
        neighbor_cache_mb: int | None = None,
        cache_policy: str | None = None,
        placement: bool | None = None,
        strict: bool | None = None,
        coalesce: bool | None = None,
        chunk_ids: int | None = None,
        dispatch_workers: int | None = None,
        wire_version: int | None = None,
        telemetry: bool | None = None,
        slow_spans: int | None = None,
        heat: bool | None = None,
        heat_topk: int | None = None,
        blackbox: bool | None = None,
        devprof: bool | None = None,
        postmortem_dir: str | None = None,
        cache_dir: str | None = None,
        stream: bool | None = None,
        delta: str | list[str] | None = None,
        config: str | None = None,
        init: str | None = None,
    ):
        self._lib = lib()
        self._handle = None
        self._closed = False
        self._connect_lock = threading.Lock()
        # config file / inline string (reference Graph::NewGraph(filename),
        # euler/client/graph.cc:163-185); explicit kwargs override it
        cfg = parse_config(config) if config else {}
        known = {
            "directory", "files", "shard_idx", "shard_num", "mode",
            "registry", "shards", "retries", "timeout_ms", "quarantine_ms",
            "rediscover_ms", "backoff_ms", "deadline_ms", "fault",
            "fault_seed", "feature_cache_mb", "neighbor_cache_mb",
            "cache_policy", "placement", "strict", "coalesce",
            "chunk_ids", "dispatch_workers", "wire_version", "telemetry",
            "slow_spans", "heat", "heat_topk", "blackbox", "devprof",
            "postmortem_dir", "cache_dir", "stream", "delta", "init",
        }
        unknown = set(cfg) - known
        if unknown:
            # only a fixed key set is consumed — a typo'd key would
            # otherwise be dropped silently (e.g. timout_ms)
            raise ValueError(
                f"unknown config keys {sorted(unknown)}; valid: "
                f"{sorted(known)}"
            )

        def pick(name, explicit, default):
            return explicit if explicit is not None else cfg.get(name, default)

        directory = pick("directory", directory, None)
        files = pick("files", files, None)
        if isinstance(files, str):
            files = [s.strip() for s in files.split(",")]
        shard_idx = int(pick("shard_idx", shard_idx, 0))
        shard_num = int(pick("shard_num", shard_num, 1))
        mode = str(pick("mode", mode, "local")).lower()
        registry = pick("registry", registry, None)
        shards = pick("shards", shards, None)
        if isinstance(shards, str):
            shards = [s.strip() for s in shards.split(",")]
        retries = int(pick("retries", retries, 3))
        timeout_ms = int(pick("timeout_ms", timeout_ms, 5000))
        quarantine_ms = int(pick("quarantine_ms", quarantine_ms, 3000))
        # mid-run registry re-LIST period (native RediscoverLoop); None =
        # the native default (3000 ms with a registry, off for shards=)
        rediscover_ms = pick("rediscover_ms", rediscover_ms, None)
        # retry pacing (native ConnPool::Call): base of the jittered
        # exponential backoff, and the overall per-call deadline spanning
        # all retries; None = native defaults (20 ms / timeout*(retries+1))
        backoff_ms = pick("backoff_ms", backoff_ms, None)
        deadline_ms = pick("deadline_ms", deadline_ms, None)
        # deterministic transport failpoints (FAULTS.md), e.g.
        # "recv_frame:err@0.5,dial:delay@200"; process-global
        fault = pick("fault", fault, None)
        fault_seed = pick("fault_seed", fault_seed, None)
        # remote hot-path knobs (native defaults apply when None):
        # feature_cache_mb (64; 0 off) bounds the client-side dense-
        # feature-row cache, strict (0) raises on a shard that failed
        # after all transport retries instead of training on defaults,
        # coalesce (1) dedups duplicate ids before wire encode,
        # chunk_ids (16384) splits large per-shard requests into
        # concurrent chunks, dispatch_workers (auto) sizes the
        # persistent dispatcher pool
        feature_cache_mb = pick("feature_cache_mb", feature_cache_mb, None)
        # locality knobs (ROADMAP item 5; native defaults apply when
        # None): neighbor_cache_mb (16; 0 off) bounds the client-side
        # neighbor-list cache — hot nodes' adjacency slices sampled
        # locally instead of per-hop wire trips; cache_policy
        # ("freq"|"fifo", default freq) selects TinyLFU-shaped vs
        # unconditional admission for BOTH client caches; placement
        # (True) fetches the shard's id->partition map at init and
        # routes through it, hash fallback when no map exists
        neighbor_cache_mb = pick("neighbor_cache_mb", neighbor_cache_mb,
                                 None)
        cache_policy = pick("cache_policy", cache_policy, None)
        placement = pick("placement", placement, None)
        if isinstance(placement, str):
            placement = str2bool(placement)
        strict = pick("strict", strict, None)
        if isinstance(strict, str):
            strict = str2bool(strict)
        coalesce = pick("coalesce", coalesce, None)
        if isinstance(coalesce, str):
            coalesce = str2bool(coalesce)
        chunk_ids = pick("chunk_ids", chunk_ids, None)
        dispatch_workers = pick("dispatch_workers", dispatch_workers, None)
        # wire_version=1 emulates a pre-envelope client (compat drills /
        # operational escape hatch), 2 forces the v2 deadline envelope;
        # None = negotiate per replica (old servers are auto-downgraded,
        # counted in wire_downgrades)
        wire_version = pick("wire_version", wire_version, None)
        # observability (eg_telemetry.h; process-global like fault=):
        # telemetry=0 kills histogram/slow-span recording, slow_spans=
        # resizes the slowest-N journal
        telemetry = pick("telemetry", telemetry, None)
        if isinstance(telemetry, str):
            telemetry = str2bool(telemetry)
        slow_spans = pick("slow_spans", slow_spans, None)
        # data-plane heat profiler (eg_heat.h; process-global like
        # telemetry=): heat=0 stops id feeds / fan-out attribution /
        # cache-class recording, heat_topk= resizes the hot-key tracker
        heat = pick("heat", heat, None)
        if isinstance(heat, str):
            heat = str2bool(heat)
        heat_topk = pick("heat_topk", heat_topk, None)
        # blackbox flight recorder + postmortem dump path
        # (eg_blackbox.h; process-global like telemetry=, but valid in
        # BOTH modes — an embedded-engine trainer crashes too, and its
        # postmortem is exactly as valuable as a shard's)
        blackbox = pick("blackbox", blackbox, None)
        if isinstance(blackbox, str):
            blackbox = str2bool(blackbox)
        # device-plane observability (eg_devprof.h; process-global like
        # blackbox=, valid in BOTH modes — an embedded-engine trainer
        # compiles and recompiles XLA programs exactly like a remote one)
        devprof = pick("devprof", devprof, None)
        if isinstance(devprof, str):
            devprof = str2bool(devprof)
        postmortem_dir = pick("postmortem_dir", postmortem_dir, None)
        cache_dir = pick("cache_dir", cache_dir, None)
        stream = pick("stream", stream, False)
        if isinstance(stream, str):
            stream = str2bool(stream)
        # snapshot-epoch delta files (eg_epoch.h; `<prefix>.delta.<n>`,
        # see convert.py --delta-from): applied over the base load at
        # connect, leaving the engine at epoch = len(delta)
        delta = pick("delta", delta, None)
        if isinstance(delta, str):
            delta = [s.strip() for s in delta.replace(";", ",").split(",")
                     if s.strip()]
        init = str(pick("init", init, "eager")).lower()
        if mode not in ("local", "remote"):
            raise ValueError("mode must be 'local' or 'remote'")
        if directory is not None and files:
            # never dropped silently: the load dispatch would consume
            # directory= and ignore the file list entirely
            raise ValueError(
                "pass directory= OR files=, not both (the embedded "
                "engine loads exactly one of them; a files= list next "
                "to directory= would be silently ignored)"
            )
        if fault_seed is not None and fault is None:
            raise ValueError(
                "fault_seed= without fault= would seed nothing — pass the "
                "failpoint spec too (FAULTS.md)"
            )
        if fault is not None and mode != "remote":
            # the failpoints live in the TCP transport; accepting the key
            # on a local graph would just mislead (nothing would fire)
            raise ValueError(
                "fault= applies to mode='remote' graphs (failpoints sit "
                "in the transport, see FAULTS.md; for service-side "
                "injection use euler_tpu.graph.native.fault_config in "
                "the shard process)"
            )
        if mode != "remote":
            # same loudness rule: these keys configure the remote client's
            # wire path (dedup, cache, chunking, dispatcher, strict shard
            # failures); an embedded engine has no wire, so accepting
            # them would silently do nothing
            for key, val in (
                ("feature_cache_mb", feature_cache_mb), ("strict", strict),
                ("neighbor_cache_mb", neighbor_cache_mb),
                ("cache_policy", cache_policy), ("placement", placement),
                ("coalesce", coalesce), ("chunk_ids", chunk_ids),
                ("dispatch_workers", dispatch_workers),
                ("wire_version", wire_version),
                ("telemetry", telemetry), ("slow_spans", slow_spans),
                ("heat", heat), ("heat_topk", heat_topk),
            ):
                if val is not None:
                    raise ValueError(
                        f"{key}= applies to mode='remote' graphs (it "
                        "configures the remote client's request path; "
                        "the embedded engine reads local memory)"
                    )
        if delta and mode != "local":
            # never dropped silently: a remote client holds no graph data
            # to merge — shards apply their own deltas (Graph.load_delta
            # per shard, or `service --load_delta`)
            raise ValueError(
                "delta= applies to mode='local' graphs (remote shards "
                "merge their own delta files — use load_delta(path, "
                "shard=...) or `python -m euler_tpu.graph.service "
                "--load_delta`; see DEPLOY.md 'Rolling graph refresh')"
            )
        if stream and mode != "local":
            # never dropped silently: remote mode reads no graph data
            # itself, so accepting the flag would just mislead
            raise ValueError(
                "stream=True applies to mode='local' graphs "
                "(remote-mode clients read from shard services, which "
                "stage their own data; see DEPLOY.md 'Remote data')"
            )
        if init not in ("eager", "lazy"):
            raise ValueError("init must be 'eager' or 'lazy'")
        # graph init arms the blackbox (the service arms it on its own
        # side): kill-switch first, then the postmortem path — BEFORE
        # the engine/remote handle exists, so even a crash during load
        # or discovery leaves a dump
        if blackbox is not None:
            from euler_tpu import blackbox as _blackbox

            _blackbox.set_blackbox(bool(blackbox))
        if devprof is not None:
            from euler_tpu import devprof as _devprof

            _devprof.set_devprof(bool(devprof))
            if devprof:
                _devprof.install()
        if postmortem_dir is not None:
            from euler_tpu import blackbox as _blackbox

            _blackbox.install(postmortem_dir)
        self._params = dict(
            directory=directory, files=files, shard_idx=shard_idx,
            shard_num=shard_num, registry=registry, shards=shards,
            retries=retries, timeout_ms=timeout_ms,
            quarantine_ms=quarantine_ms, rediscover_ms=rediscover_ms,
            backoff_ms=backoff_ms, deadline_ms=deadline_ms,
            fault=fault, fault_seed=fault_seed,
            feature_cache_mb=feature_cache_mb,
            neighbor_cache_mb=neighbor_cache_mb,
            cache_policy=cache_policy, placement=placement,
            strict=strict,
            coalesce=coalesce, chunk_ids=chunk_ids,
            dispatch_workers=dispatch_workers, wire_version=wire_version,
            telemetry=telemetry, slow_spans=slow_spans, heat=heat,
            heat_topk=heat_topk, cache_dir=cache_dir, stream=bool(stream),
            delta=delta,
        )
        self.mode = mode
        self._strict = bool(strict) if strict is not None else False
        # local-mode delta chain applied so far (load_delta re-sends the
        # whole chain per flip; seeded by the delta= config key)
        self._applied_deltas: list[str] = list(delta) if delta else []
        if init == "eager":
            self._connect()

    @property
    def _h(self):
        """Native handle; a lazy-init graph connects on first use
        (reference init=lazy, graph.cc:176-183). Thread-safe: concurrent
        first users (prefetch workers) connect exactly once."""
        if self._handle is None:
            with self._connect_lock:
                if self._handle is None:
                    self._connect()
        return self._handle

    def _connect(self) -> None:
        if self._closed:
            # close() must be final: a lingering reference (say a prefetch
            # thread) must not silently re-load the store or re-dial the
            # cluster through the lazy property
            raise RuntimeError("graph is closed")
        p = self._params
        directory = p["directory"]
        files = p["files"]
        shard_idx, shard_num = p["shard_idx"], p["shard_num"]
        registry, shards = p["registry"], p["shards"]
        cache_dir = p["cache_dir"]
        retries = p["retries"]
        timeout_ms, quarantine_ms = p["timeout_ms"], p["quarantine_ms"]
        mode = self.mode
        # Remote filesystems (the reference reads graph data straight off
        # HDFS, euler/common/hdfs_file_io.cc:79-80): any fsspec URL is
        # staged shard-aware to a local cache, then loaded through the one
        # fast local path (see euler_tpu/graph/remote_fs.py).
        from euler_tpu.graph import remote_fs

        buffers = None
        if mode == "local":
            # directory=/files= are only consumed by the embedded engine;
            # remote mode must not stage data it will never read
            if directory is not None:
                if remote_fs.is_remote_path(directory):
                    if p["stream"]:
                        # streaming ingest: fetch partition bytes to
                        # memory and parse them directly — zero local
                        # disk (the reference likewise streams off HDFS
                        # without staging, hdfs_file_io.cc:79-80)
                        buffers = remote_fs.read_directory(
                            directory,
                            shard_idx=shard_idx,
                            shard_num=shard_num,
                        )
                    else:
                        directory = remote_fs.stage_directory(
                            directory,
                            cache_dir=cache_dir,
                            shard_idx=shard_idx,
                            shard_num=shard_num,
                        )
                    # staging already applied the shard selection; the
                    # native re-filter on the staged names is a no-op
                else:
                    directory = remote_fs.strip_local_scheme(directory)
            if files and directory is None:
                # directory= wins at the load dispatch below; fetching
                # or staging a files= list that will then be ignored is
                # pure waste (and under stream=, RAM)
                if p["stream"]:
                    # stream= must never be dropped silently (the
                    # scratch-poor operator would stage to disk anyway
                    # and hit ENOSPC with no hint why)
                    buffers = remote_fs.read_files(files)
                else:
                    files = remote_fs.stage_files(
                        files, cache_dir=cache_dir
                    )
        if (
            registry is not None
            and not registry.startswith("tcp://")
            and remote_fs.is_remote_path(registry)
        ):
            raise NotImplementedError(
                f"registry on a remote filesystem is not supported "
                f"({registry}); the registry is a liveness-watched "
                "directory — use a local/NFS path, tcp://host:port of a "
                "euler_tpu.graph.registry server, or an explicit "
                "shards= list"
            )
        if mode == "remote":
            if registry:
                conf = f"registry={registry}"
            elif shards:
                # each entry: an address, or a list of replica addresses
                parts = [
                    s if isinstance(s, str) else "|".join(s) for s in shards
                ]
                conf = "shards=" + ",".join(parts)
            else:
                raise ValueError("remote mode needs registry= or shards=")
            conf += (
                f";retries={retries};timeout_ms={timeout_ms}"
                f";quarantine_ms={quarantine_ms}"
            )
            if p["rediscover_ms"] is not None:
                conf += f";rediscover_ms={int(p['rediscover_ms'])}"
            if p["backoff_ms"] is not None:
                conf += f";backoff_ms={int(p['backoff_ms'])}"
            if p["deadline_ms"] is not None:
                conf += f";deadline_ms={int(p['deadline_ms'])}"
            if p["feature_cache_mb"] is not None:
                conf += f";feature_cache_mb={int(p['feature_cache_mb'])}"
            if p["neighbor_cache_mb"] is not None:
                conf += f";neighbor_cache_mb={int(p['neighbor_cache_mb'])}"
            if p["cache_policy"] is not None:
                conf += f";cache_policy={p['cache_policy']}"
            if p["placement"] is not None:
                conf += f";placement={1 if p['placement'] else 0}"
            if p["strict"] is not None:
                conf += f";strict={1 if p['strict'] else 0}"
            if p["coalesce"] is not None:
                conf += f";coalesce={1 if p['coalesce'] else 0}"
            if p["chunk_ids"] is not None:
                conf += f";chunk_ids={int(p['chunk_ids'])}"
            if p["dispatch_workers"] is not None:
                conf += f";dispatch_workers={int(p['dispatch_workers'])}"
            if p["wire_version"] is not None:
                conf += f";wire_version={int(p['wire_version'])}"
            if p["telemetry"] is not None:
                conf += f";telemetry={1 if p['telemetry'] else 0}"
            if p["slow_spans"] is not None:
                conf += f";slow_spans={int(p['slow_spans'])}"
            if p["heat"] is not None:
                conf += f";heat={1 if p['heat'] else 0}"
            if p["heat_topk"] is not None:
                conf += f";heat_topk={int(p['heat_topk'])}"
            if p["fault"] is not None:
                # ';' is the k=v separator, so the fault grammar uses ','
                # between failpoints (FAULTS.md)
                conf += f";fault={p['fault']}"
                if p["fault_seed"] is not None:
                    conf += f";fault_seed={int(p['fault_seed'])}"
            self._handle = self._lib.eg_remote_create(conf.encode())
            if not self._handle:
                self._handle = None
                err = self._lib.eg_last_error().decode()
                raise RuntimeError(f"remote graph init failed: {err}")
            return
        # (telemetry imports this package's native loader: not at the top)
        from euler_tpu.telemetry import setup_span

        h = self._lib.eg_create()
        if buffers is not None:
            n = len(buffers)
            names = (ctypes.c_char_p * n)(
                *[name.encode() for name, _ in buffers]
            )
            bufs = (ctypes.c_void_p * n)()
            lens = (ctypes.c_uint64 * n)()
            for i, (_, blob) in enumerate(buffers):
                bufs[i] = ctypes.cast(
                    ctypes.c_char_p(blob), ctypes.c_void_p
                )
                lens[i] = len(blob)
            # `buffers` stays referenced through the call; the engine
            # copies during parse, so the bytes can drop right after
            with setup_span("setup_graph_load", sum(lens)):
                rc = self._lib.eg_load_buffers(h, bufs, lens, names, n)
        elif directory is not None:
            with setup_span(
                "setup_graph_load",
                _partition_bytes(directory, shard_idx, shard_num),
            ):
                rc = self._lib.eg_load(
                    h, directory.encode(), shard_idx, shard_num
                )
        elif files:
            arr = (ctypes.c_char_p * len(files))(*[f.encode() for f in files])
            with setup_span(
                "setup_graph_load", sum(map(os.path.getsize, files))
            ):
                rc = self._lib.eg_load_files(h, arr, len(files))
        else:
            self._lib.eg_destroy(h)
            raise ValueError("pass directory= or files=")
        if rc != 0:
            err = self._lib.eg_last_error().decode()
            self._lib.eg_destroy(h)
            raise RuntimeError(f"graph load failed: {err}")
        if p.get("delta"):
            # merge the delta chain over the fresh base: a failed merge
            # fails the whole connect (a graph silently missing its
            # updates is worse than no graph)
            joined = ";".join(p["delta"])
            if self._lib.eg_load_deltas(h, joined.encode()) != 0:
                err = self._lib.eg_last_error().decode()
                self._lib.eg_destroy(h)
                raise RuntimeError(f"delta load failed: {err}")
        self._handle = h

    @property
    def num_shards(self) -> int:
        return (
            self._lib.eg_remote_shards(self._h) if self.mode == "remote" else 1
        )

    @property
    def num_partitions(self) -> int:
        return (
            self._lib.eg_remote_partitions(self._h)
            if self.mode == "remote"
            else 1
        )

    def num_replicas(self, shard: int) -> int:
        """Current replica count of one shard's connection pool (remote
        mode) — observability for mid-run re-discovery."""
        if self.mode != "remote":
            return 1
        return self._lib.eg_remote_replica_count(self._h, shard)

    @property
    def has_placement(self) -> bool:
        """True when this remote client routes ids through a placement
        map fetched at init (kPlacement; see convert.py's degree-aware
        partitioner), False when it hash-routes — the compat fallback
        against old servers and hash-sharded data."""
        if self.mode != "remote":
            return False
        return self._lib.eg_remote_has_placement(self._h) == 1

    def shard_of(self, ids) -> np.ndarray:
        """Serving shard of each id through the client's ACTUAL routing
        (placement map when loaded, hash fallback otherwise). The
        edge-cut instrument (scripts/heat_dump.py --probe) measures
        locality with this instead of re-deriving the hash rule, so a
        placement-routed cluster is measured by the routing it uses."""
        if self.mode != "remote":
            raise ValueError(
                "shard_of() applies to mode='remote' graphs (a local "
                "graph has no shards to route to)"
            )
        arr = _ids(ids)
        out = np.empty(len(arr), dtype=np.int32)
        self._lib.eg_remote_route(
            self._h, _ptr(arr, _U64P), len(arr), _ptr(out, _I32P)
        )
        return out

    # ---- snapshot epochs (eg_epoch.h; DEPLOY.md "Rolling graph
    # refresh") ----
    def epoch(self) -> int:
        """Current snapshot epoch. Local: the epoch the embedded engine's
        snapshot was built at (0 = base load, N = after N deltas).
        Remote: the max epoch any shard has announced so far — learned
        passively from v4 reply stamps and registry heartbeats, so it
        can lag a fresh flip by one call/poll."""
        return int(self._lib.eg_graph_epoch(self._h))

    def shard_epoch(self, shard: int) -> int:
        """Last epoch announced by one shard (remote mode; 0 = never
        flipped or not yet observed)."""
        if self.mode != "remote":
            raise ValueError(
                "shard_epoch() applies to mode='remote' graphs (a local "
                "graph has exactly one epoch — use epoch())"
            )
        return int(self._lib.eg_remote_epoch(self._h, shard))

    @property
    def cache_gen(self) -> int:
        """The client's cache generation (remote mode; 0 for local):
        bumped once per observed epoch raise on any shard. Python-side
        caches (euler_tpu/serving/microbatch.py) key entries by this,
        exactly like the native feature/neighbor caches."""
        if self.mode != "remote":
            return 0
        return int(self._lib.eg_remote_cache_gen(self._h))

    def load_delta(self, path: str, shard: int | None = None) -> int:
        """Apply one delta file and flip to a fresh snapshot; returns the
        new epoch.

        Local graphs take the delta path directly (shard= must be None).
        Remote graphs ask ONE shard to merge a file on the SHARD's
        filesystem (shard= required) — roll through shards one at a time
        so the previous-epoch window covers in-flight multi-hop reads
        (DEPLOY.md 'Rolling graph refresh'). Raises on parse/validation/
        merge failure; the serving snapshot is untouched on failure."""
        if self.mode == "remote":
            if shard is None:
                raise ValueError(
                    "remote load_delta needs shard= (each shard merges "
                    "its own delta file; roll through shards in turn)"
                )
            ep = self._lib.eg_remote_load_delta(
                self._h, int(shard), path.encode()
            )
            if ep < 0:
                raise RuntimeError(self._lib.eg_last_error().decode())
            return int(ep)
        if shard is not None:
            raise ValueError(
                "shard= applies to mode='remote' graphs (a local graph "
                "merges the delta into its own embedded engine)"
            )
        # the native merge rebuilds base + the WHOLE chain (epoch = chain
        # length), so successive local flips re-send every delta applied
        # so far — the flipped snapshot stays bit-identical to a fresh
        # load of the same merged inputs
        chain = list(self._applied_deltas) + [path]
        joined = ";".join(chain)
        if self._lib.eg_load_deltas(self._h, joined.encode()) != 0:
            raise RuntimeError(self._lib.eg_last_error().decode())
        self._applied_deltas = chain
        return self.epoch()

    def _check_strict(self):
        """Raise the pending strict-mode failure, if any. With
        ``strict=True`` (remote graphs) a shard call that exhausted every
        transport retry must surface as an error instead of silently
        degrading its rows to defaults; the fixed-shape native query ABI
        returns void, so the failure crosses the C ABI through this poll
        (eg_remote_strict_error; counted in `rpc_errors`, FAULTS.md)."""
        if not self._strict:
            return
        buf = ctypes.create_string_buffer(512)
        if self._lib.eg_remote_strict_error(self._handle, buf, 512) > 0:
            raise RuntimeError(buf.value.decode())

    def close(self) -> None:
        # touch _handle, not _h: closing a lazy graph must not connect it
        self._closed = True
        if getattr(self, "_handle", None):
            self._lib.eg_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # ---- introspection ----
    @property
    def num_nodes(self) -> int:
        return self._lib.eg_num_nodes(self._h)

    @property
    def num_edges(self) -> int:
        return self._lib.eg_num_edges(self._h)

    @property
    def node_type_num(self) -> int:
        return self._lib.eg_node_type_num(self._h)

    @property
    def edge_type_num(self) -> int:
        return self._lib.eg_edge_type_num(self._h)

    def feature_num(self, kind: int) -> int:
        return self._lib.eg_feature_num(self._h, kind)

    def type_weight_sums(self, edges: bool = False) -> np.ndarray:
        n = self.edge_type_num if edges else self.node_type_num
        out = np.zeros(n, dtype=np.float32)
        if n:
            self._lib.eg_type_weight_sums(
                self._h, 1 if edges else 0, _ptr(out, _F32P)
            )
        return out

    # ---- global sampling ----
    def sample_node(self, count: int, node_type: int = -1) -> np.ndarray:
        out = np.empty(count, dtype=np.uint64)
        self._lib.eg_sample_node(self._h, count, node_type, _ptr(out, _U64P))
        self._check_strict()
        return out.view(np.int64)

    def sample_edge(self, count: int, edge_type: int = -1):
        src = np.empty(count, dtype=np.uint64)
        dst = np.empty(count, dtype=np.uint64)
        t = np.empty(count, dtype=np.int32)
        self._lib.eg_sample_edge(
            self._h, count, edge_type, _ptr(src, _U64P), _ptr(dst, _U64P),
            _ptr(t, _I32P),
        )
        self._check_strict()
        return src.view(np.int64), dst.view(np.int64), t

    def sample_node_with_src(self, src_ids, count: int) -> np.ndarray:
        """[n, count] negatives drawn from each src's node-type sampler."""
        ids = _ids(src_ids)
        out = np.empty((len(ids), count), dtype=np.uint64)
        self._lib.eg_sample_node_with_src(
            self._h, _ptr(ids, _U64P), len(ids), count, _ptr(out, _U64P)
        )
        self._check_strict()
        return out.view(np.int64)

    def node_types(self, ids) -> np.ndarray:
        ids = _ids(ids)
        out = np.empty(len(ids), dtype=np.int32)
        self._lib.eg_get_node_type(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(out, _I32P)
        )
        self._check_strict()
        return out

    def node_weights(self, ids) -> np.ndarray:
        """Per-node sampling weights (0 for unknown ids). Works in both
        modes: local reads the embedded engine; remote scatters a
        kNodeWeight RPC per shard — so the device-graph exporter
        (build_node_sampler / build_typed_node_sampler) composes with
        sharded graphs. Raises when a shard cannot answer: a weight
        silently read as 0 would bias the exported sampler (unlike the
        query ops, which legitimately degrade to defaults)."""
        ids = _ids(ids)
        out = np.empty(len(ids), dtype=np.float32)
        rc = self._lib.eg_get_node_weight(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(out, _F32P)
        )
        if rc != 0:
            # consume any pending strict record first (same failure, the
            # shard-naming message) so it cannot go stale and fire on an
            # unrelated later call
            self._check_strict()
            raise RuntimeError(self._lib.eg_last_error().decode())
        self._check_strict()
        return out

    # ---- neighbor ops ----
    def sample_neighbor(
        self, ids, edge_types, count: int, default_node: int = -1
    ):
        """Returns (nbr_ids [n,count] i64, weights [n,count] f32,
        types [n,count] i32)."""
        ids = _ids(ids)
        et = _i32(edge_types)
        n = len(ids)
        out_i = np.empty((n, count), dtype=np.uint64)
        out_w = np.empty((n, count), dtype=np.float32)
        out_t = np.empty((n, count), dtype=np.int32)
        self._lib.eg_sample_neighbor(
            self._h, _ptr(ids, _U64P), n, _ptr(et, _I32P), len(et), count,
            _default_u64(default_node), _ptr(out_i, _U64P), _ptr(out_w, _F32P),
            _ptr(out_t, _I32P),
        )
        self._check_strict()
        return out_i.view(np.int64), out_w, out_t

    def sample_fanout(self, ids, edge_types, counts, default_node: int = -1):
        """Fused multi-hop sampling: one native call for all hops.

        edge_types: per-hop list of edge-type lists; counts: per-hop fanouts.
        Returns (ids_per_hop, weights_per_hop, types_per_hop); hop h arrays
        are flat with n * prod(counts[:h+1]) rows. ids_per_hop[0] is the
        (flattened) input.
        """
        ids = _ids(ids)
        nhops = len(counts)
        et_lists = [_i32(e) for e in edge_types]
        et_flat = (
            np.concatenate(et_lists) if et_lists else np.zeros(0, np.int32)
        )
        et_counts = _i32([len(e) for e in et_lists])
        counts_arr = _i32(counts)
        out_i, out_w, out_t = [], [], []
        m = len(ids)
        for h in range(nhops):
            m *= int(counts[h])
            out_i.append(np.empty(m, dtype=np.uint64))
            out_w.append(np.empty(m, dtype=np.float32))
            out_t.append(np.empty(m, dtype=np.int32))
        ids_ptrs = (_U64P * nhops)(*[_ptr(a, _U64P) for a in out_i])
        w_ptrs = (_F32P * nhops)(*[_ptr(a, _F32P) for a in out_w])
        t_ptrs = (_I32P * nhops)(*[_ptr(a, _I32P) for a in out_t])
        self._lib.eg_sample_fanout(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(et_flat, _I32P),
            _ptr(et_counts, _I32P), _ptr(counts_arr, _I32P), nhops,
            _default_u64(default_node), ids_ptrs, w_ptrs, t_ptrs,
        )
        self._check_strict()
        return (
            [ids.view(np.int64)] + [a.view(np.int64) for a in out_i],
            out_w,
            out_t,
        )

    def sample_fanout_async(
        self, ids, edge_types, counts, default_node: int = -1
    ):
        """Submit one whole multi-hop sample as an in-flight async op.

        Remote graphs only. The native hop chain runs entirely on the
        client's dispatcher pool (hop h+1's shard jobs are enqueued by
        hop h's completion continuation), so this returns immediately
        with an :class:`AsyncFanout` handle — ``poll()`` it, then
        ``take()`` for the same (ids_per_hop, weights, types) tuple
        ``sample_fanout`` returns. The handle owns every buffer the
        native op writes into; keep it referenced until the take.

        Returns None when the native async-op pool is full or the graph
        is not remote — callers fall back to the sync ``sample_fanout``
        (the depth pipeline in euler_tpu/parallel/prefetch.py does this
        transparently).
        """
        if self.mode != "remote":
            return None
        ids = _ids(ids)
        nhops = len(counts)
        et_lists = [_i32(e) for e in edge_types]
        et_flat = (
            np.concatenate(et_lists) if et_lists else np.zeros(0, np.int32)
        )
        et_counts = _i32([len(e) for e in et_lists])
        counts_arr = _i32(counts)
        out_i, out_w, out_t = [], [], []
        m = len(ids)
        for h in range(nhops):
            m *= int(counts[h])
            out_i.append(np.empty(m, dtype=np.uint64))
            out_w.append(np.empty(m, dtype=np.float32))
            out_t.append(np.empty(m, dtype=np.int32))
        ids_ptrs = (_U64P * nhops)(*[_ptr(a, _U64P) for a in out_i])
        w_ptrs = (_F32P * nhops)(*[_ptr(a, _F32P) for a in out_w])
        t_ptrs = (_I32P * nhops)(*[_ptr(a, _I32P) for a in out_t])
        slot = self._lib.eg_remote_sample_async(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(et_flat, _I32P),
            _ptr(et_counts, _I32P), _ptr(counts_arr, _I32P), nhops,
            _default_u64(default_node), ids_ptrs, w_ptrs, t_ptrs,
        )
        if slot < 0:
            return None
        return AsyncFanout(
            self, slot, ids, et_flat, et_counts, counts_arr,
            out_i, out_w, out_t,
        )

    def get_full_neighbor(self, ids, edge_types, sorted: bool = False):
        """Ragged full adjacency: (nbr_ids, weights, types, row_counts)."""
        ids = _ids(ids)
        et = _i32(edge_types)
        r = self._lib.eg_get_full_neighbor(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(et, _I32P), len(et),
            1 if sorted else 0,
        )
        try:
            nbr = self._fetch(r, 0, 0, np.uint64)
            w = self._fetch(r, 1, 0, np.float32)
            t = self._fetch(r, 2, 0, np.int32)
            counts = self._fetch(r, 2, 1, np.int32)
        finally:
            self._lib.eg_result_free(r)
        self._check_strict()
        return nbr.view(np.int64), w, t, counts

    def get_top_k_neighbor(self, ids, edge_types, k: int, default_node=-1):
        ids = _ids(ids)
        et = _i32(edge_types)
        n = len(ids)
        out_i = np.empty((n, k), dtype=np.uint64)
        out_w = np.empty((n, k), dtype=np.float32)
        out_t = np.empty((n, k), dtype=np.int32)
        self._lib.eg_get_top_k_neighbor(
            self._h, _ptr(ids, _U64P), n, _ptr(et, _I32P), len(et), k,
            _default_u64(default_node), _ptr(out_i, _U64P), _ptr(out_w, _F32P),
            _ptr(out_t, _I32P),
        )
        self._check_strict()
        return out_i.view(np.int64), out_w, out_t

    # ---- walks ----
    def random_walk(
        self, ids, edge_types, walk_len: int = None, p: float = 1.0,
        q: float = 1.0, default_node: int = -1,
    ) -> np.ndarray:
        """[n, walk_len+1] int64 walks; column 0 is the start node.

        edge_types is either a flat list (same types every step; walk_len
        required) or a per-step list of lists defining a heterogeneous
        metapath (walk_len inferred), e.g. [[0], [1], [0]].
        """
        ids = _ids(ids)
        if len(edge_types) > 0 and isinstance(
            edge_types[0], (list, tuple, np.ndarray)
        ):
            steps = [_i32(e) for e in edge_types]
            if walk_len is None:
                walk_len = len(steps)
            elif walk_len != len(steps):
                raise ValueError("walk_len != len(edge_types metapath)")
        else:
            if walk_len is None:
                raise ValueError("walk_len required with flat edge_types")
            steps = [_i32(edge_types)] * walk_len
        et_flat = (
            np.concatenate(steps) if steps else np.zeros(0, np.int32)
        )
        et_counts = _i32([len(s) for s in steps])
        out = np.empty((len(ids), walk_len + 1), dtype=np.uint64)
        self._lib.eg_random_walk(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(et_flat, _I32P),
            _ptr(et_counts, _I32P), walk_len, p, q,
            _default_u64(default_node), _ptr(out, _U64P),
        )
        self._check_strict()
        return out.view(np.int64)

    # ---- features ----
    def get_dense_feature(self, ids, fids, dims) -> np.ndarray:
        """[n, sum(dims)] float32, zero-padded per slot."""
        ids = _ids(ids)
        fids = _i32(fids)
        dims = _i32(dims)
        out = np.empty((len(ids), int(dims.sum())), dtype=np.float32)
        self._lib.eg_get_dense_feature(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(fids, _I32P),
            _ptr(dims, _I32P), len(fids), _ptr(out, _F32P),
        )
        self._check_strict()
        return out

    def get_edge_dense_feature(self, src, dst, types, fids, dims) -> np.ndarray:
        src = _ids(src)
        dst = _ids(dst)
        types = _i32(types)
        fids = _i32(fids)
        dims = _i32(dims)
        out = np.empty((len(src), int(dims.sum())), dtype=np.float32)
        self._lib.eg_get_edge_dense_feature(
            self._h, _ptr(src, _U64P), _ptr(dst, _U64P), _ptr(types, _I32P),
            len(src), _ptr(fids, _I32P), _ptr(dims, _I32P), len(fids),
            _ptr(out, _F32P),
        )
        self._check_strict()
        return out

    def get_sparse_feature(self, ids, fids):
        """Per slot: (values i64 concat, row_counts i32[n])."""
        ids = _ids(ids)
        fids = _i32(fids)
        r = self._lib.eg_get_sparse_feature(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(fids, _I32P), len(fids)
        )
        return self._drain_sparse(r, len(fids))

    def get_edge_sparse_feature(self, src, dst, types, fids):
        src = _ids(src)
        dst = _ids(dst)
        types = _i32(types)
        fids = _i32(fids)
        r = self._lib.eg_get_edge_sparse_feature(
            self._h, _ptr(src, _U64P), _ptr(dst, _U64P), _ptr(types, _I32P),
            len(src), _ptr(fids, _I32P), len(fids),
        )
        return self._drain_sparse(r, len(fids))

    def get_binary_feature(self, ids, fids):
        """Per slot: list of bytes, one per row."""
        ids = _ids(ids)
        fids = _i32(fids)
        r = self._lib.eg_get_binary_feature(
            self._h, _ptr(ids, _U64P), len(ids), _ptr(fids, _I32P), len(fids)
        )
        return self._drain_binary(r, len(fids))

    def get_edge_binary_feature(self, src, dst, types, fids):
        src = _ids(src)
        dst = _ids(dst)
        types = _i32(types)
        fids = _i32(fids)
        r = self._lib.eg_get_edge_binary_feature(
            self._h, _ptr(src, _U64P), _ptr(dst, _U64P), _ptr(types, _I32P),
            len(src), _ptr(fids, _I32P), len(fids),
        )
        return self._drain_binary(r, len(fids))

    # ---- result plumbing ----
    def _fetch(self, r, kind: int, slot: int, dtype) -> np.ndarray:
        n = self._lib.eg_result_size(r, kind, slot)
        out = np.empty(max(n, 0), dtype=dtype)
        if n > 0:
            self._lib.eg_result_copy(
                r, kind, slot, out.ctypes.data_as(ctypes.c_void_p)
            )
        return out

    def _drain_sparse(self, r, nslots: int):
        try:
            out = []
            for k in range(nslots):
                vals = self._fetch(r, 0, k, np.uint64).view(np.int64)
                counts = self._fetch(r, 2, k, np.int32)
                out.append((vals, counts))
        finally:
            self._lib.eg_result_free(r)
        self._check_strict()
        return out

    def _drain_binary(self, r, nslots: int):
        try:
            out = []
            for k in range(nslots):
                n = self._lib.eg_result_size(r, 3, k)
                buf = ctypes.create_string_buffer(max(int(n), 1))
                if n > 0:
                    self._lib.eg_result_copy(r, 3, k, buf)
                data = buf.raw[: int(n)]
                sizes = self._fetch(r, 2, k, np.int32)
                rows = []
                off = 0
                for s in sizes:
                    rows.append(data[off : off + int(s)])
                    off += int(s)
                out.append(rows)
        finally:
            self._lib.eg_result_free(r)
        self._check_strict()
        return out


class AsyncFanout:
    """Handle of one in-flight async multi-hop sample
    (:meth:`Graph.sample_fanout_async`).

    Owns every buffer the native op writes into (the request arrays are
    copied native-side, but the per-hop outputs are written in place),
    so the handle must stay referenced until :meth:`take` returns. One
    take per handle; the native slot recycles on take.
    """

    def __init__(self, graph, slot, ids, et_flat, et_counts, counts_arr,
                 out_i, out_w, out_t):
        self._graph = graph
        self._slot = slot
        self._ids = ids
        # pinned until the take: the native op borrows these buffers
        self._pin = (et_flat, et_counts, counts_arr)
        self._out_i = out_i
        self._out_w = out_w
        self._out_t = out_t
        self._taken = False

    def poll(self) -> bool:
        """True when the op has completed (take will not block)."""
        if self._taken:
            return True
        return self._graph._lib.eg_remote_async_poll(
            self._graph._h, self._slot) == 1

    def take(self):
        """Block until the op completes, recycle its native slot, and
        return the same (ids_per_hop, weights_per_hop, types_per_hop)
        tuple ``sample_fanout`` returns. Raises under ``strict=`` when
        a shard failed inside the op — identical semantics to the sync
        path, just surfaced at the take instead of the call."""
        if self._taken:
            raise RuntimeError("AsyncFanout.take() called twice")
        rc = self._graph._lib.eg_remote_async_take(
            self._graph._h, self._slot)
        self._taken = True
        if rc != 0:
            raise RuntimeError(
                "eg_remote_async_take failed for slot %d" % self._slot)
        self._graph._check_strict()
        return (
            [self._ids.view(np.int64)]
            + [a.view(np.int64) for a in self._out_i],
            self._out_w,
            self._out_t,
        )

    def __del__(self):
        # an abandoned handle must not leak its native slot (and the op
        # may still be writing into our buffers): block for completion
        try:
            if not self._taken:
                self._graph._lib.eg_remote_async_take(
                    self._graph._h, self._slot)
                self._taken = True
        except Exception:
            pass
