"""eg_serve: online embedding inference over a trained checkpoint.

The serving gap named by ROADMAP item 3: everything below this module
already exists — the graph client (local or sharded-remote, with PR-9
placement routing and neighbor/feature caches), the trained checkpoint
(checkpoint.py), the jitted embed step (Model.make_embed_step) — and
nothing answered "embed these user ids". This module wires them into a
server:

    request -> MicroBatcher (coalesce + shed + deadline)
            -> per-unique-id neighborhood sampling (graph client)
            -> one padded-bucket jitted forward -> rows per request

Determinism is a serving feature here, not an accident: each id's
neighborhood is sampled ONCE with an id-derived native RNG seed and
cached (``--serve_sample_cache``), so an id's embedding is bit-stable
across requests, across co-batched traffic, and identical to
:meth:`EmbedServer.embed_direct` — the parity anchor the serve tests
and the load drill pin.

Every dispatch pads to ONE fixed bucket (``max_batch`` rows, padding
repeats a real sampled block), so a single XLA program serves all
traffic. That is what makes the parity claim honest: within one
program, row-wise model math is position- and padding-independent
(pinned by tests), while XLA re-tiles per SHAPE — empirically, the
same row differs ~1e-6 between a size-1 and a size-8 program, so
variable buckets could never promise bit-identity. Phase telemetry
rides the native
``serve:*`` histograms; admission/shedding rides the ``serve_*``
counters (FAULTS.md).

Usage (inference-mode sampling, all_edge_type metapaths — the
evaluate/save_embedding convention):

    python -m euler_tpu.serve --data_dir ... --model graphsage_supervised \
        --model_dir ckpt --serve_port 9200 [--serve_slo_ms 50] ...

or train-then-serve in one process: ``python -m euler_tpu ...
--serve_after=1`` (run_loop; serves with the training sampling config).
"""

from __future__ import annotations

import logging
import signal
import sys
import threading
import time
from collections import OrderedDict
from typing import Optional

import numpy as np

from euler_tpu import devprof
from euler_tpu import telemetry as T
from euler_tpu.graph import native
from euler_tpu.serving import MicroBatcher, SLOTracker, EmbedFrontend

log = logging.getLogger("euler_tpu.serve")

_MIX = 0x9E3779B97F4A7C15  # splitmix64 increment
_MASK = (1 << 64) - 1


def _id_seed(seed: int, nid: int) -> int:
    """Deterministic nonzero 64-bit RNG seed for one (server seed, id)."""
    h = (nid * _MIX + seed * 0xBF58476D1CE4E5B9 + 0x94D049BB133111EB) & _MASK
    h ^= h >> 31
    return (h * _MIX) & _MASK or 1


class EmbedServer:
    """Micro-batched embedding inference over one model + graph + state.

    ``state`` is a restored (or freshly initialized) train-state pytree
    — the same structure Checkpointer.restore returns. The graph client
    carries its own transport config (retries/deadline_ms/caches), so a
    sharded-remote deployment needs nothing extra here: configure the
    Graph with ``deadline_ms`` at or under the serve deadline and every
    sampling RPC inherits the budget.
    """

    def __init__(self, model, graph, state, *, max_batch: int = 64,
                 max_wait_us: int = 2000, queue_cap: int = 128,
                 slo_ms: float = 100.0, seed: int = 42,
                 sample_cache: int = 65536,
                 strict_bucket: bool = False):
        import jax

        if getattr(model, "device_sampling", False):
            raise ValueError(
                "EmbedServer samples neighborhoods on the host per "
                "unique id (the determinism anchor); build the serving "
                "model with device_sampling=False"
            )
        self.model = model
        self.graph = graph
        self.max_batch = int(max_batch)
        self.seed = int(seed)
        self.sample_cache = max(int(sample_cache), 1)
        self._state = state
        self._jax = jax
        # Compile-storm guard (OBSERVABILITY.md "Device plane"): the
        # fixed-bucket design means ONE compile, ever — any post-warmup
        # recompile is a broken bucket contract (and a silent 100x), so
        # it bumps serve_recompiles + journals the shape diff; with
        # strict_bucket= it raises devprof.RecompileError.
        self._embed_fn = devprof.watch(
            jax.jit(model.make_embed_step()),
            name="embed_step",
            strict=strict_bucket,
            on_recompile=lambda e: native.counter_add("serve_recompiles"),
        )
        self._cache: OrderedDict = OrderedDict()
        self._cache_lock = threading.Lock()
        self.slo = SLOTracker(slo_ms)
        self.batcher = MicroBatcher(
            self._embed_unique,
            max_batch=max_batch,
            max_wait_us=max_wait_us,
            queue_cap=queue_cap,
            on_done=self._on_done,
        )

    # ---- lifecycle ----

    def start(self) -> "EmbedServer":
        self.batcher.start()
        return self

    def close(self) -> None:
        self.batcher.close()

    def __enter__(self) -> "EmbedServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- request path ----

    def embed(self, ids, deadline_ms: Optional[float] = None) -> np.ndarray:
        """Embeddings for ids through the micro-batcher: [n, dim]
        float32, one row per requested id (duplicates allowed). Raises
        serving.BusyError / serving.DeadlineError on shed/expiry."""
        return np.asarray(
            self.batcher.submit(ids, deadline_ms=deadline_ms),
            dtype=np.float32,
        )

    def embed_direct(self, nid: int) -> np.ndarray:
        """Reference path: one id, no micro-batching — the bit-parity
        anchor the batched path is pinned against."""
        return np.asarray(
            self._forward([self._block(int(nid))])[0], dtype=np.float32
        )

    def stats(self) -> dict:
        """Live serving stats (the frontend's ``stats`` op): SLO
        verdict, serve-phase percentiles, serve counters, coalescing
        ledger."""
        hists = T.serve_hists()
        phases = {}
        for name, h in hists.items():
            if not h["count"]:
                continue
            pct = T.percentiles(h, (50, 99))
            phases[name] = {
                "count": h["count"],
                "p50_us": round(pct.get(50, 0.0), 1),
                "p99_us": round(pct.get(99, 0.0), 1),
            }
        ctr = {
            k: v for k, v in native.counters().items()
            if k.startswith("serve_")
        }
        batch_h = T.telemetry_json()["hist"].get("serve_batch", {})
        batch = {}
        if batch_h.get("count"):
            batch = {
                "dispatches": batch_h["count"],
                "mean_unique_ids": round(
                    batch_h["sum_us"] / batch_h["count"], 2
                ),
            }
        return {
            "slo": self.slo.report(),
            "serve_phases": phases,
            "counters": ctr,
            "batch": batch,
            "devprof": devprof.compile_summary(),
        }

    # ---- internals ----

    def _on_done(self, total_us: float, error) -> None:
        if error is None:
            self.slo.record(total_us)

    def _block(self, nid: int) -> dict:
        """One id's sampled model inputs — drawn once with an
        id-derived seed, then cached (hot ids sample zero times).

        Entries are keyed by the graph client's cache generation
        (Graph.cache_gen, bumped on every observed epoch flip): a hit
        sampled before a rolling graph refresh evicts and resamples
        against the new snapshot (counted epoch_stale_hits_evicted, the
        same ledger the native feature/neighbor caches use), so the
        bit-stability promise holds *within* an epoch — exactly the
        window in which it is meaningful."""
        gen = getattr(self.graph, "cache_gen", 0)
        with self._cache_lock:
            ent = self._cache.get(nid)
            if ent is not None:
                if ent[0] == gen:
                    self._cache.move_to_end(nid)
                    return ent[1]
                del self._cache[nid]
                native.counter_add("epoch_stale_hits_evicted", 1)
        native.lib().eg_seed(_id_seed(self.seed, nid))
        blk = self.model.sample_embed(
            self.graph, np.array([nid], dtype=np.int64)
        )
        with self._cache_lock:
            self._cache[nid] = (gen, blk)
            while len(self._cache) > self.sample_cache:
                self._cache.popitem(last=False)
        return blk

    def _forward(self, blocks: list) -> np.ndarray:
        """One fixed-bucket device dispatch over per-id blocks: always
        padded to max_batch rows, so ONE jitted program serves every
        dispatch — the bit-parity guarantee (see module docstring)."""
        n = len(blocks)
        padded = blocks + [blocks[0]] * (self.max_batch - n)
        batch = self._jax.tree.map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
            *padded,
        )
        devprof.count_h2d(batch)
        emb = self._jax.block_until_ready(
            self._embed_fn(self._state, batch)
        )
        devprof.count_d2h(emb)
        return np.asarray(emb)[:n]

    def _embed_unique(self, uids: np.ndarray) -> np.ndarray:
        """The batcher's callback: sample per unique id (cached), then
        dispatch in max_batch-sized chunks."""
        t0 = time.monotonic()
        blocks = [self._block(int(i)) for i in uids]
        T.record_serve_phase("sample", (time.monotonic() - t0) * 1e6)
        t1 = time.monotonic()
        outs = [
            self._forward(blocks[off:off + self.max_batch])
            for off in range(0, len(blocks), self.max_batch)
        ]
        T.record_serve_phase("dispatch", (time.monotonic() - t1) * 1e6)
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)


def restore_serving_state(model, graph, args, mesh):
    """Initialize the state structure and restore the checkpoint from
    --model_dir — REQUIRED here: serving fresh random params is a bug,
    so unlike training's resume path this raises (Checkpointer.restore's
    loud ValueError) when the directory has no checkpoint."""
    import jax

    from euler_tpu import train as train_lib
    from euler_tpu.checkpoint import Checkpointer
    from euler_tpu.parallel import pad_tables_for_mesh

    opt = train_lib.get_optimizer(args.optimizer, args.learning_rate)
    example = np.asarray(
        graph.sample_node(args.batch_size, args.train_node_type)
    )
    state = model.init_state(
        jax.random.PRNGKey(args.seed), graph, example, opt
    )
    state = pad_tables_for_mesh(state, mesh)
    ckpt = Checkpointer(args.model_dir)
    try:
        state = ckpt.restore(state)
    finally:
        ckpt.close()
    return state


def build_server(model, graph, args, mesh) -> EmbedServer:
    """EmbedServer from the run_loop flag surface + a restored
    checkpoint."""
    state = restore_serving_state(model, graph, args, mesh)
    return EmbedServer(
        model, graph, state,
        max_batch=args.serve_max_batch,
        max_wait_us=args.serve_max_wait_us,
        queue_cap=args.serve_queue_cap,
        slo_ms=args.serve_slo_ms,
        seed=args.seed,
        sample_cache=args.serve_sample_cache,
        strict_bucket=bool(args.serve_strict_bucket),
    )


def run_serve(model, graph, args, mesh, block: bool = True):
    """Start the embedding server + frontend (run_loop --serve_after
    and the serve CLI both land here).

    ``block=True`` serves until SIGTERM/SIGINT, draining on the way out
    (the rolling-restart contract: stop accepting, finish in-flight,
    drain the batch queue). ``block=False`` returns the live
    ``(server, frontend)`` for in-process callers/tests — the caller
    owns ``frontend.stop()`` + ``server.close()``."""
    server = build_server(model, graph, args, mesh).start()
    frontend = EmbedFrontend(
        server,
        host=args.serve_host,
        port=args.serve_port,
        max_conns=args.serve_max_conns,
        default_deadline_ms=args.serve_deadline_ms,
    )
    log.info(
        "serving embeddings on %s (max_batch=%d max_wait_us=%d "
        "queue_cap=%d slo_ms=%g)", frontend.address,
        args.serve_max_batch, args.serve_max_wait_us,
        args.serve_queue_cap, args.serve_slo_ms,
    )
    if not block:
        return server, frontend
    stop = threading.Event()

    def _stop(signum, _frame):
        log.info("signal %d: draining embedding server", signum)
        stop.set()

    prev = {
        s: signal.signal(s, _stop)
        for s in (signal.SIGTERM, signal.SIGINT)
    }
    try:
        while not stop.wait(0.5):
            pass
    finally:
        for s, h in prev.items():
            signal.signal(s, h)
        frontend.drain()
        server.close()  # drains the queued batches
        frontend.stop()
        report = server.slo.report()
        log.info("serve SLO at exit: %s", report)
    return server, frontend


def main(argv=None) -> int:
    """`python -m euler_tpu.serve`: serve a trained checkpoint.

    Reuses the run_loop flag surface (graph/model/checkpoint flags mean
    the same thing) + the serve flags; the model is built with the
    INFERENCE sampling config (all_edge_type metapaths — the
    evaluate/save_embedding convention), so --mode is ignored."""
    from euler_tpu import run_loop

    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    logging.getLogger("absl").setLevel(logging.WARNING)
    from euler_tpu.parallel import enable_compile_cache, make_mesh

    args = run_loop.define_flags().parse_args(argv)
    args.mode = "evaluate"  # inference sampling config (all_edge_type)
    if not args.telemetry:
        T.set_telemetry(False)
    # compile cache + device plane before the embed jit: the serve
    # forward is the program the cache saves a relaunch from
    # recompiling, and the compile-storm guard needs the listener live
    log.info("persistent compile cache: %s", enable_compile_cache() or "off")
    devprof.setup(enabled=args.devprof, sample_ms=1000)
    graph, services = run_loop.build_graph(args)
    try:
        mesh = make_mesh(args.num_devices,
                         model_parallel=args.model_parallel)
        model = run_loop.build_model(args, graph)
        run_serve(model, graph, args, mesh, block=True)
    finally:
        ledger = {k: v for k, v in native.counters().items() if v}
        if ledger:
            log.info("serve counters at exit: %s", ledger)
        for s in services:
            if hasattr(s, "drain"):
                s.drain()
            s.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
