"""Training / evaluation / embedding-export CLI driver.

Reference equivalent: tf_euler/python/run_loop.py — same flag surface
(:36-92), same model names in the dispatch (:222-354), same three modes
(train :95-140, evaluate :143-171, save_embedding :174-219) — rebuilt for
the TPU stack:

* ``MonitoredTrainingSession`` -> euler_tpu.train.train (jitted step,
  orbax checkpoints in --model_dir, resume-from-latest).
* PS/worker ClusterSpec (run_loop.py:371-397) -> one process per TPU host
  with jax.distributed (--coordinator_addr/--num_processes/--process_id);
  within a process, data parallelism over the local device mesh.
* ``initialize_shared_graph`` (tf_euler base.py:64) -> --graph_mode=shared:
  every process serves its graph shard (GraphService) and connects a
  remote client over the flat-file --registry.

Usage:  python -m euler_tpu --data_dir ... --model graphsage_supervised ...
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
from typing import Optional

import numpy as np

import euler_tpu
from euler_tpu import models
from euler_tpu.graph import device as device_graph
from euler_tpu.parallel import make_mesh
from euler_tpu import train as train_lib

log = logging.getLogger("euler_tpu")


# one truthy-string rule shared with Graph's config parsing — the CLI
# and config-string spellings must accept the same values
from euler_tpu.graph.graph import str2bool as _str2bool  # noqa: E402


def _int_list(v) -> list[int]:
    if isinstance(v, (list, tuple)):
        return [int(x) for x in v]
    return [int(x) for x in str(v).split(",") if x != ""]


def define_flags(parser: Optional[argparse.ArgumentParser] = None):
    """Flag surface of reference run_loop.py:36-92 (ZK flags replaced by
    the flat-file registry; PS flags by jax.distributed)."""
    p = parser or argparse.ArgumentParser(prog="euler_tpu")
    p.add_argument(
        "--mode",
        default="train",
        choices=["train", "evaluate", "save_embedding"],
    )
    # graph
    p.add_argument("--data_dir", default="")
    p.add_argument("--stream", type=_str2bool, default=False, help=(
        "with a remote --data_dir URL (gs://, s3://, ...), parse "
        "fetched partition bytes straight into the store instead of "
        "staging them to local disk first (zero local scratch; "
        "re-fetches each launch)"))
    p.add_argument("--graph_mode", default="local",
                   choices=["local", "remote", "shared"])
    p.add_argument("--registry", default="")
    p.add_argument("--rediscover_ms", type=int, default=None, help=(
        "mid-run registry re-LIST period for remote/shared clients "
        "(default: native 3000 ms with a registry; 0 disables) — how a "
        "shard restarted on a new address is re-learned mid-training"))
    p.add_argument("--backoff_ms", type=int, default=None, help=(
        "base of the jittered exponential retry backoff in the remote "
        "client (default: native 20 ms; 0 = hot retry)"))
    p.add_argument("--deadline_ms", type=int, default=None, help=(
        "overall wall-clock budget of ONE graph call spanning all its "
        "retries (default: timeout_ms * (retries+1))"))
    p.add_argument("--feature_cache_mb", type=int, default=None, help=(
        "byte budget (MB) of the remote client's dense-feature-row "
        "cache (remote/shared graph modes; native default 64, 0 "
        "disables). The graph is immutable after load, so cached rows "
        "never invalidate"))
    p.add_argument("--neighbor_cache_mb", type=int, default=None, help=(
        "byte budget (MB) of the remote client's neighbor-list cache "
        "(remote/shared modes; native default 16, 0 disables): hot "
        "nodes' adjacency slices are fetched once and sampled locally "
        "— distribution-identical to the shard engine (PERF.md "
        "'Locality')"))
    p.add_argument("--cache_policy", default=None,
                   choices=("freq", "fifo"), help=(
        "admission policy of both remote client caches (native default "
        "freq = TinyLFU-shaped over the heat sketch; fifo restores "
        "unconditional admission)"))
    p.add_argument("--placement", type=_str2bool, default=None, help=(
        "fetch the cluster's placement map at init and route ids "
        "through it, hash fallback when none exists (remote/shared "
        "modes; native default on; see convert.py --placement degree)"))
    p.add_argument("--strict", type=_str2bool, default=False, help=(
        "remote/shared graph modes: raise when a shard call fails after "
        "all transport retries instead of silently training on "
        "default-filled rows (failures are counted in rpc_errors either "
        "way)"))
    p.add_argument("--fault", default="", help=(
        "deterministic transport failpoint spec for chaos drills, e.g. "
        "'recv_frame:err@0.1,dial:delay@50' (remote/shared modes; see "
        "FAULTS.md)"))
    p.add_argument("--fault_seed", type=int, default=0, help=(
        "seed for --fault: the same seed replays the same injected-"
        "failure sequence at every failpoint"))
    p.add_argument("--service_host", default="", help=(
        "address this process's graph shard binds and advertises "
        "(shared mode). Empty = auto: the interface that routes to a "
        "tcp:// registry host, else 127.0.0.1"))
    p.add_argument("--service_workers", type=int, default=None, help=(
        "shared mode: handler pool size of this process's shard service "
        "(default: 2x cores). Connections beyond workers+pending get a "
        "BUSY reply clients fail over on (eg_admission.h)"))
    p.add_argument("--service_pending", type=int, default=None, help=(
        "shared mode: admitted-work headroom beyond the shard service's "
        "handler pool before new connections are answered BUSY "
        "(default 64)"))
    p.add_argument("--shards", default="",
                   help="comma list of host:port (remote mode)")
    p.add_argument("--train_node_type", type=int, default=0)
    p.add_argument("--all_node_type", type=int, default=-1)
    p.add_argument("--train_edge_type", default="0")
    p.add_argument("--all_edge_type", default="0,1,2")
    p.add_argument("--max_id", type=int, default=-1)
    p.add_argument("--feature_idx", type=int, default=-1)
    p.add_argument("--feature_dim", type=int, default=0)
    p.add_argument("--label_idx", type=int, default=-1)
    p.add_argument("--label_dim", type=int, default=0)
    p.add_argument("--num_classes", type=int, default=None)
    p.add_argument("--id_file", default="")
    # model
    p.add_argument("--model", default="graphsage_supervised")
    p.add_argument("--sigmoid_loss", type=_str2bool, default=True)
    p.add_argument("--xent_loss", type=_str2bool, default=True)
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--num_negs", type=int, default=5)
    p.add_argument("--order", type=int, default=1)
    p.add_argument("--walk_len", type=int, default=5)
    p.add_argument("--walk_p", type=float, default=1.0)
    p.add_argument("--walk_q", type=float, default=1.0)
    p.add_argument("--walk_trials", type=int, default=0, help=(
        "rejection-walk proposal budget per biased step on the device "
        "alias path (0 = library default); higher lowers the "
        "exhaustion-fallback rate at extreme p/q"))
    p.add_argument("--left_win_size", type=int, default=5)
    p.add_argument("--right_win_size", type=int, default=5)
    p.add_argument("--fanouts", default="10,10")
    p.add_argument(
        "--aggregator",
        default="mean",
        choices=["gcn", "mean", "meanpool", "maxpool", "attention"],
    )
    p.add_argument("--concat", type=_str2bool, default=True)
    p.add_argument(
        "--device_features", type=_str2bool, default=False,
        help="keep the dense feature/label tables HBM-resident and gather "
             "on device (graphsage/gcn/scalable/gat models); ships only "
             "node ids per step",
    )
    p.add_argument(
        "--device_sampling", type=_str2bool, default=False,
        help="also keep the ADJACENCY HBM-resident and sample fanouts/"
             "walks inside the jitted step (graphsage, "
             "graphsage_supervised, scalable_sage, gcn, scalable_gcn, "
             "gat, line, node2vec incl. biased p/q walks, lshne); the "
             "host ships only root ids per step. For feature models "
             "this implies --device_features; the shallow id-embedding "
             "models run it standalone",
    )
    p.add_argument(
        "--feature_dtype", default="",
        help="storage dtype for the device-resident dense feature tables "
             "(e.g. bfloat16: half the HBM footprint and gather bytes; "
             "rows cast back to float32 at the gather). Empty = float32",
    )
    p.add_argument("--use_residual", type=_str2bool, default=False)
    p.add_argument("--store_learning_rate", type=float, default=0.001)
    p.add_argument("--store_init_maxval", type=float, default=0.05)
    p.add_argument("--head_num", type=int, default=1)
    p.add_argument("--embedding_file", default="",
                   help="embedding.npy for model=saved_embedding "
                        "(default: <model_dir>/embedding.npy)")
    # training
    p.add_argument("--model_dir", default="ckpt")
    p.add_argument("--batch_size", type=int, default=512)
    p.add_argument("--optimizer", default="adam",
                   choices=sorted(train_lib.OPTIMIZERS))
    p.add_argument("--learning_rate", type=float, default=0.01)
    p.add_argument("--num_epochs", type=int, default=20)
    p.add_argument("--log_steps", type=int, default=20)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--num_devices", type=int, default=None,
                   help="devices in the data-parallel mesh (default: all)")
    p.add_argument("--model_parallel", type=int, default=1,
                   help="width of the 'model' mesh axis; >1 row-shards the "
                        "device-resident tables (consts, Scalable stores) "
                        "across it")
    p.add_argument("--max_degree", type=int, default=None, help=(
        "cap the device-sampling slab width (heaviest neighbors kept, "
        "renormalized) — heavy-tail graphs only; changes hub "
        "distributions, see PERF.md's truncation study"))
    p.add_argument("--alias_sampling", type=_str2bool, default=False,
                   help=(
                       "device-sample through exact flat-CSR alias "
                       "tables (O(edges) memory, no truncation) instead "
                       "of padded slabs — the recommended form for "
                       "power-law graphs like real Reddit"))
    p.add_argument("--metrics_every", type=int, default=0, help=(
        "append a telemetry snapshot line (counters + per-op client "
        "p50/p99 latency) to --metrics_file every N training steps; "
        "0 disables (OBSERVABILITY.md)"))
    p.add_argument("--metrics_file", default="", help=(
        "JSONL path for --metrics_every snapshots (default: "
        "<model_dir>/metrics.jsonl)"))
    p.add_argument("--telemetry", type=_str2bool, default=True, help=(
        "process-global latency-histogram/slow-span recording "
        "(eg_telemetry); 0 is the kill-switch — counters, span timers "
        "AND the step-phase profiler all honor it"))
    p.add_argument("--postmortem_dir", default="", help=(
        "arm the blackbox postmortem path (eg_blackbox): fatal signals "
        "(SIGSEGV/SIGBUS/SIGABRT/SIGFPE) AND unhandled Python "
        "exceptions write <dir>/postmortem.<pid>[.exception].json — "
        "flight-recorder rings, counters, resource history, backtrace "
        "— before the process dies; collect a dead cluster's dumps "
        "with scripts/postmortem.py (OBSERVABILITY.md 'Postmortems')"))
    p.add_argument("--blackbox", type=_str2bool, default=True, help=(
        "flight-recorder kill-switch: 0 stops ring recording AND "
        "suppresses postmortem dumps (counters/telemetry unaffected)"))
    p.add_argument("--trace_file", default="", help=(
        "write a merged Chrome-trace/Perfetto JSON here when training "
        "ends: the set-up lane (graph load, table export, slabs, "
        "upload, trace/lower/compile), per-step phase slices "
        "(input_stall/sample/h2d/device/host) + this client's "
        "slow-span journal + every live shard's "
        "scraped journal, flow-linked by wire-v3 trace ids — open in "
        "ui.perfetto.dev (OBSERVABILITY.md 'Step phases')"))
    p.add_argument("--prefetch_depth", type=int, default=2)
    p.add_argument("--prefetch_threads", type=int, default=2)
    p.add_argument("--sampler_depth", type=int, default=2, help=(
        "remote graphs: number of training steps whose sampling is kept "
        "in flight through the engine's async completion queue "
        "(eg_remote_sample_async) — step k+1..k+N fan-outs overlap step "
        "k's H2D+device compute with no dedicated sampler threads. 0 "
        "falls back to the thread-pool prefetch; ignored for local "
        "graphs (PERF.md 'Pipelined sampling')"))
    p.add_argument("--profile_dir", default="")
    p.add_argument("--devprof", type=_str2bool, default=True, help=(
        "device-plane observability kill-switch (eg_devprof): XLA "
        "compile/recompile counters + latency histogram, post-warmup "
        "recompile journaling with the offending shape diff, device-"
        "memory gauges in the blackbox resource ring, h2d/d2h byte "
        "counters; 0 disarms all of it (OBSERVABILITY.md 'Device "
        "plane')"))
    # serving (euler_tpu/serve.py; DEPLOY.md "Serving runbook")
    p.add_argument("--serve_after", type=_str2bool, default=False, help=(
        "train mode: after training saves its final checkpoint, "
        "immediately serve it — start the embedding inference server "
        "(euler_tpu.serve) on --serve_port and run until SIGTERM/"
        "SIGINT, draining on the way out. Serves with the TRAINING "
        "sampling config; `python -m euler_tpu.serve` serves an "
        "existing checkpoint with the inference config instead"))
    from euler_tpu.serving import add_serve_flags

    add_serve_flags(p)
    # multi-process (multi-host TPU) — replaces PS/worker flags
    p.add_argument("--coordinator_addr", default="")
    p.add_argument("--num_processes", type=int, default=1)
    p.add_argument("--process_id", type=int, default=0)
    return p


def check_serve_flags(args) -> None:
    """Reject serve-only flags on a run that will never serve — they
    would silently do nothing (the --stream/--fault loudness rule)."""
    from euler_tpu.serving import serve_flag_overrides

    if args.serve_after and args.mode != "train":
        raise ValueError(
            "--serve_after means train-then-serve and needs "
            f"--mode=train (got --mode={args.mode}); to serve an "
            "existing checkpoint use `python -m euler_tpu.serve`"
        )
    overrides = serve_flag_overrides(args)
    if overrides and not args.serve_after:
        raise ValueError(
            f"serve-only flags {', '.join(overrides)} do nothing in "
            f"--mode={args.mode} without --serve_after; add "
            "--serve_after=1 (train, then serve the checkpoint) or use "
            "`python -m euler_tpu.serve` against a saved --model_dir"
        )


def build_graph(args):
    """Local / remote / shared graph init (reference tf_euler base.py:35-91:
    initialize_graph / initialize_shared_graph)."""
    services = []
    if args.stream and args.graph_mode != "local":
        # the shard service stages deliberately (a long-lived serving
        # host wants the warm cache); dropping the flag silently would
        # leave a scratch-poor operator staging anyway and hitting
        # ENOSPC with no hint why
        raise ValueError(
            "--stream is only supported with --graph_mode=local "
            "(shared/remote services stage their shard to the local "
            "cache; see DEPLOY.md 'Remote data')"
        )
    if args.fault and args.graph_mode == "local":
        # same loudness rule as --stream: the failpoints live in the TCP
        # transport, so on a local graph the flag would silently do nothing
        raise ValueError(
            "--fault needs --graph_mode=remote or shared (failpoints sit "
            "in the transport; see FAULTS.md)"
        )
    if args.graph_mode == "local" and (
        args.feature_cache_mb is not None or args.strict
        or args.neighbor_cache_mb is not None
        or args.cache_policy is not None or args.placement is not None
    ):
        raise ValueError(
            "--feature_cache_mb/--neighbor_cache_mb/--cache_policy/"
            "--placement/--strict need --graph_mode=remote or shared "
            "(they configure the remote client's request path; a local "
            "graph reads its own memory)"
        )
    if args.graph_mode == "local":
        graph = euler_tpu.Graph(
            directory=args.data_dir, stream=args.stream
        )
    elif args.graph_mode == "remote":
        graph = euler_tpu.Graph(
            mode="remote",
            registry=args.registry or None,
            shards=args.shards.split(",") if args.shards else None,
            rediscover_ms=args.rediscover_ms,
            backoff_ms=args.backoff_ms,
            deadline_ms=args.deadline_ms,
            feature_cache_mb=args.feature_cache_mb,
            neighbor_cache_mb=args.neighbor_cache_mb,
            cache_policy=args.cache_policy,
            placement=args.placement,
            strict=args.strict or None,
            fault=args.fault or None,
            fault_seed=args.fault_seed if args.fault else None,
        )
    else:  # shared: serve this process's shard, then connect remote
        if not args.registry:
            raise ValueError("--graph_mode=shared needs --registry")
        import time

        from euler_tpu.graph import registry as registry_mod

        tcp_registry = args.registry.startswith("tcp://")
        if tcp_registry:
            # TCP coordination plane (no shared filesystem needed):
            # process 0 hosts the registry at the URL's port; every other
            # process waits for it to answer before registering its shard.
            host, port = registry_mod.parse_tcp_url(args.registry)
            if args.process_id == 0:
                services.append(registry_mod.RegistryServer(port=port))
            else:
                deadline = time.time() + 120.0
                while True:
                    try:
                        registry_mod.query(args.registry)
                        break
                    except ConnectionError:
                        if time.time() > deadline:
                            raise TimeoutError(
                                f"registry {args.registry} unreachable "
                                "after 120s (does process 0 run on "
                                f"{host}?)"
                            )
                        time.sleep(0.2)
        # The shard must advertise an address other hosts can dial: with a
        # remote tcp:// registry, default to the local interface that
        # routes toward the registry host (the reference's GetIP analog,
        # euler/common/net_util.cc:32); loopback only for single-host runs.
        service_host = args.service_host
        if not service_host:
            service_host = "127.0.0.1"
            if tcp_registry and host not in ("127.0.0.1", "localhost"):
                import socket as _socket

                probe = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
                try:
                    probe.connect((host, 9))  # no traffic; routing only
                    service_host = probe.getsockname()[0]
                finally:
                    probe.close()
        services.append(
            euler_tpu.GraphService(
                args.data_dir,
                shard_idx=args.process_id,
                shard_num=args.num_processes,
                host=service_host,
                registry=args.registry,
                workers=args.service_workers,
                pending=args.service_pending,
            )
        )
        if tcp_registry:
            # Entries are heartbeat-kept with a TTL, so LIST only ever
            # returns live shards — no extra probing needed (stale
            # entries from a killed run expire on their own).
            def live_shards() -> set:
                try:
                    return set(registry_mod.query(args.registry))
                except ConnectionError:
                    return set()

            stale_hint = ""
        else:
            # Flat-file registry: wait for every shard to register AND
            # accept connections before connecting. A liveness probe (TCP
            # connect) filters out stale entries left by a SIGKILLed prior
            # run with the same --registry — those would otherwise satisfy
            # a pure count check and produce a confusing connect failure
            # later.
            import socket

            # Dead verdicts are cached per filename with an expiry:
            # re-probing dead hosts every 0.1s poll would burn the
            # deadline on serial 1s connect timeouts, but a permanent
            # verdict would blacklist a shard whose single probe hit a
            # transient failure (dropped SYN, probe racing the listen()
            # call). Expired entries get re-probed, so a not-yet-listening
            # live shard is only deferred, never lost.
            dead: dict[str, float] = {}  # entry -> verdict expiry time
            DEAD_TTL = 5.0

            def _alive(entry: str) -> bool:
                # registry filename: "<shard>#<host>_<port>" (eg_service.cc)
                if dead.get(entry, 0.0) > time.time():
                    return False
                try:
                    host, port = entry.split("#", 1)[1].rsplit("_", 1)
                    with socket.create_connection((host, int(port)), 1.0):
                        dead.pop(entry, None)
                        return True
                except (OSError, ValueError):
                    dead[entry] = time.time() + DEAD_TTL
                    return False

            def live_shards() -> set:
                return {
                    f.split("#", 1)[0]
                    for f in os.listdir(args.registry)
                    if "#" in f and not f.endswith(".tmp") and _alive(f)
                }

            stale_hint = ("; stale entries from a killed run are ignored "
                          "— clear the registry dir")

        deadline = time.time() + 120.0
        while True:
            live = live_shards()
            if len(live) >= args.num_processes:
                break
            if time.time() > deadline:
                raise TimeoutError(
                    f"only live shards {sorted(live)} in "
                    f"{args.registry} after 120s "
                    f"(need {args.num_processes}{stale_hint})"
                )
            time.sleep(0.1)
        graph = euler_tpu.Graph(
            mode="remote", registry=args.registry,
            rediscover_ms=args.rediscover_ms,
            backoff_ms=args.backoff_ms,
            deadline_ms=args.deadline_ms,
            feature_cache_mb=args.feature_cache_mb,
            neighbor_cache_mb=args.neighbor_cache_mb,
            cache_policy=args.cache_policy,
            placement=args.placement,
            strict=args.strict or None,
            fault=args.fault or None,
            fault_seed=args.fault_seed if args.fault else None,
        )
    return graph, services


class SavedEmbedding(models.Model):
    """Frozen saved-embedding encoder + trainable classifier
    (reference run_loop.py:340-351)."""

    metric_name = "f1"

    def __init__(self, embedding: np.ndarray, label_idx, label_dim,
                 num_classes=None, sigmoid_loss=True):
        import flax.linen as nn
        import jax

        super().__init__()
        self.embedding = embedding.astype(np.float32)
        self.label_idx = label_idx
        self.label_dim = label_dim
        outer = self

        class _Module(nn.Module):
            @nn.compact
            def __call__(self, batch):
                logits = nn.Dense(num_classes or label_dim)(
                    jax.lax.stop_gradient(batch["emb"])
                )
                loss, preds = models.base.supervised_decoder(
                    logits, batch["labels"], sigmoid_loss
                )
                from euler_tpu.nn import metrics as m

                return models.ModelOutput(
                    embedding=batch["emb"],
                    loss=loss,
                    metric_name="f1",
                    metric=m.f1_counts(batch["labels"], preds),
                )

            def embed(self, batch):
                return batch["emb"]

        self.module = _Module()

    def sample(self, graph, inputs):
        ids = np.asarray(inputs, dtype=np.int64).reshape(-1)
        safe = np.clip(ids, 0, len(self.embedding) - 1)
        return {
            "emb": self.embedding[safe],
            "labels": graph.get_dense_feature(
                ids, [self.label_idx], [self.label_dim]
            ),
        }


def build_model(args, graph):
    """Model dispatch with the reference's model names
    (reference run_loop.py:222-354), in the device-sampling form the
    flags ask for: ``--max_degree`` / ``--alias_sampling`` go to the
    model's ``set_sampling_options`` here, where one is given, so that
    ``main()``, ``serve.py`` and the benchmark's harness build the same
    model from the same flags. A model that refuses the form (the
    full-neighbourhood families refuse ``--alias_sampling``) raises."""
    model = _dispatch_model(args, graph)
    if (args.max_degree is not None or args.alias_sampling) and hasattr(
        model, "set_sampling_options"
    ):
        model.set_sampling_options(
            max_degree=args.max_degree, alias=args.alias_sampling
        )
    return model


def _dispatch_model(args, graph):
    fanouts = _int_list(args.fanouts)
    train_edge = _int_list(args.train_edge_type)
    all_edge = _int_list(args.all_edge_type)
    metapath = [list(train_edge if args.mode == "train" else all_edge)] * max(
        len(fanouts), 1
    )
    name = args.model
    common_sup = dict(
        label_idx=args.label_idx,
        label_dim=args.label_dim,
        num_classes=args.num_classes,
        sigmoid_loss=args.sigmoid_loss,
        feature_idx=args.feature_idx,
        feature_dim=args.feature_dim,
    )
    if name == "line":
        return models.LINE(
            node_type=args.all_node_type,
            edge_type=all_edge,
            max_id=args.max_id,
            dim=args.dim,
            xent_loss=args.xent_loss,
            num_negs=args.num_negs,
            order=args.order,
            device_sampling=args.device_sampling,
        )
    if name in ("randomwalk", "deepwalk", "node2vec"):
        return models.Node2Vec(
            node_type=args.all_node_type,
            edge_type=all_edge,
            max_id=args.max_id,
            dim=args.dim,
            xent_loss=args.xent_loss,
            num_negs=args.num_negs,
            walk_len=args.walk_len,
            walk_p=args.walk_p,
            walk_q=args.walk_q,
            left_win_size=args.left_win_size,
            right_win_size=args.right_win_size,
            device_sampling=args.device_sampling,
            walk_trials=args.walk_trials,
        )
    if name in ("gcn", "gcn_supervised"):
        # Full-neighbor GCN needs per-hop dense caps for static shapes.
        cap = max(fanouts) if fanouts else 10
        return models.SupervisedGCN(
            metapath=metapath,
            dim=args.dim,
            max_nodes_per_hop=[args.batch_size * (cap**h) for h in
                               range(1, len(metapath) + 1)],
            max_edges_per_hop=[args.batch_size * (cap ** (h + 1)) for h in
                               range(len(metapath))],
            aggregator=args.aggregator,
            max_id=args.max_id,
            use_residual=args.use_residual,
            device_features=args.device_features or args.device_sampling,
            feature_dtype=args.feature_dtype or None,
            device_sampling=args.device_sampling,
            **common_sup,
        )
    if name == "scalable_gcn":
        return models.ScalableGCN(
            edge_type=metapath[0],
            num_layers=len(fanouts),
            dim=args.dim,
            max_id=args.max_id,
            # per-ROOT cap on unique 1-hop neighbors (the model multiplies
            # by the batch size at sample time)
            max_neighbors=fanouts[0],
            aggregator=args.aggregator,
            use_residual=args.use_residual,
            store_learning_rate=args.store_learning_rate,
            store_init_maxval=args.store_init_maxval,
            device_features=args.device_features or args.device_sampling,
            feature_dtype=args.feature_dtype or None,
            device_sampling=args.device_sampling,
            train_node_type=args.train_node_type,
            **common_sup,
        )
    if name == "graphsage":
        return models.GraphSage(
            node_type=args.train_node_type,
            edge_type=train_edge,
            max_id=args.max_id,
            xent_loss=args.xent_loss,
            num_negs=args.num_negs,
            metapath=metapath,
            fanouts=fanouts,
            dim=args.dim,
            aggregator=args.aggregator,
            concat=args.concat,
            feature_idx=args.feature_idx,
            feature_dim=args.feature_dim,
            device_features=args.device_features or args.device_sampling,
            feature_dtype=args.feature_dtype or None,
            device_sampling=args.device_sampling,
        )
    if name == "graphsage_supervised":
        return models.SupervisedGraphSage(
            metapath=metapath,
            fanouts=fanouts,
            dim=args.dim,
            aggregator=args.aggregator,
            concat=args.concat,
            max_id=args.max_id,
            device_features=args.device_features or args.device_sampling,
            feature_dtype=args.feature_dtype or None,
            device_sampling=args.device_sampling,
            train_node_type=args.train_node_type,
            **common_sup,
        )
    if name == "scalable_sage":
        return models.ScalableSage(
            edge_type=metapath[0],
            fanout=fanouts[0],
            num_layers=len(fanouts),
            dim=args.dim,
            aggregator=args.aggregator,
            concat=args.concat,
            max_id=args.max_id,
            store_learning_rate=args.store_learning_rate,
            store_init_maxval=args.store_init_maxval,
            device_features=args.device_features or args.device_sampling,
            feature_dtype=args.feature_dtype or None,
            device_sampling=args.device_sampling,
            train_node_type=args.train_node_type,
            **common_sup,
        )
    if name == "gat":
        return models.GAT(
            label_idx=args.label_idx,
            label_dim=args.label_dim,
            num_classes=args.num_classes,
            sigmoid_loss=args.sigmoid_loss,
            feature_idx=args.feature_idx,
            feature_dim=args.feature_dim,
            max_id=args.max_id,
            head_num=args.head_num,
            hidden_dim=args.dim,
            nb_num=5,
            device_features=args.device_features or args.device_sampling,
            feature_dtype=args.feature_dtype or None,
            device_sampling=args.device_sampling,
            train_node_type=args.train_node_type,
        )
    if name == "lshne":
        return models.LsHNE(
            node_type=-1,
            # one view, two 3-step homogeneous metapaths (per-step
            # edge-type LISTS — a flat [0,0,0] would be rejected by the
            # walk's metapath parser)
            path_patterns=[[[[0], [0], [0]], [[0], [0], [0]]]],
            max_id=args.max_id,
            dim=128,
            sparse_feature_dims=[args.max_id + 2],
            feature_ids=[args.feature_idx if args.feature_idx >= 0 else 0],
            device_sampling=args.device_sampling,
        )
    if name == "saved_embedding":
        emb = np.load(
            args.embedding_file
            or os.path.join(args.model_dir, "embedding.npy")
        )
        return SavedEmbedding(
            emb,
            args.label_idx,
            args.label_dim,
            args.num_classes,
            args.sigmoid_loss,
        )
    raise ValueError(f"unsupported model {name!r}")


def _num_steps(args) -> int:
    per_epoch = max((args.max_id + 1) // args.batch_size, 1)
    return per_epoch * args.num_epochs


def run_train(model, graph, args, mesh, recorder=None):
    """``recorder``: the ``--trace_file`` recorder, which ``main`` starts
    before the graph is built so that the export carries the set-up
    lane; stopped and written out here, however the run ends."""
    import jax

    batch = args.batch_size * getattr(model, "batch_size_ratio", 1)
    # jax.distributed data parallelism: --batch_size stays the GLOBAL
    # batch (flag parity with the reference's per-cluster semantics);
    # each process samples its share and the shards concatenate onto the
    # global mesh in train_lib (shard_batch).
    n_proc = jax.process_count()
    if batch % n_proc:
        raise ValueError(
            f"--batch_size*ratio {batch} not divisible by "
            f"{n_proc} processes"
        )
    batch //= n_proc

    def source_fn(step):
        return np.asarray(graph.sample_node(batch, args.train_node_type))

    step_hook = None
    if args.metrics_every > 0:
        from euler_tpu.telemetry import append_metrics_line, job_tick

        metrics_path = args.metrics_file or os.path.join(
            args.model_dir or ".", "metrics.jsonl"
        )
        os.makedirs(os.path.dirname(metrics_path) or ".", exist_ok=True)

        def step_hook(step, _path=metrics_path):
            if step % args.metrics_every == 0:
                job_tick("metrics_every")
                append_metrics_line(_path, step)
                job_tick("metrics_every", end=True)

    try:
        state, history = train_lib.train(
            model,
            graph,
            source_fn,
            num_steps=_num_steps(args),
            optimizer=args.optimizer,
            learning_rate=args.learning_rate,
            mesh=mesh,
            log_every=args.log_steps,
            seed=args.seed,
            prefetch_depth=args.prefetch_depth,
            prefetch_threads=args.prefetch_threads,
            sampler_depth=args.sampler_depth,
            checkpoint_dir=args.model_dir or None,
            profile_dir=args.profile_dir or None,
            step_hook=step_hook,
        )
    finally:
        if recorder is not None:
            # export even on an interrupted run — the trace of a run
            # that died mid-step is exactly the one worth reading
            recorder.stop()
            from euler_tpu.trace import write_trace

            os.makedirs(
                os.path.dirname(args.trace_file) or ".", exist_ok=True
            )
            # --profile_dir device lanes merge in, time-aligned via the
            # eg_align marker train() stamped into the capture
            trace = write_trace(args.trace_file, recorder, graph,
                                profile_dir=args.profile_dir or None)
            log.info(
                "trace: %d events -> %s (open in ui.perfetto.dev)",
                len(trace["traceEvents"]), args.trace_file,
            )
    return state, history


def _restore_state(model, graph, args, mesh):
    import jax

    from euler_tpu.checkpoint import Checkpointer

    opt = train_lib.get_optimizer(args.optimizer, args.learning_rate)
    example = np.asarray(
        graph.sample_node(args.batch_size, args.train_node_type)
    )
    state = model.init_state(jax.random.PRNGKey(args.seed), graph, example,
                             opt)
    # Model-parallel training saved tables row-padded to the model axis;
    # the restore template must match those shapes (same --model_parallel
    # as training).
    from euler_tpu.parallel import pad_tables_for_mesh

    state = pad_tables_for_mesh(state, mesh)
    ckpt = Checkpointer(args.model_dir)
    try:
        if ckpt.latest_step() is not None:
            state = ckpt.restore(state)
        else:
            log.warning("no checkpoint in %s; using fresh params",
                        args.model_dir)
    finally:
        ckpt.close()
    return state


def run_evaluate(model, graph, args, mesh):
    state = _restore_state(model, graph, args, mesh)
    if args.id_file:
        ids = np.concatenate([
            np.loadtxt(f, dtype=np.int64).reshape(-1)
            for f in args.id_file.split(",")
        ])
    else:
        ids = np.arange(args.max_id + 1, dtype=np.int64)
    batch = args.batch_size
    # Wrap-pad to a full batch multiple so every jitted shape is static
    # (the reference streams exact ragged batches; with |ids| >> batch the
    # duplicated rows are a negligible fraction of the metric counts).
    # np.resize cycles ids, so this works even when len(ids) < pad.
    pad = (-len(ids)) % batch
    padded = np.resize(ids, len(ids) + pad) if pad else ids

    def batches():
        for i in range(0, len(padded), batch):
            yield padded[i : i + batch]

    result = train_lib.evaluate(model, graph, batches(), state, mesh=mesh)
    import jax

    if args.model_dir and jax.process_index() == 0:
        # persist the metrics next to the checkpoint so callers (dress
        # rehearsals, sweep scripts) can gate on them instead of
        # scraping logs
        import json

        os.makedirs(args.model_dir, exist_ok=True)
        with open(os.path.join(args.model_dir, "eval.json"), "w") as f:
            json.dump(
                {**result, "id_file": args.id_file, "model": args.model},
                f,
            )
    return result


def run_save_embedding(model, graph, args, mesh):
    state = _restore_state(model, graph, args, mesh)
    emb = train_lib.save_embedding(
        model, graph, args.max_id, state, batch_size=args.batch_size,
        mesh=mesh,
    )
    os.makedirs(args.model_dir, exist_ok=True)
    out = os.path.join(args.model_dir, "embedding.npy")
    np.save(out, emb)
    ids_out = os.path.join(args.model_dir, "id.txt")
    np.savetxt(ids_out, np.arange(args.max_id + 1, dtype=np.int64), fmt="%d")
    log.info("saved %s %s and %s", out, emb.shape, ids_out)
    return out


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    # Orbax/absl emit per-save INFO spam once a root handler exists.
    logging.getLogger("absl").setLevel(logging.WARNING)
    args = define_flags().parse_args(argv)
    check_serve_flags(args)
    if args.coordinator_addr:
        import jax

        jax.distributed.initialize(
            coordinator_address=args.coordinator_addr,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    if not args.telemetry:
        # kill-switch BEFORE any graph/service exists so not even the
        # discovery calls record histograms
        from euler_tpu.telemetry import set_telemetry

        set_telemetry(False)
    from euler_tpu import blackbox as blackbox_mod

    if not args.blackbox:
        blackbox_mod.set_blackbox(False)
    # compile cache + device plane: before any jit so the cache covers
    # the first program and the listener sees every compile
    from euler_tpu import devprof as devprof_mod
    from euler_tpu.parallel import enable_compile_cache

    log.info("persistent compile cache: %s", enable_compile_cache() or "off")
    devprof_mod.setup(enabled=args.devprof, sample_ms=1000)
    if args.postmortem_dir:
        # arm BEFORE any graph/service exists, so even a crash during
        # load or discovery leaves a dump
        blackbox_mod.install(args.postmortem_dir,
                             shard=args.process_id)

    def _exception_postmortem():
        # crash-dump-on-unhandled-exception: the Python twin of the
        # fatal-signal path — same dump format (signal 0 =
        # "exception"), so an incident reads identically whether the
        # process died in native or Python code. The exception itself
        # still propagates (the traceback is the Python half of the
        # postmortem).
        if not args.postmortem_dir:
            return
        path = os.path.join(
            args.postmortem_dir,
            f"postmortem.{os.getpid()}.exception.json",
        )
        try:
            blackbox_mod.write_postmortem(path)
            log.error("unhandled exception; postmortem: %s", path)
        except Exception:
            log.exception("postmortem dump failed")

    recorder = None
    if args.trace_file and args.mode == "train":
        # before the graph is built: the set-up spans (OBSERVABILITY.md
        # "Set-up phases") reach the sink from Graph() on
        from euler_tpu.trace import TraceRecorder

        recorder = TraceRecorder().start()
    try:
        graph, services = build_graph(args)
    except Exception:
        _exception_postmortem()
        if recorder is not None:
            recorder.stop()
        raise
    try:
        mesh = make_mesh(args.num_devices, model_parallel=args.model_parallel)
        # multi-chip device sampling keeps the fused Pallas draw by
        # running it per shard inside shard_map (plain pjit cannot
        # partition pallas_call): the mesh is registered for exactly
        # this run — build_model's consts, every mode's restore and
        # every jitted step trace inside the scope.
        with device_graph.kernel_mesh_scope(mesh):
            model = build_model(args, graph)
            if args.mode == "train":
                run_train(model, graph, args, mesh, recorder)
                if args.serve_after:
                    # train -> save -> immediately serve: the freshest
                    # checkpoint goes live without a second process or a
                    # re-parse of the data dir. Serves with the TRAINING
                    # sampling config (train_edge metapaths) — documented
                    # trade-off; `python -m euler_tpu.serve` is the
                    # inference-config path. Blocks until SIGTERM/SIGINT,
                    # then drains.
                    from euler_tpu import serve as serve_mod

                    serve_mod.run_serve(model, graph, args, mesh)
            elif args.mode == "evaluate":
                run_evaluate(model, graph, args, mesh)
            else:
                run_save_embedding(model, graph, args, mesh)
    except Exception:
        _exception_postmortem()
        raise
    finally:
        if recorder is not None:
            recorder.stop()  # (a raise before run_train took it over)
        # transport + server survivability ledger (eg_counters_* ABI):
        # in shared mode this process also served its shard, so the
        # snapshot covers both sides — busy_rejects/handler_timeouts/
        # deadline_rejects next to the client's retries/failovers
        ledger = {k: v for k, v in euler_tpu.counters().items() if v}
        if ledger:
            log.info("transport/server counters: %s", ledger)
        for s in services:
            # GraphService: finish in-flight shard requests before the
            # teardown (the registry server has no drain phase)
            if hasattr(s, "drain"):
                s.drain()
            s.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
