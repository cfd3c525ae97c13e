"""Model zoo base classes.

Reference equivalent: tf_euler/python/models/base.py (ModelOutput :28,
UnsupervisedModel :41-105, SupervisedModel :181-234).

Architecture: every model is a pair of phases —
  sample(graph, inputs) -> batch dict        (host, numpy, inside prefetch)
  module.apply(vars, batch) -> ModelOutput   (device, pure JAX, jitted)
The reference interleaves graph ops into the TF graph; splitting them is
what makes the device step a single static XLA program and lets the host
sampler run ahead of the TPU.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from euler_tpu import devprof
from euler_tpu.nn import metrics
from euler_tpu.telemetry import setup_span

log = logging.getLogger("euler_tpu")


@dataclasses.dataclass
class ModelOutput:
    embedding: Any
    loss: Any
    metric_name: str
    metric: Any  # scalar (mrr/acc) or f1 counts [tp, fp, fn]
    # per-step event counts of the model's ``step_counters``, in that
    # order ([k] float32), or None: they leave the step beside the metric
    # and train() adds them into the telemetry ledger once a log window
    counters: Any = None


@jax.named_scope("loss")
def supervised_decoder(logits, labels, sigmoid_loss: bool):
    """Loss + hard predictions (reference models/base.py:207-221)."""
    if sigmoid_loss:
        loss = optax.sigmoid_binary_cross_entropy(logits, labels).mean()
        predictions = jnp.floor(nn.sigmoid(logits) + 0.5)
    else:
        loss = optax.softmax_cross_entropy(logits, labels).mean()
        num_classes = logits.shape[-1]
        predictions = nn.one_hot(jnp.argmax(logits, axis=-1), num_classes)
    return loss, predictions


@jax.named_scope("loss")
def unsupervised_decoder(emb, emb_pos, emb_negs, xent_loss: bool):
    """Negative-sampling decoder (reference models/base.py:82-95).

    emb/emb_pos: [B, 1, d]; emb_negs: [B, num_negs, d].
    """
    logits = jnp.einsum("bid,bjd->bij", emb, emb_pos)  # [B,1,1]
    neg_logits = jnp.einsum("bid,bjd->bij", emb, emb_negs)  # [B,1,negs]
    mrr = metrics.mrr(logits, neg_logits)
    if xent_loss:
        true_xent = optax.sigmoid_binary_cross_entropy(
            logits, jnp.ones_like(logits)
        )
        neg_xent = optax.sigmoid_binary_cross_entropy(
            neg_logits, jnp.zeros_like(neg_logits)
        )
        loss = true_xent.sum() + neg_xent.sum()
    else:
        neg_cost = jax_logsumexp(neg_logits)
        loss = -jnp.sum(logits - neg_cost)
    return loss, mrr


def jax_logsumexp(x):
    import jax.scipy.special as jsp

    return jsp.logsumexp(x, axis=2, keepdims=True)


@jax.named_scope("loss")
def shared_negs_decoder(emb, emb_pos, emb_negs, xent_loss: bool):
    """UnsupervisedModelV2-style shared negatives
    (reference models/base.py:152-165): emb_negs [num_negs, d] shared by the
    whole batch."""
    logits = jnp.einsum("bid,bjd->bij", emb, emb_pos)
    neg_logits = jnp.einsum("bid,nd->bin", emb, emb_negs)
    mrr = metrics.mrr(logits, neg_logits)
    if xent_loss:
        true_xent = optax.sigmoid_binary_cross_entropy(
            logits, jnp.ones_like(logits)
        )
        neg_xent = optax.sigmoid_binary_cross_entropy(
            neg_logits, jnp.zeros_like(neg_logits)
        )
        loss = true_xent.sum() + neg_xent.sum()
    else:
        neg_cost = jax_logsumexp(neg_logits)
        loss = -jnp.sum(logits - neg_cost)
    return loss, mrr


def upload_sparse_tables(
    graph, max_id: int, feature_idxs, max_len: int, default_values
) -> list:
    """Padded sparse-feature tables for every node (rows 0..max_id+1, row
    max_id+1 = default/padding), as device arrays ready for
    state['consts'] — one {'ids', 'mask'} dict per feature slot. Shared
    by every model family that gathers sparse features on device."""
    from euler_tpu import ops

    all_ids = np.arange(max_id + 2, dtype=np.int64)
    with setup_span("setup_table_export") as export:
        tables = [
            (t_ids.astype(np.int32), t_mask)
            for t_ids, t_mask in ops.get_sparse_feature(
                graph, all_ids, list(feature_idxs), max_len,
                default_values=list(default_values),
            )
        ]
        export.nbytes = sum(i.nbytes + m.nbytes for i, m in tables)
    with setup_span("setup_upload", export.nbytes):
        return [
            {"ids": jnp.asarray(t_ids), "mask": jnp.asarray(t_mask)}
            for t_ids, t_mask in tables
        ]


def export_table(graph, ids, feature_idx: int, width: int, dtype=None):
    """One whole-table ``get_dense_feature`` export ([len(ids), width]
    float32 through numpy) and its way to the device, each under its
    set-up span. The upload's span ends where ``jnp.asarray`` returns:
    the host's part of it; what the runtime still copies after that runs
    on under whatever comes next (no fence here: it would hold the next
    table's export, and the step's trace and compile, behind the copy)."""
    with setup_span("setup_table_export") as export:
        host = graph.get_dense_feature(ids, [feature_idx], [width])
        export.nbytes = host.nbytes
    with setup_span("setup_upload", host.nbytes):
        return jnp.asarray(host, dtype=dtype)


# The minor dimension of the TPU's (8, 128) memory tile. The runtime
# lays a 2-D array out in whichever dimension order pads least under
# that tile, so a [nodes, 602] table arrives column-major (602 -> 608
# sublanes against 602 -> 640 lanes) and a row gather from it costs a
# transpose of the whole table every step (PERF.md section 6, PR 28). A
# width that is a multiple of the lanes pads nothing row-major, which
# makes rows-contiguous the runtime's own choice in every jit that takes
# the feature table, with no layout argument for it anywhere. The one
# layout argument of the program is for the Scalable* stores
# (parallel/mesh.py state_sharding pins them rows-major): their logical
# [max_id + 2, dim] shape is what callers that hand train() a state
# build and read back and what a checkpoint holds, so the width route
# was not open there (PERF.md section 6, PR 31).
TABLE_LANES = 128


def stored_width(feature_dim: int) -> int:
    """The width the dense feature table's rows are stored at:
    ``feature_dim`` rounded up to a multiple of TABLE_LANES (a width that
    already is one stores as it is)."""
    return -(-feature_dim // TABLE_LANES) * TABLE_LANES


def gather_rows(table, ids, feature_dim: int):
    """Rows ``ids`` of the device-resident feature table as the
    [..., feature_dim] float32 the modules compute on: gather stored
    rows, slice the pad lanes off (they reach no matmul, loss or
    gradient), then undo a reduced-precision table's cast."""
    return table[ids][..., :feature_dim].astype(jnp.float32)


def gather_consts(feats: dict, consts: dict, feature_dim: int) -> dict:
    """Materialize device-resident features for one node set: replace the
    host-side 'gids' indices with gathers from the HBM-resident tables
    (dense rows cut back from the stored width to ``feature_dim``, and
    padded sparse id+mask rows when configured). A reduced-precision
    table (feature_dtype='bfloat16') is cast back to float32 after the
    gather so the module math is unchanged — only the HBM-resident bytes
    (and the gather traffic) shrink."""
    if not consts or "gids" not in feats:
        return feats
    feats = dict(feats)
    g = feats["gids"]
    with jax.named_scope("gather_features"):
        if "features" in consts:
            feats["dense"] = gather_rows(
                consts["features"], g, feature_dim
            )
        if "sparse" in consts and "sparse" not in feats:
            feats["sparse"] = [
                (t["ids"][g], t["mask"][g]) for t in consts["sparse"]
            ]
    return feats


def lookup_labels(batch: dict, consts: dict, root_ids):
    """Labels for a supervised batch: host-gathered if present, otherwise
    a device gather from the consts label table at root_ids."""
    if "labels" in batch:
        return batch["labels"]
    if not consts:
        raise ValueError(
            "batch has no 'labels' and no consts tables were passed: a "
            "device_features=True batch must be applied with "
            "state['consts'] (from Model.init_state)"
        )
    with jax.named_scope("gather_labels"):
        return consts["labels"][root_ids]


def resolve_device_features(
    device_features: bool,
    feature_idx: int,
    max_id: int,
    has_sparse: bool = False,
) -> bool:
    """Validate a model's device_features request. Silently off when the
    model has no dense (or sparse, when has_sparse) features; a hard error
    when max_id is unset, because the table would have one row and every
    id would clip to it — silently training all nodes on node 0's
    features."""
    if not device_features or (feature_idx < 0 and not has_sparse):
        return False
    if max_id < 0:
        raise ValueError(
            "device_features=True requires max_id >= 0 (the feature/label "
            "tables are sized max_id+2)"
        )
    return True


class Model:
    """Host-side model driver: owns config, builds the flax module, and
    implements the sampling phase. Subclasses define:
      module: nn.Module with __call__(batch) -> ModelOutput
      sample(graph, inputs) -> batch dict (numpy arrays, fixed shapes)
    and optionally sample_embed/embed for inference. Models with extra
    device state (embedding stores) override init_state/make_train_step.

    device_features=True switches dense feature/label delivery from
    host-gather-and-transfer to device-resident tables: init_state uploads
    the full feature (and label) table to HBM once (state['consts'],
    replicated, or row-sharded over a 'model' mesh axis), sample() ships
    only int32 node ids, and the module gathers rows on device. The
    feature table is stored ``stored_width(feature_dim)`` wide (zero lanes
    past feature_dim), which is what keeps its rows contiguous in HBM so
    the gather reads it in place; the gather hands the module
    [rows, feature_dim]. The train step donates state, so the tables'
    buffers are aliased input to output across steps. This is the
    TPU-native replacement for the reference's PS-side embedding gathers
    (tf_euler/python/utils/embedding.py) and cuts per-step host->device
    traffic by ~feature_dim x."""

    metric_name = "loss"
    batch_size_ratio = 1  # reference Model.batch_size_ratio
    device_features = False
    # storage dtype for the device-resident dense feature table (model
    # constructors expose this as the feature_dtype kwarg; the
    # EULER_TPU_FEATURE_DTYPE env var overrides process-wide). None =
    # float32. 'bfloat16' halves the table's HBM footprint and gather
    # bytes; rows are cast back to float32 at the gather.
    feature_dtype: Optional[str] = None
    # names of the native counters (eg_stats.h) a step of this model
    # counts into, in the order of ModelOutput.counters; () = none
    step_counters: Sequence[str] = ()

    def __init__(self):
        self.module: nn.Module = None

    def sample(self, graph, inputs) -> dict:
        raise NotImplementedError

    # Inference phase: by default reuse the training batch layout.
    def sample_embed(self, graph, inputs) -> dict:
        return self.sample(graph, inputs)

    # ---- split sampling (the sampler_depth pipeline's model API) ----
    # The depth-N step pipeline (euler_tpu/parallel/prefetch.py
    # pipeline(), train.py sampler_depth=) needs sampling split at its
    # blocking point: sample_start submits the step's graph queries
    # WITHOUT waiting (remote graphs: one eg_remote_sample_async op
    # whose hop chain runs on the native dispatcher pool) and returns an
    # opaque pending token; sample_finish blocks on that token and
    # builds the batch. The defaults keep every model correct — start
    # does the whole synchronous sample and finish just unwraps — so
    # only models with an async fast path (SupervisedGraphSage) override.
    def sample_start(self, graph, inputs):
        return self.sample(graph, inputs)

    def sample_finish(self, graph, pending) -> dict:
        return pending

    # ---- device-resident sampling (euler_tpu/graph/device.py) ----
    def init_device_sampling(
        self, device_sampling: bool, require_features: bool = True
    ) -> None:
        """Resolve the device_sampling flag (call AFTER device_features is
        resolved) and set up the per-batch seed counter. Models whose
        encoder can run id-only (shallow embeddings) pass
        require_features=False."""
        import itertools

        if device_sampling and require_features and not self.device_features:
            raise ValueError(
                "device_sampling=True requires device_features=True "
                "(the sampled ids are consumed by on-device gathers)"
            )
        self.device_sampling = bool(device_sampling) and (
            self.device_features or not require_features
        )
        # itertools.count: sample() runs in concurrent prefetch workers
        # and next() is atomic, where += would race and duplicate seeds
        self._sample_seed = itertools.count(1)

    # device-sampling adjacency form, set via set_sampling_options:
    # a max_degree slab cap for heavy-tailed graphs (truncation, the
    # reference-semantics deviation PERF.md prices), or the exact O(E)
    # alias form (no truncation; build_alias_adjacency)
    sampling_max_degree: Optional[int] = None
    sampling_alias: bool = False
    # families whose device pipeline reads the 2-D slab itself (the
    # full-neighborhood GCN path walks adj["nbr"][:, W]) set this False:
    # the flat-CSR alias dict has no slab to walk
    alias_sampling_ok: bool = True

    def set_sampling_options(
        self, max_degree: Optional[int] = None, alias: bool = False
    ) -> None:
        """Choose the device adjacency form BEFORE init_state/train:
        ``max_degree`` caps the padded slab's width (heaviest neighbors
        kept — changes hub distributions, see PERF.md's truncation
        study); ``alias`` switches to the exact flat-CSR alias sampler
        (no truncation, O(edges) memory) — the recommended form for
        power-law graphs. Biased (p/q) walk adjacencies build the alias
        form with id-sorted rows and route through the exact
        rejection-sampled walk (device.alias_biased_random_walk). The
        flags ``--max_degree`` / ``--alias_sampling`` reach this in
        ``run_loop.build_model``, so every caller of it (``main()``,
        ``serve.py``, the benchmark's harness) gets the form the flags
        ask for; a model that cannot take it raises there."""
        if alias and max_degree is not None:
            raise ValueError(
                "alias sampling is exact: max_degree does not apply"
            )
        if alias and not self.alias_sampling_ok:
            raise ValueError(
                f"{type(self).__name__} walks the 2-D adjacency slab "
                "(full-neighborhood aggregation) — alias sampling does "
                "not apply; use max_degree to bound slab width instead"
            )
        self.sampling_max_degree = max_degree
        self.sampling_alias = alias

    @staticmethod
    def adj_key(edge_types, sorted: bool = False) -> str:
        """consts['adj'] key for one edge-type set (shared so every model
        family and its module agree on the naming). sorted=True names the
        id-sorted slab variant biased walks need."""
        return (
            "et" + "_".join(map(str, edge_types))
            + ("_sorted" if sorted else "")
        )

    def add_sampling_consts(
        self,
        consts: dict,
        graph,
        edge_type_sets,
        negs_type: Optional[int] = None,
        max_degree: Optional[int] = None,
        sorted: bool = False,
    ) -> dict:
        """Upload the device-sampling structures: one adjacency slab per
        DISTINCT edge-type set plus an optional typed node sampler for
        negatives.
        ``max_degree`` caps the slab width on heavy-tailed graphs
        (heaviest neighbors kept, build_adjacency warns); ``sorted``
        builds id-sorted rows (under their own keys) for
        device_graph.biased_random_walk. ``max_degree`` defaults to the
        model's set_sampling_options value; so does the slab-vs-alias
        choice (alias = exact flat-CSR tables, never sorted), which
        ``run_loop.build_model`` sets from ``--max_degree`` /
        ``--alias_sampling`` before any consts are built."""
        from euler_tpu.graph import device as device_graph

        from euler_tpu.graph import pallas_sampling

        explicit_cap = max_degree is not None
        if max_degree is None:
            max_degree = self.sampling_max_degree
        # an explicit per-call cap (e.g. GCN's pad-cap slabs) always
        # means "this caller walks the slab" — never swap it for alias
        use_alias = self.sampling_alias and not explicit_cap
        # pack for the fused kernel on a single-device TPU (auto) or when
        # a kernel mesh is registered (per-shard shard_map path)
        use_pallas = pallas_sampling.available() or (
            device_graph.kernel_mesh() is not None
            and pallas_sampling.sharded_available()
        )
        no_pallas_why = "" if use_pallas else device_graph.no_kernel_why()
        adj = consts.setdefault("adj", {})
        for et in edge_type_sets:
            k = self.adj_key(et, sorted=sorted)
            if k not in adj:
                if use_alias:
                    # sorted alias rows feed the exact rejection-sampled
                    # biased walk (alias_biased_random_walk)
                    adj[k] = device_graph.build_alias_adjacency(
                        graph, et, self.max_id, sorted=sorted
                    )
                    continue
                if sorted and max_degree is not None:
                    # ENFORCED guard on the measured distortion: biased
                    # (p/q) walks over a truncated sorted slab sample a
                    # distribution at mean TVD ~0.35 from the reference's
                    # on hub-parent steps (PERF.md walk study) — silently
                    # training Node2Vec on that is not acceptable. The
                    # CSR export is fetched ONCE and the truncation
                    # decision made from its counts, so the guard never
                    # allocates a throwaway (N x max_degree) slab on
                    # exactly the heavy-tail graphs it exists for.
                    pre = device_graph._fetch_flat_csr(
                        graph, et, self.max_id, 65536, sorted=True
                    )
                    trunc = int((pre[0] > max_degree).sum())
                    if trunc:
                        import warnings

                        warnings.warn(
                            "add_sampling_consts: sorted slab for edge "
                            f"types {list(et)} would truncate {trunc} "
                            f"rows at max_degree={max_degree}; biased "
                            "walks on a truncated slab are measurably "
                            "distorted (mean TVD ~0.35, PERF.md walk "
                            "study) — switching this walk adjacency to "
                            "the exact alias+rejection form"
                        )
                        adj[k] = device_graph.build_alias_adjacency(
                            graph, et, self.max_id, sorted=True,
                            _prefetched=pre,
                        )
                        continue
                    slab = device_graph.build_adjacency(
                        graph, et, self.max_id, max_degree=max_degree,
                        sorted=True, _prefetched=pre,
                    )
                else:
                    slab = device_graph.build_adjacency(
                        graph, et, self.max_id, max_degree=max_degree,
                        sorted=sorted,
                    )
                # host-side metadata, never part of the traced consts
                slab.pop("truncated_rows", 0)
                adj[k] = slab
                # packed slab routes sample_neighbor through the fused
                # Pallas kernel (sorted slabs feed biased walks, which
                # read nbr/cum directly — no packing). Said once per
                # slab either way: the draw path a step takes is
                # decided here as much as at trace time.
                why = no_pallas_why
                if sorted:
                    why = "sorted slab: biased walks read nbr/cum directly"
                elif use_pallas:
                    packed = pallas_sampling.pack_adjacency(adj[k])
                    if packed is not None:
                        adj[k]["packed"] = packed
                    else:
                        why = (
                            f"slab width {slab['nbr'].shape[1]} over "
                            f"MAX_W={pallas_sampling.MAX_W} or packed "
                            "copy over MAX_PACKED_BYTES"
                        )
                log.info(
                    "device sampling %s: %s", k,
                    f"slab NOT packed for the Pallas kernel ({why})"
                    if why else "slab packed for the Pallas kernel",
                )
        if negs_type is not None:
            consts["negs"] = device_graph.build_node_sampler(
                graph, negs_type, self.max_id
            )
        return consts

    def device_sample_batch(self, inputs) -> dict:
        """The whole per-step host payload in device-sampling mode: root
        ids + a per-batch RNG seed ([B] so it shards like the rest; the
        module reads element 0 — all equal)."""
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        return {
            "roots": np.clip(roots, 0, self.max_id + 1).astype(np.int32),
            "seed": np.full(
                len(roots), next(self._sample_seed), np.int32
            ),
        }

    def node_inputs(self, graph, ids: np.ndarray) -> dict:
        """Shared host-side gather of one node set's ShallowEncoder inputs,
        driven by the model's configured feature attributes (use_id /
        feature_idx / feature_dim / sparse_feature_idx /
        sparse_feature_max_ids / sparse_max_len / max_id)."""
        from euler_tpu import ops

        ids = np.asarray(ids).reshape(-1)
        feats: dict = {}
        if getattr(self, "use_id", False):
            feats["ids"] = np.clip(ids, 0, self.max_id + 1).astype(np.int32)
        if getattr(self, "feature_idx", -1) >= 0:
            if self.device_features:
                feats["gids"] = (
                    feats["ids"]
                    if "ids" in feats
                    else np.clip(ids, 0, self.max_id + 1).astype(np.int32)
                )
            else:
                feats["dense"] = graph.get_dense_feature(
                    ids, [self.feature_idx], [self.feature_dim]
                )
        sparse_idx = getattr(self, "sparse_feature_idx", [])
        if sparse_idx:
            if self.device_features:
                # the padded sparse tables live in consts (build_consts);
                # the module gathers rows at gids on device
                feats.setdefault(
                    "gids",
                    np.clip(ids, 0, self.max_id + 1).astype(np.int32),
                )
            else:
                feats["sparse"] = ops.get_sparse_feature(
                    graph,
                    ids,
                    sparse_idx,
                    self.sparse_max_len,
                    default_values=[
                        m + 1 for m in self.sparse_feature_max_ids
                    ],
                )
        return feats

    # ---- device state & steps ----
    def build_consts(self, graph) -> dict:
        """Device-resident lookup tables (uploaded once at init). Row
        max_id+1 is the default/padding node; the engine returns zeros for
        it, matching the host-gather path's default fill."""
        if not self.device_features:
            return {}
        n = self.max_id + 2
        ids = np.arange(n, dtype=np.int64)
        consts = {}
        if getattr(self, "feature_idx", -1) >= 0:
            # feature_dtype='bfloat16' (constructor kwarg or
            # EULER_TPU_FEATURE_DTYPE env) halves the table's HBM
            # footprint and the per-step gather bytes; rows are cast back
            # to float32 at the gather (gather_consts), so everything
            # downstream is unchanged. Labels stay float32 — they are
            # loss targets, not gathered at fanout scale.
            dt = self.feature_dtype or os.environ.get(
                "EULER_TPU_FEATURE_DTYPE"
            )
            if dt:
                try:
                    dt = jnp.dtype(dt)
                except TypeError as e:
                    raise ValueError(
                        f"bad feature table dtype {dt!r} (from the "
                        "feature_dtype kwarg or EULER_TPU_FEATURE_DTYPE; "
                        "use a numpy dtype name like 'bfloat16')"
                    ) from e
            # the engine zero-fills a slot up to the width it is asked
            # for, so the lane padding costs no second host copy
            width = stored_width(self.feature_dim)
            consts["features"] = export_table(
                graph, ids, self.feature_idx, width, dtype=dt or None
            )
            devprof.record_feature_table(self.feature_dim, width)
            log.info(
                "feature table: [%d, %d] %s stored [%d, %d], rows "
                "contiguous", n, self.feature_dim,
                consts["features"].dtype, n, width,
            )
        if getattr(self, "label_idx", -1) >= 0:
            consts["labels"] = export_table(
                graph, ids, self.label_idx, self.label_dim
            )
        sparse_idx = getattr(self, "sparse_feature_idx", [])
        if sparse_idx:
            consts["sparse"] = upload_sparse_tables(
                graph, self.max_id, sparse_idx, self.sparse_max_len,
                [m + 1 for m in self.sparse_feature_max_ids],
            )
        return consts

    def _apply(self, params, batch, consts, **kw):
        if consts:
            return self.module.apply({"params": params}, batch, consts, **kw)
        return self.module.apply({"params": params}, batch, **kw)

    def init_state(self, rng, graph, example_inputs, optimizer) -> dict:
        batch = self.sample(graph, example_inputs)
        consts = self.build_consts(graph)
        if consts:
            variables = self.module.init(rng, batch, consts)
        else:
            variables = self.module.init(rng, batch)
        params = variables["params"]
        state = {"params": params, "opt_state": optimizer.init(params)}
        if consts:
            state["consts"] = consts
        return state

    def describe_state(self, state) -> None:
        """Hook ``train()`` calls once, on the state as it sits on the
        devices: say (gauges, route log) what per-node tables the state
        holds besides ``consts``. Default: none."""

    def make_train_step(self, optimizer):
        """Pure (state, batch) -> (state, loss, metric); jitted by the
        trainer with params replicated and batch sharded over 'data'. The
        (donated) consts tables pass through unchanged, so XLA aliases
        each table's output buffer to its input. Aliasing alone does not
        make the step copy-free: a table whose rows are not contiguous
        in the layout it arrives in is re-laid-out before every gather,
        which is why build_consts stores the feature table at a lane-
        multiple width (stored_width). The layer boundaries carry
        the ``jax.named_scope`` names of ``trace.STEP_SCOPES`` (metadata
        only: the compiled program is what it was)."""

        def train_step(state, batch):
            consts = state.get("consts")

            def loss_fn(p):
                out = self._apply(p, batch, consts)
                return out.loss, out

            (loss, out), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state["params"])
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(
                    grads, state["opt_state"], state["params"]
                )
                params = optax.apply_updates(state["params"], updates)
            new_state = {"params": params, "opt_state": opt_state}
            if "consts" in state:
                new_state["consts"] = consts
            if out.counters is not None:
                return new_state, loss, (out.metric, out.counters)
            return new_state, loss, out.metric

        return train_step

    def make_eval_step(self):
        def eval_step(state, batch):
            out = self._apply(state["params"], batch, state.get("consts"))
            return out.loss, out.metric

        return eval_step

    def make_embed_step(self):
        def embed_step(state, batch):
            return self._apply(
                state["params"],
                batch,
                state.get("consts"),
                method=self.module.embed,
            )

        return embed_step


def describe_table(what: str, table, width: int, count: str) -> None:
    """Say what the device made of a per-node [rows, width] table of a
    training state (a Scalable* store, an id-embedding table): the gauges
    ``store_table_width`` / ``store_table_stored_width`` and one
    route-log line, read from the placed array's own layout. A stored
    width of 0 means the table lies column-major: rows not contiguous,
    and a step that gathers and scatters rows copies the whole table
    around them."""
    layout = table.format.layout
    rows_major = tuple(layout.major_to_minor) == (0, 1)
    lanes = layout.tiling[0][-1] if layout.tiling else 1
    stored = -(-width // lanes) * lanes if rows_major else 0
    devprof.record_store_table(width, stored)
    log.info(
        "%s table: [%d, %d] %s %s, device layout major_to_minor=%s "
        "tiling=%s: %s",
        what, table.shape[0], width, table.dtype, count,
        tuple(layout.major_to_minor), tuple(layout.tiling),
        f"rows contiguous, stored {stored} wide" if rows_major
        else "column-major, rows not contiguous",
    )


def last_occurrence(ids):
    """[n] bool: True where no later row of ``ids`` holds the same id."""
    pos = jnp.arange(ids.shape[0])
    later_same = (ids[:, None] == ids[None, :]) & (pos[None, :] > pos[:, None])
    return ~later_same.any(axis=1)


class ScalableStoreModel(Model):
    """Shared training machinery for the Scalable{GCN,Sage} family
    (reference encoders.py:218-519 + the gcn.py/graphsage.py session hooks).

    Each step samples only the 1-hop neighborhood; deeper layers read stale
    neighbor embeddings from per-layer stores. The reference splits the
    bookkeeping across three TF session hooks and an auxiliary Adam; here it
    all fuses into one jitted step:
      1. read stale downstream grads at this batch's nodes, clear the rows
      2. main update from d(loss)/d(params)
      3. store-Adam update from d(store_loss)/d(params), where store_loss =
         sum(node_emb * stale_grad)
      4. scatter-add d(loss + store_loss)/d(store_read) at the neighbors
      5. write fresh activations back to the stores
    A root drawn more than once into a batch has one fresh activation per
    occurrence (its neighbors are drawn anew each time). The reference's
    scatter_update leaves open which one stays; here the last occurrence
    in batch order is written and the others are dropped, so the step is a
    function of its batch whatever order the device writes rows in (the
    host-sampled and device-sampled batches share this step). Steps 1 and
    4 need no rule: the clear writes equal rows, and the clear comes
    before the add for a root that is also a neighbor.
    Requires: self.num_layers, self.dim, self.max_id,
    self.store_learning_rate, self.store_init_maxval, and a module exposing
    forward_train(batch, store_reads) -> (loss, metric, node_embeddings, emb)
    with batch keys node_ids / neigh_ids.
    """

    def init_state(self, rng, graph, example_inputs, optimizer) -> dict:
        batch = self.sample(graph, example_inputs)
        consts = self.build_consts(graph) or None
        # a device-sampling batch (roots + seed) expands here eagerly so
        # the module init sees the node_ids/neigh_ids layout
        batch = self._expand_batch(batch, consts)
        store_reads = [
            jnp.zeros((len(batch["neigh_ids"]), self.dim))
            for _ in range(self.num_layers - 1)
        ]
        # Scalable modules all take consts=None, so pass it positionally.
        variables = self.module.init(rng, batch, store_reads, consts)
        params = variables["params"]
        n_store = self.max_id + 2
        k1 = jax.random.fold_in(rng, 1)
        stores = [
            jax.random.uniform(
                jax.random.fold_in(k1, i),
                (n_store, self.dim),
                minval=0.0,
                maxval=self.store_init_maxval,
            )
            for i in range(1, self.num_layers)
        ]
        grad_stores = [
            jnp.zeros((n_store, self.dim)) for _ in range(1, self.num_layers)
        ]
        store_opt = optax.adam(self.store_learning_rate)
        state = {
            "params": params,
            "opt_state": optimizer.init(params),
            "stores": stores,
            "grad_stores": grad_stores,
            "store_opt_state": store_opt.init(params),
        }
        if consts:
            state["consts"] = consts
        return state

    def make_train_step(self, optimizer):
        store_opt = optax.adam(self.store_learning_rate)
        module = self.module
        num_stores = self.num_layers - 1

        def train_step(state, batch):
            consts = state.get("consts")  # None when not device_features
            batch = self._expand_batch(batch, consts)
            node_ids = batch["node_ids"]
            neigh_ids = batch["neigh_ids"]
            with jax.named_scope("stores_read"):
                store_reads = [s[neigh_ids] for s in state["stores"]]
                stale = [gs[node_ids] for gs in state["grad_stores"]]
                grad_stores = [
                    gs.at[node_ids].set(jnp.zeros_like(s))
                    for gs, s in zip(state["grad_stores"], stale)
                ]

            def forward(params, reads):
                return module.apply(
                    {"params": params},
                    batch,
                    reads,
                    consts,
                    method=module.forward_train,
                )

            def loss_fn(params, reads):
                loss, metric, node_embeddings, _ = forward(params, reads)
                return loss, (metric, node_embeddings)

            (loss, (metric, node_embs)), (gp_main, gr_main) = (
                jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
                    state["params"], store_reads
                )
            )
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(
                    gp_main, state["opt_state"], state["params"]
                )
                params = optax.apply_updates(state["params"], updates)

            if num_stores > 0:

                def store_loss_fn(params, reads):
                    _, _, node_embeddings, _ = forward(params, reads)
                    return sum(
                        jnp.sum(emb * jax.lax.stop_gradient(g))
                        for emb, g in zip(node_embeddings, stale)
                    )

                gp_store, gr_store = jax.grad(
                    store_loss_fn, argnums=(0, 1)
                )(state["params"], store_reads)
                with jax.named_scope("optimizer"):
                    supdates, store_opt_state = store_opt.update(
                        gp_store, state["store_opt_state"], params
                    )
                    params = optax.apply_updates(params, supdates)
                with jax.named_scope("stores_write"):
                    grad_stores = [
                        gs.at[neigh_ids].add(gm + gss)
                        for gs, gm, gss in zip(
                            grad_stores, gr_main, gr_store
                        )
                    ]
            else:
                store_opt_state = state["store_opt_state"]

            with jax.named_scope("stores_write"):
                # rows past the store's end are dropped: every occurrence
                # of a root but its last writes nothing
                keep = last_occurrence(node_ids)
                stores = [
                    s.at[jnp.where(keep, node_ids, s.shape[0])].set(
                        jax.lax.stop_gradient(emb), mode="drop"
                    )
                    for s, emb in zip(state["stores"], node_embs)
                ]
            new_state = {
                "params": params,
                "opt_state": opt_state,
                "stores": stores,
                "grad_stores": grad_stores,
                "store_opt_state": store_opt_state,
            }
            if consts:
                new_state["consts"] = consts
            return new_state, loss, metric

        return train_step

    def describe_state(self, state) -> None:
        """The stores' width and what the device made of it: gauges
        ``store_table_width`` / ``store_table_stored_width`` and one
        route-log line. The stores are [n, dim] float32 with dim under a
        lane tile; left to itself a TPU lays such a table column-major,
        so ``state_sharding`` (parallel/mesh.py, the program's one
        layout argument) pins the store leaves rows-major and a row then
        pads to the tile's lanes. Read here from the arrays' own layout,
        after ``put_global``: a stored width of 0 means a state that
        did not pass ``state_sharding`` on its way to the device."""
        stores = state.get("stores") or []
        if stores:
            describe_table(
                "store", stores[0], self.dim,
                f"x {2 * len(stores)} (stores and gradient stores)")

    def _expand_batch(self, batch, consts):
        """Hook: turn a device-sampling batch (roots + seed) into the
        node_ids/neigh_ids layout inside jit. Default: pass through."""
        return batch

    def _apply_with_stores(self, state, batch):
        batch = self._expand_batch(batch, state.get("consts"))
        store_reads = [s[batch["neigh_ids"]] for s in state["stores"]]
        return self.module.apply(
            {"params": state["params"]},
            batch,
            store_reads,
            state.get("consts"),
        )

    def make_eval_step(self):
        def eval_step(state, batch):
            out = self._apply_with_stores(state, batch)
            return out.loss, out.metric

        return eval_step

    def make_embed_step(self):
        def embed_step(state, batch):
            out = self._apply_with_stores(state, batch)
            return out.embedding

        return embed_step
