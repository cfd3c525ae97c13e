"""GCN models: full-neighbor supervised GCN + ScalableGCN.

Reference equivalents: tf_euler/python/models/gcn.py (SupervisedGCN :26,
ScalableGCN :47 + the session-run-hook store machinery) and encoders.py
(GCNEncoder :165, ScalableGCNEncoder :218-324).

TPU adaptations:
- Full-neighbor expansion pads to static per-hop node/edge caps
  (ragged -> fixed shapes); aggregation is a row sum over the device
  expansion's regular edge list, segment_sum over any other
  (nn/sparse_aggregators.py).
- ScalableGCN's embedding/gradient stores are device arrays carried in the
  train state, and the reference's three session hooks (update_store,
  update_gradient, optimize_store) plus the auxiliary store Adam all fuse
  into the single jitted train step.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu import ops
from euler_tpu.models import base
from euler_tpu.nn import metrics, sparse_aggregators
from euler_tpu.nn.encoders import GCNEncoder, ShallowEncoder

log = logging.getLogger("euler_tpu")

# The most slots one block of layer 0's blocked message gather reads
# (``_SupervisedGCNModule._slot_rows``): a block is as many whole parent
# rows as fit under it.
SLOT_BLOCK = 65536


@functools.lru_cache(maxsize=64)
def _log_message_route(hop: int, slots: int, route: str) -> None:
    """One line per distinct shape and outcome, said while tracing (as
    graph/device.py says its draw and expand paths): where layer 0's
    messages of a hop's edge list are gathered from."""
    log.info("message path: hop %d %d slots -> %s", hop, slots, route)


@functools.lru_cache(maxsize=64)
def _log_slot_gather(hop: int, slots: int, blocks: int, rows: int) -> None:
    """One line per distinct shape, said while tracing: a hop whose
    stored-table messages are gathered a block of parent rows at a time."""
    log.info("slot gather: hop %d %d slots -> %d blocks of %d rows, while "
             "the set's real rows last", hop, slots, blocks, rows)


class _SupervisedGCNModule(nn.Module):
    num_layers: int
    dim: int
    num_classes: int
    aggregator: str = "gcn"
    use_residual: bool = False
    sigmoid_loss: bool = True
    feature_dim: int = 0
    max_id: int = -1
    embedding_dim: int = 16
    sparse_feature_max_ids: Sequence[int] = ()
    # device-sampling mode: per-hop keys into consts["adj"] + static
    # unique-node caps (the full-neighbor expansion is deterministic, so
    # "sampling" here is just the on-device multi-hop dedup)
    hop_adj_keys: Sequence[str] = ()
    node_caps: Sequence[int] = ()

    def setup(self):
        self.node_encoder = ShallowEncoder(
            dim=self.dim if self.use_residual else None,
            feature_dim=self.feature_dim,
            max_id=self.max_id,
            embedding_dim=self.embedding_dim,
            sparse_feature_max_ids=tuple(self.sparse_feature_max_ids),
            combiner="add" if self.use_residual else "concat",
        )
        self.encoder = GCNEncoder(
            num_layers=self.num_layers,
            dim=self.dim,
            aggregator=self.aggregator,
            use_residual=self.use_residual,
        )
        self.predict = nn.Dense(self.num_classes)

    def _hops_adjs(self, batch, consts):
        """(hop feature dicts, adjacency dicts): host-built ("hops" +
        "adjs") or expanded HERE on device from the HBM-resident slabs
        ("roots")."""
        if "hops" in batch:
            return batch["hops"], batch["adjs"]
        from euler_tpu.graph import device as device_graph

        adjs = [consts["adj"][k] for k in self.hop_adj_keys]
        hops = device_graph.multi_hop_neighbor(
            adjs, batch["roots"], list(self.node_caps)
        )
        node_sets = [batch["roots"]] + [h["nodes"] for h in hops]
        if self.max_id >= 0:  # use_id: the gids double as embedding ids
            feats = [{"gids": i, "ids": i} for i in node_sets]
        else:
            feats = [{"gids": i} for i in node_sets]
        return feats, hops

    @staticmethod
    def _expand_counters(adjs, gathered):
        """[slots, true edges, unique nodes past a cap, slots whose rows
        layer 0's messages read] of a device expansion
        (SupervisedGCN.step_counters); None for host-built adjacencies,
        whose expansion raises where a cap does not hold. ``gathered``:
        the last of these, or None where every slot's row is read."""
        if not all("overflow" in a for a in adjs):
            return None
        slots = jnp.float32(sum(a["mask"].shape[0] for a in adjs))
        return jnp.stack([
            slots,
            sum(a["edges"] for a in adjs),
            sum(a["overflow"] for a in adjs).astype(jnp.float32),
            slots if gathered is None else gathered,
        ])

    def _hop_rows_why(self, batch, consts):
        """Why layer 0's messages have to be read out of the hops' own
        rows, or None where a hop's rows are nothing but rows of the
        device-resident feature table (a device expansion's ``gids``, a
        node encoder that is the identity on the dense rows): every
        aggregator takes them a slot (``SlotRows``), and ``_forward``
        gathers the messages from the stored table in one pass."""
        if "hops" in batch:
            return "host-expanded batch"
        if not consts or "features" not in consts:
            return "no device-resident feature table"
        if self.max_id >= 0:
            return "use_id: an id embedding beside the rows"
        if self.sparse_feature_max_ids:
            return "sparse features beside the rows"
        if self.use_residual:
            return "use_residual: the rows are projected"
        return None

    def _forward(self, batch, consts):
        hops, adjs = self._hops_adjs(batch, consts)
        why = self._hop_rows_why(batch, consts)
        one_pass = why is None
        if one_pass:
            lanes = consts["features"].shape[-1]
            route = f"one pass from the stored table ({lanes} lanes)"
        else:
            route = f"from the hop's rows ({why})"
        for h, adj in enumerate(adjs):
            _log_message_route(h + 1, adj["mask"].shape[0], route)
        # the outermost hop's set has one reader, layer 0's messages:
        # where those come from the table its rows are never gathered
        hidden = [
            self.node_encoder(
                base.gather_consts(f, consts, self.feature_dim)
            )
            for f in (hops[:-1] if one_pass else hops)
        ]
        first_neigh = gathered = None
        if one_pass:
            hidden.append(None)
            first_neigh, gathered = [], jnp.float32(0)
            for h, adj in enumerate(adjs):
                rows, read = self._slot_rows(
                    h + 1, adj["ids"], consts["features"],
                    adjs[h - 1] if h else None)
                first_neigh.append(rows)
                gathered = gathered + read
        embedding = self.encoder(hidden, adjs, first_neigh)
        return embedding, hops, self._expand_counters(adjs, gathered)

    def _slot_rows(self, hop, ids, table, parents=None):
        """Layer 0's messages of one hop's edge list, before the mask:
        the stored table's row of every slot's own node (the expansion's
        ``ids``, which is ``nodes[dst]`` on every unmasked slot), the pad
        lanes cut after that gather (never between two gathers: a 50-wide
        intermediate is laid column-major and a row gather out of it
        reads a row across 50 separated columns, PERF.md section 6).
        ``table[nodes][..., :F][dst]`` to the bit on every unmasked slot.
        Nothing else of the step reads the outer hop's set, so where its
        cap cannot bind its sort and rank are dead code (graph/device.py
        ``multi_hop_neighbor``). Returns the rows and the number of slots
        whose rows were read.

        Where the hop's parents are a previous hop's padded set
        (``parents``) of more than one block, only the blocks that hold
        a real parent row are gathered, in a ``while`` loop: the set's
        real rows are its prefix (``parents["real"]`` of them) and the
        slots are laid ``[C, W]`` by parent, so every slot after them
        holds the default id, and keeps the default row the buffer was
        filled with, which is what one pass reads there: the result is
        the one-pass gather's to the bit. Elsewhere (hop 1, whose parents
        are the roots, or a set of one block) one pass over every slot.
        """
        slots = ids.shape[0]
        if parents is not None:
            C = parents["nodes"].shape[0]
            W = slots // C
            # parent rows a block: the largest divisor of C whose slots
            # fit under SLOT_BLOCK, so the blocks tile the set
            B = next(b for b in range(min(C, max(1, SLOT_BLOCK // W)), 0, -1)
                     if C % b == 0)
        if parents is None or B == C:
            with jax.named_scope("gather_features"):
                rows = base.gather_rows(table, ids, self.feature_dim)
            return sparse_aggregators.SlotRows(rows), jnp.float32(slots)
        _log_slot_gather(hop, slots, C // B, B)
        blocks = (parents["real"] + B - 1) // B

        def gather_block(i, buf):
            # scoped inside the body: the ``while`` itself carries no
            # scope, or its event would count its body's time again
            with jax.named_scope("gather_features"):
                start = i * (B * W)
                block = jax.lax.dynamic_slice(ids, (start,), (B * W,))
                return jax.lax.dynamic_update_slice(
                    buf, table[block], (start, 0))

        with jax.named_scope("gather_features"):
            # the default row: where a default parent row is left unread,
            # the last slot is one of its slots and holds the default id
            buf = jnp.broadcast_to(table[ids[-1]], (slots, table.shape[1]))
        buf = jax.lax.fori_loop(0, blocks, gather_block, buf)
        with jax.named_scope("gather_features"):
            # cut and cast after the loop, as base.gather_rows does
            rows = buf[:, :self.feature_dim].astype(jnp.float32)
        read = (blocks * (B * W)).astype(jnp.float32)
        return sparse_aggregators.SlotRows(rows), read

    def embed(self, batch, consts=None):
        return self._forward(batch, consts)[0]

    def __call__(self, batch, consts=None):
        embedding, hops, counters = self._forward(batch, consts)
        with jax.named_scope("dense"):
            logits = self.predict(embedding)
        labels = base.lookup_labels(batch, consts, hops[0].get("gids"))
        loss, predictions = base.supervised_decoder(
            logits, labels, self.sigmoid_loss
        )
        return base.ModelOutput(
            embedding=embedding,
            loss=loss,
            metric_name="f1",
            metric=metrics.f1_counts(labels, predictions),
            counters=counters,
        )


class SupervisedGCN(base.Model):
    """Full-neighbor GCN (reference models/gcn.py:26). max_nodes_per_hop /
    max_edges_per_hop are the static pad caps required for TPU shapes."""

    metric_name = "f1"
    # full-neighborhood aggregation walks the 2-D slab (device.py
    # multi_hop_neighbor) — the flat-CSR alias form has no slab to walk
    alias_sampling_ok = False

    def __init__(
        self,
        label_idx: int,
        label_dim: int,
        metapath: Sequence[Sequence[int]],
        dim: int,
        max_nodes_per_hop: Sequence[int],
        max_edges_per_hop: Sequence[int],
        aggregator: str = "gcn",
        feature_idx: int = -1,
        feature_dim: int = 0,
        max_id: int = -1,
        use_id: bool = False,
        embedding_dim: int = 16,
        sparse_feature_idx: Sequence[int] = (),
        sparse_feature_max_ids: Sequence[int] = (),
        sparse_max_len: int = 16,
        use_residual: bool = False,
        num_classes: Optional[int] = None,
        sigmoid_loss: bool = True,
        device_features: bool = False,
        feature_dtype: Optional[str] = None,
        device_sampling: bool = False,
        max_degree: Optional[int] = None,
    ):
        super().__init__()
        self.feature_dtype = feature_dtype
        self.device_features = base.resolve_device_features(
            device_features, feature_idx, max_id
        )
        self.init_device_sampling(device_sampling)
        if self.device_sampling:
            # a hop past its static cap drops nodes, and layer 0's
            # messages skip the blocks of default parent rows: counted
            # in the step
            self.step_counters = (
                "expand_slots", "expand_edges", "expand_overflow_nodes",
                "expand_gathered_slots",
            )
        self.label_idx = label_idx
        self.label_dim = label_dim
        self.metapath = [list(m) for m in metapath]
        self.max_nodes_per_hop = list(max_nodes_per_hop)
        self.max_edges_per_hop = list(max_edges_per_hop)
        self.max_degree = max_degree
        self.feature_idx = feature_idx
        self.feature_dim = feature_dim
        self.max_id = max_id
        self.use_id = use_id
        self.sparse_feature_idx = list(sparse_feature_idx)
        self.sparse_feature_max_ids = list(sparse_feature_max_ids)
        self.sparse_max_len = sparse_max_len
        self._hop_adj_keys = [self.adj_key(m) for m in self.metapath]
        self.module = _SupervisedGCNModule(
            num_layers=len(self.metapath),
            dim=dim,
            num_classes=num_classes or label_dim,
            aggregator=aggregator,
            use_residual=use_residual,
            sigmoid_loss=sigmoid_loss,
            feature_dim=feature_dim if feature_idx >= 0 else 0,
            max_id=max_id if use_id else -1,
            embedding_dim=embedding_dim,
            sparse_feature_max_ids=tuple(sparse_feature_max_ids),
            hop_adj_keys=tuple(self._hop_adj_keys),
            node_caps=tuple(self.max_nodes_per_hop),
        )

    def build_consts(self, graph) -> dict:
        consts = super().build_consts(graph)
        if self.device_sampling:
            self.add_sampling_consts(
                consts, graph, self.metapath, max_degree=self.max_degree
            )
        return consts

    def sample(self, graph, inputs) -> dict:
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            # the full-neighbor multi-hop expansion happens inside the
            # jitted step (deterministic — the seed is unused)
            return self.device_sample_batch(roots)
        roots, hops = ops.get_multi_hop_neighbor(
            graph,
            roots,
            self.metapath,
            max_nodes_per_hop=self.max_nodes_per_hop,
            max_edges_per_hop=self.max_edges_per_hop,
            default_node=self.max_id + 1 if self.max_id >= 0 else -1,
        )
        hop_feats = [self.node_inputs(graph, roots)] + [
            self.node_inputs(graph, h.nodes) for h in hops
        ]
        batch = {"hops": hop_feats, "adjs": [h.adj for h in hops]}
        if not self.device_features:
            batch["labels"] = graph.get_dense_feature(
                roots, [self.label_idx], [self.label_dim]
            )
        return batch


class _ScalableGCNModule(nn.Module):
    """Training-mode ScalableGCN forward: 1-hop adjacency + per-layer store
    reads (reference encoders.py:254-288). Pure function of
    (params, store_reads); the store plumbing lives in the train step."""

    num_layers: int
    dim: int
    num_classes: int
    aggregator: str = "gcn"
    use_residual: bool = False
    sigmoid_loss: bool = True
    feature_dim: int = 0
    max_id: int = -1
    embedding_dim: int = 16

    def setup(self):
        self.node_encoder = ShallowEncoder(
            dim=self.dim if self.use_residual else None,
            feature_dim=self.feature_dim,
            max_id=self.max_id,
            embedding_dim=self.embedding_dim,
            combiner="add" if self.use_residual else "concat",
        )
        agg_cls = sparse_aggregators.get(self.aggregator)
        self.aggs = [
            agg_cls(
                self.dim,
                activation=nn.relu if l < self.num_layers - 1 else None,
            )
            for l in range(self.num_layers)
        ]
        self.predict = nn.Dense(self.num_classes)

    def forward_train(self, batch, store_reads, consts=None):
        node_emb = self.node_encoder(
            base.gather_consts(
                batch["node_feats"], consts, self.feature_dim
            )
        )
        neigh_emb = self.node_encoder(
            base.gather_consts(
                batch["neigh_feats"], consts, self.feature_dim
            )
        )
        adj = batch["adj"]
        node_embeddings = []
        for layer in range(self.num_layers):
            h = self.aggs[layer]((node_emb, neigh_emb, adj))
            if self.use_residual:
                h = node_emb + h
            node_emb = h
            node_embeddings.append(node_emb)
            if layer < self.num_layers - 1:
                neigh_emb = store_reads[layer]
        logits = self.predict(node_emb)
        labels = base.lookup_labels(batch, consts, batch["node_ids"])
        loss, predictions = base.supervised_decoder(
            logits, labels, self.sigmoid_loss
        )
        return (
            loss,
            metrics.f1_counts(labels, predictions),
            node_embeddings,
            node_emb,
        )

    def __call__(self, batch, store_reads, consts=None):
        loss, f1c, _, emb = self.forward_train(batch, store_reads, consts)
        return base.ModelOutput(
            embedding=emb, loss=loss, metric_name="f1", metric=f1c
        )


class ScalableGCN(base.ScalableStoreModel):
    """ScalableGCN (reference models/gcn.py:47 + encoders.py:218-324): each
    step samples only the 1-hop neighborhood; deeper layers read stale
    neighbor embeddings from a store. Training machinery inherited
    from base.ScalableStoreModel."""

    metric_name = "f1"
    # _expand_batch gathers full slab rows (adj["nbr"][roots] over W
    # columns) — needs the 2-D slab form
    alias_sampling_ok = False

    def __init__(
        self,
        label_idx: int,
        label_dim: int,
        edge_type: Sequence[int],
        num_layers: int,
        dim: int,
        max_id: int,
        max_neighbors: int,
        max_edges: Optional[int] = None,
        aggregator: str = "gcn",
        feature_idx: int = -1,
        feature_dim: int = 0,
        use_id: bool = False,
        embedding_dim: int = 16,
        use_residual: bool = False,
        store_learning_rate: float = 0.001,
        store_init_maxval: float = 0.05,
        num_classes: Optional[int] = None,
        sigmoid_loss: bool = True,
        device_features: bool = False,
        feature_dtype: Optional[str] = None,
        device_sampling: bool = False,
        train_node_type: int = -1,
    ):
        super().__init__()
        self.feature_dtype = feature_dtype
        self.device_features = base.resolve_device_features(
            device_features, feature_idx, max_id
        )
        self.max_id = max_id
        self.init_device_sampling(device_sampling)
        self.train_node_type = train_node_type
        self.label_idx = label_idx
        self.label_dim = label_dim
        self.edge_type = list(edge_type)
        self.num_layers = num_layers
        self.dim = dim
        # Per-ROOT caps: the reference expands the full ragged 1-hop
        # neighborhood (encoders.py:262 get_multi_hop_neighbor); for static
        # TPU shapes we pad to batch * max_neighbors unique neighbors and
        # batch * max_edges adjacency entries per sampled batch.
        self.max_neighbors = max_neighbors
        self.max_edges = max_edges if max_edges is not None else max_neighbors * 4
        self.feature_idx = feature_idx
        self.feature_dim = feature_dim
        self.use_id = use_id
        self.store_learning_rate = store_learning_rate
        self.store_init_maxval = store_init_maxval
        self.module = _ScalableGCNModule(
            num_layers=num_layers,
            dim=dim,
            num_classes=num_classes or label_dim,
            aggregator=aggregator,
            use_residual=use_residual,
            sigmoid_loss=sigmoid_loss,
            feature_dim=feature_dim if feature_idx >= 0 else 0,
            max_id=max_id if use_id else -1,
            embedding_dim=embedding_dim,
        )
        # NOTE: evaluation uses the same 1-hop + stale-store approximation
        # as training (ScalableStoreModel.make_eval_step). The reference's
        # non-training branch (encoders.py:256-258) instead runs the exact
        # full-neighbor GCN; use SupervisedGCN with the trained params for
        # exact evaluation.

    def build_consts(self, graph) -> dict:
        consts = super().build_consts(graph)
        if self.device_sampling:
            # max_neighbors (the host path's per-root dense cap) bounds
            # the slab width too: a power-law hub must not balloon every
            # batch to B x global-max-degree
            self.add_sampling_consts(
                consts, graph, [self.edge_type],
                max_degree=self.max_neighbors,
            )
        return consts

    def _expand_batch(self, batch, consts):
        """Device full-neighbor expansion: the adjacency slab row IS the
        1-hop neighborhood (padded to W, masked by degree) — no host
        dedup; duplicate neighbor slots add up like duplicate edges.
        """
        if "roots" not in batch:
            return batch
        slab = consts["adj"][self.adj_key(self.edge_type)]
        roots = batch["roots"]
        B = roots.shape[0]
        W = slab["nbr"].shape[1]
        nbrs = slab["nbr"][roots]                      # [B, W]
        deg = slab["deg"][roots]                       # [B]
        mask = (
            jnp.arange(W, dtype=jnp.int32)[None, :] < deg[:, None]
        ).astype(jnp.float32)
        flat = nbrs.reshape(-1)
        adj = {
            # a constant of the static shapes: a regular list, whose rows
            # the sparse aggregators sum (nn/sparse_aggregators.py)
            "src": np.repeat(np.arange(B, dtype=np.int32), W),
            "dst": jnp.arange(B * W, dtype=jnp.int32),
            "mask": mask.reshape(-1),
        }
        node_feats = {"gids": roots}
        neigh_feats = {"gids": flat}
        if self.use_id:
            node_feats["ids"] = roots
            neigh_feats["ids"] = flat
        return {
            "node_feats": node_feats,
            "neigh_feats": neigh_feats,
            "node_ids": roots,
            "neigh_ids": flat,
            "adj": adj,
        }

    def sample(self, graph, inputs) -> dict:
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.device_sample_batch(roots)
        B = len(roots)
        roots_out, hops = ops.get_multi_hop_neighbor(
            graph,
            roots,
            [self.edge_type],
            max_nodes_per_hop=[B * self.max_neighbors],
            max_edges_per_hop=[B * self.max_edges],
            default_node=self.max_id + 1,
        )
        hop = hops[0]
        batch = {
            "node_feats": self.node_inputs(graph, roots_out),
            "neigh_feats": self.node_inputs(graph, hop.nodes),
            "node_ids": np.clip(roots_out, 0, self.max_id + 1),
            "neigh_ids": np.clip(hop.nodes, 0, self.max_id + 1),
            "adj": hop.adj,
        }
        if not self.device_features:
            batch["labels"] = graph.get_dense_feature(
                roots, [self.label_idx], [self.label_dim]
            )
        return batch

