"""GraphSAGE models (supervised + unsupervised).

Reference equivalent: tf_euler/python/models/graphsage.py (:26 GraphSage,
:59 SupervisedGraphSage) and examples/sage.py. Sampling (fanout + feature
gather) runs on the host in one fused native call; the device module is the
aggregation pyramid + decoder.
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax.linen as nn
import jax
import numpy as np

from euler_tpu.models import base
from euler_tpu.nn import metrics
from euler_tpu.nn.encoders import (
    SageEncoder,
    ScalableSageEncoder,
    ShallowEncoder,
)


class _SupervisedSageModule(nn.Module):
    fanouts: Sequence[int]
    dim: int
    num_classes: int
    aggregator: str = "mean"
    concat: bool = False
    sigmoid_loss: bool = True
    # node-encoder config
    feature_dim: int = 0
    max_id: int = -1
    embedding_dim: int = 16
    sparse_feature_max_ids: Sequence[int] = ()
    # device-sampling mode: per-hop keys into consts["adj"]
    hop_adj_keys: Sequence[str] = ()

    def setup(self):
        self.node_encoder = ShallowEncoder(
            feature_dim=self.feature_dim,
            max_id=self.max_id,
            embedding_dim=self.embedding_dim,
            sparse_feature_max_ids=self.sparse_feature_max_ids,
        )
        self.encoder = SageEncoder(
            self.fanouts, self.dim, self.aggregator, self.concat
        )
        self.predict = nn.Dense(self.num_classes)

    def _hops(self, batch, consts):
        """Training inputs per hop: host-sampled ("hops") or sampled HERE
        on device from the HBM-resident adjacency ("roots" + "seed")."""
        if "hops" in batch:
            return batch["hops"]
        from euler_tpu.graph import device as device_graph

        with jax.named_scope("draw"):
            key = jax.random.PRNGKey(batch["seed"][0])
            adjs = [consts["adj"][k] for k in self.hop_adj_keys]
            ids = device_graph.sample_fanout(
                adjs, batch["roots"], key, list(self.fanouts)
            )
        if self.max_id >= 0:  # use_id: the gids double as embedding ids
            return [{"gids": i, "ids": i} for i in ids]
        return [{"gids": i} for i in ids]

    def _embed_hops(self, hops, consts):
        hidden = [
            self.node_encoder(
                base.gather_consts(f, consts, self.feature_dim)
            )
            for f in hops
        ]
        return self.encoder(hidden)

    def embed(self, batch, consts=None):
        return self._embed_hops(self._hops(batch, consts), consts)

    def __call__(self, batch, consts=None):
        hops = self._hops(batch, consts)
        embedding = self._embed_hops(hops, consts)
        with jax.named_scope("dense"):
            logits = self.predict(embedding)
        labels = base.lookup_labels(batch, consts, hops[0].get("gids"))
        loss, predictions = base.supervised_decoder(
            logits, labels, self.sigmoid_loss
        )
        with jax.named_scope("loss"):
            metric = metrics.f1_counts(labels, predictions)
        return base.ModelOutput(
            embedding=embedding,
            loss=loss,
            metric_name="f1",
            metric=metric,
        )


class SupervisedGraphSage(base.Model):
    """Supervised node classification (reference models/graphsage.py:59-78,
    examples/sage.py:51-76)."""

    metric_name = "f1"

    def __init__(
        self,
        label_idx: int,
        label_dim: int,
        metapath: Sequence[Sequence[int]],
        fanouts: Sequence[int],
        dim: int,
        feature_idx: int = -1,
        feature_dim: int = 0,
        aggregator: str = "mean",
        concat: bool = False,
        max_id: int = -1,
        use_id: bool = False,
        embedding_dim: int = 16,
        sparse_feature_idx: Sequence[int] = (),
        sparse_feature_max_ids: Sequence[int] = (),
        sparse_max_len: int = 16,
        num_classes: Optional[int] = None,
        sigmoid_loss: bool = True,
        device_features: bool = False,
        feature_dtype: Optional[str] = None,
        device_sampling: bool = False,
        train_node_type: int = -1,
    ):
        super().__init__()
        self.feature_dtype = feature_dtype
        self.train_node_type = train_node_type
        self.device_features = base.resolve_device_features(
            device_features, feature_idx, max_id,
            has_sparse=bool(sparse_feature_idx),
        )
        self.max_id = max_id
        self.init_device_sampling(device_sampling)
        self.label_idx = label_idx
        self.label_dim = label_dim
        self.metapath = [list(m) for m in metapath]
        self.fanouts = list(fanouts)
        self.feature_idx = feature_idx
        self.feature_dim = feature_dim
        self.use_id = use_id
        self.sparse_feature_idx = list(sparse_feature_idx)
        self.sparse_feature_max_ids = list(sparse_feature_max_ids)
        self.sparse_max_len = sparse_max_len
        self.default_node = max_id + 1 if max_id >= 0 else -1
        # device-sampling: one adjacency slab per distinct hop type-set,
        # hops referencing the same set share one upload
        self._hop_adj_keys = [self.adj_key(m) for m in self.metapath]
        self.module = _SupervisedSageModule(
            fanouts=tuple(fanouts),
            dim=dim,
            num_classes=num_classes or label_dim,
            aggregator=aggregator,
            concat=concat,
            sigmoid_loss=sigmoid_loss,
            feature_dim=feature_dim if feature_idx >= 0 else 0,
            max_id=max_id if use_id else -1,
            embedding_dim=embedding_dim,
            sparse_feature_max_ids=tuple(sparse_feature_max_ids),
            hop_adj_keys=tuple(self._hop_adj_keys),
        )

    def build_consts(self, graph) -> dict:
        consts = super().build_consts(graph)
        if self.device_sampling:
            self.add_sampling_consts(consts, graph, self.metapath)
        return consts

    def _batch_from_hops(self, graph, inputs, ids_per_hop) -> dict:
        hops = [self.node_inputs(graph, ids) for ids in ids_per_hop]
        if self.device_features:
            return {"hops": hops}  # labels gathered on device from consts
        labels = graph.get_dense_feature(
            inputs, [self.label_idx], [self.label_dim]
        )
        return {"hops": hops, "labels": labels}

    def sample(self, graph, inputs) -> dict:
        inputs = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            # the fanout happens inside the jitted step; host ships only
            # root ids + a per-batch seed for the device RNG
            return self.device_sample_batch(inputs)
        ids_per_hop, _, _ = graph.sample_fanout(
            inputs, self.metapath, self.fanouts, self.default_node
        )
        return self._batch_from_hops(graph, inputs, ids_per_hop)

    def sample_start(self, graph, inputs):
        """Non-blocking half of sample() for the sampler_depth pipeline:
        submit the whole fan-out as one native async op (hop chain on
        the remote client's dispatcher pool) and return immediately.
        Falls back to the synchronous sample() whenever the graph has no
        async path (local mode, mock graphs) or the native op pool is
        momentarily full — the pipeline then still works, just without
        native overlap for that step."""
        inputs = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.device_sample_batch(inputs)
        start = getattr(graph, "sample_fanout_async", None)
        handle = (
            start(inputs, self.metapath, self.fanouts, self.default_node)
            if start is not None
            else None
        )
        if handle is None:
            return self.sample(graph, inputs)
        return (inputs, handle)

    def sample_finish(self, graph, pending) -> dict:
        if not (
            isinstance(pending, tuple)
            and len(pending) == 2
            and hasattr(pending[1], "take")
        ):
            return pending  # sample_start already produced the batch
        inputs, handle = pending
        ids_per_hop, _, _ = handle.take()
        return self._batch_from_hops(graph, inputs, ids_per_hop)


class _ScalableSageModule(nn.Module):
    """Training-mode ScalableSage forward: 1-hop fanout + per-layer store
    reads (reference encoders.py:449-483)."""

    fanout: int
    num_layers: int
    dim: int
    num_classes: int
    aggregator: str = "mean"
    concat: bool = False
    sigmoid_loss: bool = True
    feature_dim: int = 0
    max_id: int = -1
    embedding_dim: int = 16

    def setup(self):
        self.node_encoder = ShallowEncoder(
            feature_dim=self.feature_dim,
            max_id=self.max_id,
            embedding_dim=self.embedding_dim,
        )
        self.encoder = ScalableSageEncoder(
            fanout=self.fanout,
            num_layers=self.num_layers,
            dim=self.dim,
            aggregator=self.aggregator,
            concat=self.concat,
        )
        self.predict = nn.Dense(self.num_classes)

    def forward_train(self, batch, store_reads, consts=None):
        node_feat = self.node_encoder(
            base.gather_consts(
                batch["node_feats"], consts, self.feature_dim
            )
        )
        neigh_feat = self.node_encoder(
            base.gather_consts(
                batch["neigh_feats"], consts, self.feature_dim
            )
        )
        emb, node_embeddings = self.encoder(node_feat, neigh_feat, store_reads)
        logits = self.predict(emb)
        labels = base.lookup_labels(batch, consts, batch["node_ids"])
        loss, predictions = base.supervised_decoder(
            logits, labels, self.sigmoid_loss
        )
        return (
            loss,
            metrics.f1_counts(labels, predictions),
            node_embeddings,
            emb,
        )

    def __call__(self, batch, store_reads, consts=None):
        loss, f1c, _, emb = self.forward_train(batch, store_reads, consts)
        return base.ModelOutput(
            embedding=emb, loss=loss, metric_name="f1", metric=f1c
        )


class ScalableSage(base.ScalableStoreModel):
    """ScalableSage (reference models/graphsage.py:81 + encoders.py:404-519):
    GraphSAGE whose receptive field is capped at one sampled hop per step by
    per-layer historical-embedding stores. Store machinery inherited from
    base.ScalableStoreModel."""

    metric_name = "f1"

    def __init__(
        self,
        label_idx: int,
        label_dim: int,
        edge_type: Sequence[int],
        fanout: int,
        num_layers: int,
        dim: int,
        max_id: int,
        aggregator: str = "mean",
        concat: bool = False,
        feature_idx: int = -1,
        feature_dim: int = 0,
        use_id: bool = False,
        embedding_dim: int = 16,
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
        self.fanout = fanout
        self.num_layers = num_layers
        self.dim = dim
        self.max_id = max_id
        self.feature_idx = feature_idx
        self.feature_dim = feature_dim
        self.use_id = use_id
        self.store_learning_rate = store_learning_rate
        self.store_init_maxval = store_init_maxval
        self._adj_key = self.adj_key(self.edge_type)
        self.module = _ScalableSageModule(
            fanout=fanout,
            num_layers=num_layers,
            dim=dim,
            num_classes=num_classes or label_dim,
            aggregator=aggregator,
            concat=concat,
            sigmoid_loss=sigmoid_loss,
            feature_dim=feature_dim if feature_idx >= 0 else 0,
            max_id=max_id if use_id else -1,
            embedding_dim=embedding_dim,
        )

    def build_consts(self, graph) -> dict:
        consts = super().build_consts(graph)
        if self.device_sampling:
            self.add_sampling_consts(consts, graph, [self.edge_type])
        return consts

    def _expand_batch(self, batch, consts):
        if "roots" not in batch:
            return batch
        import jax

        from euler_tpu.graph import device as device_graph

        roots = batch["roots"]
        with jax.named_scope("draw"):
            key = jax.random.PRNGKey(batch["seed"][0])
            neigh = device_graph.sample_neighbor(
                consts["adj"][self._adj_key], roots, key, self.fanout
            ).reshape(-1)
        node_feats = {"gids": roots}
        neigh_feats = {"gids": neigh}
        if self.use_id:
            node_feats["ids"] = roots
            neigh_feats["ids"] = neigh
        return {
            "node_feats": node_feats,
            "neigh_feats": neigh_feats,
            "node_ids": roots,
            "neigh_ids": neigh,
        }

    def sample(self, graph, inputs) -> dict:
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.device_sample_batch(roots)
        ids_per_hop, _, _ = graph.sample_fanout(
            roots, [self.edge_type], [self.fanout], self.max_id + 1
        )
        neigh = ids_per_hop[1]
        batch = {
            "node_feats": self.node_inputs(graph, roots),
            "neigh_feats": self.node_inputs(graph, neigh),
            "node_ids": np.clip(roots, 0, self.max_id + 1),
            "neigh_ids": np.clip(neigh, 0, self.max_id + 1),
        }
        if not self.device_features:
            batch["labels"] = graph.get_dense_feature(
                roots, [self.label_idx], [self.label_dim]
            )
        return batch


class _UnsupervisedSageModule(nn.Module):
    fanouts: Sequence[int]
    dim: int
    aggregator: str = "mean"
    concat: bool = False
    xent_loss: bool = False
    feature_dim: int = 0
    max_id: int = -1
    embedding_dim: int = 16
    sparse_feature_max_ids: Sequence[int] = ()
    shared_negs: bool = False
    # device-sampling mode
    hop_adj_keys: Sequence[str] = ()
    pos_adj_key: str = ""
    num_negs: int = 5

    def setup(self):
        self.node_encoder = ShallowEncoder(
            feature_dim=self.feature_dim,
            max_id=self.max_id,
            embedding_dim=self.embedding_dim,
            sparse_feature_max_ids=self.sparse_feature_max_ids,
        )
        self.encoder = SageEncoder(
            self.fanouts, self.dim, self.aggregator, self.concat
        )
        # Context encoder: separate tower over the same input layout
        # (reference GraphSage.{target,context}_encoder are two encoders,
        # models/graphsage.py:26-56).
        self.context_node_encoder = ShallowEncoder(
            feature_dim=self.feature_dim,
            max_id=self.max_id,
            embedding_dim=self.embedding_dim,
            sparse_feature_max_ids=self.sparse_feature_max_ids,
        )
        self.context_encoder = SageEncoder(
            self.fanouts, self.dim, self.aggregator, self.concat
        )

    def _encode(self, hops, context: bool, consts=None):
        hops = [
            base.gather_consts(f, consts, self.feature_dim) for f in hops
        ]
        if context:
            hidden = [self.context_node_encoder(f) for f in hops]
            return self.context_encoder(hidden)
        hidden = [self.node_encoder(f) for f in hops]
        return self.encoder(hidden)

    def _device_fanout(self, roots, consts, key):
        from euler_tpu.graph import device as device_graph

        adjs = [consts["adj"][k] for k in self.hop_adj_keys]
        ids = device_graph.sample_fanout(
            adjs, roots, key, list(self.fanouts)
        )
        if self.max_id >= 0:
            return [{"gids": i, "ids": i} for i in ids]
        return [{"gids": i} for i in ids]

    def _all_hops(self, batch, consts):
        """(src_hops, pos_hops, neg_hops): host-sampled or built here from
        roots + seed (positives = 1-hop draws, negatives = global typed
        draws from consts['negs'])."""
        if "src_hops" in batch:
            return (
                batch["src_hops"],
                batch.get("pos_hops"),
                batch.get("neg_hops"),
            )
        import jax

        from euler_tpu.graph import device as device_graph

        roots = batch["roots"]
        key = jax.random.PRNGKey(batch["seed"][0])
        k_pos, k_neg, k_src, k_p, k_n = jax.random.split(key, 5)
        pos = device_graph.sample_neighbor(
            consts["adj"][self.pos_adj_key], roots, k_pos, 1
        ).reshape(-1)
        negs = device_graph.sample_node(
            consts["negs"], k_neg, roots.shape[0] * self.num_negs
        )
        return (
            self._device_fanout(roots, consts, k_src),
            self._device_fanout(pos, consts, k_p),
            self._device_fanout(negs, consts, k_n),
        )

    def embed(self, batch, consts=None):
        src_hops, _, _ = self._all_hops(batch, consts)
        return self._encode(src_hops, False, consts)

    def __call__(self, batch, consts=None):
        src_hops, pos_hops, neg_hops = self._all_hops(batch, consts)
        emb = self._encode(src_hops, False, consts)
        emb_pos = self._encode(pos_hops, True, consts)
        emb_negs = self._encode(neg_hops, True, consts)
        B = emb.shape[0]
        emb3 = emb.reshape(B, 1, -1)
        pos3 = emb_pos.reshape(B, 1, -1)
        if self.shared_negs:
            loss, mrr = base.shared_negs_decoder(
                emb3, pos3, emb_negs, self.xent_loss
            )
        else:
            negs3 = emb_negs.reshape(B, -1, emb.shape[-1])
            loss, mrr = base.unsupervised_decoder(
                emb3, pos3, negs3, self.xent_loss
            )
        return base.ModelOutput(
            embedding=emb, loss=loss, metric_name="mrr", metric=mrr
        )


class GraphSage(base.Model):
    """Unsupervised GraphSAGE (reference models/graphsage.py:26-56):
    positives are 1-hop neighbors, negatives are global typed samples."""

    metric_name = "mrr"

    def __init__(
        self,
        node_type: int,
        edge_type: Sequence[int],
        max_id: int,
        metapath: Sequence[Sequence[int]],
        fanouts: Sequence[int],
        dim: int,
        num_negs: int = 5,
        feature_idx: int = -1,
        feature_dim: int = 0,
        aggregator: str = "mean",
        concat: bool = False,
        xent_loss: bool = False,
        use_id: bool = False,
        embedding_dim: int = 16,
        device_features: bool = False,
        feature_dtype: Optional[str] = None,
        device_sampling: bool = False,
    ):
        super().__init__()
        self.feature_dtype = feature_dtype
        self.device_features = base.resolve_device_features(
            device_features, feature_idx, max_id
        )
        self.max_id = max_id
        self.init_device_sampling(device_sampling)
        self.node_type = node_type
        self.edge_type = list(edge_type)
        self.metapath = [list(m) for m in metapath]
        self.fanouts = list(fanouts)
        self.num_negs = num_negs
        self.feature_idx = feature_idx
        self.feature_dim = feature_dim
        self.use_id = use_id
        self.default_node = max_id + 1
        self._hop_adj_keys = [self.adj_key(m) for m in self.metapath]
        self._pos_adj_key = self.adj_key(self.edge_type)
        self.module = _UnsupervisedSageModule(
            fanouts=tuple(fanouts),
            dim=dim,
            aggregator=aggregator,
            concat=concat,
            xent_loss=xent_loss,
            feature_dim=feature_dim if feature_idx >= 0 else 0,
            max_id=max_id if use_id else -1,
            embedding_dim=embedding_dim,
            hop_adj_keys=tuple(self._hop_adj_keys),
            pos_adj_key=self._pos_adj_key,
            num_negs=num_negs,
        )

    def build_consts(self, graph) -> dict:
        consts = super().build_consts(graph)
        if self.device_sampling:
            # typed negatives (reference: global sample_node(node_type))
            self.add_sampling_consts(
                consts, graph, self.metapath + [self.edge_type],
                negs_type=self.node_type,
            )
        return consts

    def _hops(self, graph, ids: np.ndarray) -> list:
        ids_per_hop, _, _ = graph.sample_fanout(
            ids, self.metapath, self.fanouts, self.default_node
        )
        return [self.node_inputs(graph, hop_ids) for hop_ids in ids_per_hop]

    def sample(self, graph, inputs) -> dict:
        inputs = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.device_sample_batch(inputs)
        pos, _, _ = graph.sample_neighbor(
            inputs, self.edge_type, 1, self.default_node
        )
        negs = graph.sample_node(
            len(inputs) * self.num_negs, self.node_type
        )
        return {
            "src_hops": self._hops(graph, inputs),
            "pos_hops": self._hops(graph, pos.reshape(-1)),
            "neg_hops": self._hops(graph, negs),
        }

    def sample_embed(self, graph, inputs) -> dict:
        inputs = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.sample(graph, inputs)
        return {"src_hops": self._hops(graph, inputs)}
