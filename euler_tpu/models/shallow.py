"""Shallow network-embedding models: LINE and Node2Vec.

Reference equivalents: tf_euler/python/models/line.py:26 (first/second
order) and node2vec.py:26 (walk -> gen_pair -> shallow encoders). Walks and
pair generation run on the host (one native call for the whole walk chain,
vs the reference's walk_len sequential async RPCs,
tf_euler/kernels/random_walk_op.cc:31-140); the device sees fixed-shape
(src, pos, negs) node-input batches.
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax.linen as nn
import jax
import numpy as np

from euler_tpu import ops
from euler_tpu.models import base
from euler_tpu.nn.encoders import ShallowEncoder


class _ShallowUnsupModule(nn.Module):
    dim: int
    feature_dim: int = 0
    max_id: int = -1
    embedding_dim: int = 16
    sparse_feature_max_ids: Sequence[int] = ()
    combiner: str = "add"
    xent_loss: bool = False
    num_negs: int = 5
    share_context: bool = False  # LINE first-order shares the encoder
    # device-sampling mode: LINE when walk_len == 0, Node2Vec otherwise
    adj_key: str = ""
    walk_len: int = 0
    left_win: int = 0
    right_win: int = 0
    has_features: bool = False
    has_sparse: bool = False
    # node2vec bias; p=q=1 takes the plain-walk fast path. Biased walks
    # need adj_key to name an id-SORTED slab (built by
    # add_sampling_consts(sorted=True)).
    walk_p: float = 1.0
    walk_q: float = 1.0
    # rejection-walk proposal budget (alias adjacencies only); 0 =
    # device.DEFAULT_WALK_TRIALS
    walk_trials: int = 0

    def setup(self):
        kw = dict(
            dim=self.dim,
            feature_dim=self.feature_dim,
            max_id=self.max_id,
            embedding_dim=self.embedding_dim,
            sparse_feature_max_ids=tuple(self.sparse_feature_max_ids),
            combiner=self.combiner,
        )
        self.target = ShallowEncoder(**kw)
        if not self.share_context:
            self.context = ShallowEncoder(**kw)

    def _context(self, x):
        return self.target(x) if self.share_context else self.context(x)

    def _feats(self, ids):
        f = {}
        if self.max_id >= 0:
            f["ids"] = ids
        if self.has_features or self.has_sparse:
            f["gids"] = ids
        return f

    def _inputs(self, batch, consts):
        """(src, pos, negs) encoder inputs: host-sampled or derived here
        from roots + seed (LINE: 1-hop positives; Node2Vec: device walks
        -> skip-gram pairs)."""
        if "src" in batch:
            return batch["src"], batch.get("pos"), batch.get("negs")
        from euler_tpu.graph import device as device_graph

        roots = batch["roots"]
        key = jax.random.PRNGKey(batch["seed"][0])
        k_walk, k_neg = jax.random.split(key)
        adj = consts["adj"][self.adj_key]
        if self.walk_len > 0:
            with jax.named_scope("walk"):
                src, pos = self._walk_pairs(adj, roots, k_walk)
        else:
            src = roots
            with jax.named_scope("draw"):
                pos = device_graph.sample_neighbor(
                    adj, roots, k_walk, 1)[:, 0]
        with jax.named_scope("negatives"):
            negs = device_graph.sample_node(
                consts["negs"], k_neg, src.shape[0] * self.num_negs
            )
        return self._feats(src), self._feats(pos), self._feats(negs)

    def _walk_pairs(self, adj, roots, key):
        """(src, pos) of the skip-gram pairs of one device walk a root:
        ``walk_len`` chained single-neighbour draws, then the window
        rule's static index arrays."""
        from euler_tpu.graph import device as device_graph

        if self.walk_p != 1.0 or self.walk_q != 1.0:
            # trace-time guard: biased membership search is garbage
            # on unsorted rows; the naming convention (adj_key(et,
            # sorted=True)) is the sortedness contract
            if not self.adj_key.endswith("_sorted"):
                raise ValueError(
                    "biased walks (walk_p/walk_q != 1) need an "
                    "id-sorted adjacency slab: build consts with "
                    "add_sampling_consts(sorted=True) and pass the "
                    "matching adj_key(et, sorted=True)"
                )
            if "off" in adj:
                # flat-CSR alias form (chosen by set_sampling_options
                # or forced by the truncation guard): the rejection-
                # sampled walk is exact over FULL neighbor lists
                paths = device_graph.alias_biased_random_walk(
                    adj, roots, key, self.walk_len,
                    self.walk_p, self.walk_q,
                    trials=self.walk_trials or None,
                )
            else:
                paths = device_graph.biased_random_walk(
                    adj, roots, key, self.walk_len,
                    self.walk_p, self.walk_q,
                )
        else:
            paths = device_graph.random_walk(
                adj, roots, key, self.walk_len
            )
        ti, ci = ops.walk.pair_indices(
            self.walk_len + 1, self.left_win, self.right_win
        )
        return paths[:, ti].reshape(-1), paths[:, ci].reshape(-1)

    def _gathered(self, feats, consts):
        return base.gather_consts(feats, consts, self.feature_dim)

    def _rows(self, encoder, feats, consts):
        """One node set through an encoder. The ``pair_rows`` scope holds
        the gathers from the id-embedding tables (and, transposed, the
        scatter-adds of their gradients); the feature gathers and the
        dense layers inside keep their own, inner scopes."""
        with jax.named_scope("pair_rows"):
            return encoder(self._gathered(feats, consts))

    def embed(self, batch, consts=None):
        src, _, _ = self._inputs(batch, consts)
        return self._rows(self.target, src, consts)

    def __call__(self, batch, consts=None):
        src, pos, negs = self._inputs(batch, consts)
        emb = self._rows(self.target, src, consts)  # [B, d]
        emb_pos = self._rows(self._context, pos, consts)
        emb_negs = self._rows(self._context, negs, consts)
        B = emb.shape[0]
        loss, mrr = base.unsupervised_decoder(
            emb.reshape(B, 1, -1),
            emb_pos.reshape(B, 1, -1),
            emb_negs.reshape(B, self.num_negs, -1),
            self.xent_loss,
        )
        return base.ModelOutput(
            embedding=emb, loss=loss, metric_name="mrr", metric=mrr
        )


class _ShallowUnsupervised(base.Model):
    """Shared host plumbing for models whose batch is (src, pos, negs)
    node-input dicts."""

    metric_name = "mrr"

    def __init__(
        self,
        node_type: int,
        max_id: int,
        feature_idx: int = -1,
        feature_dim: int = 0,
        use_id: bool = True,
        sparse_feature_idx: Sequence[int] = (),
        sparse_feature_max_ids: Sequence[int] = (),
        sparse_max_len: int = 16,
        num_negs: int = 5,
        device_features: bool = False,
        feature_dtype: Optional[str] = None,
        device_sampling: bool = False,
    ):
        super().__init__()
        self.feature_dtype = feature_dtype
        self.node_type = node_type
        self.max_id = max_id
        self.feature_idx = feature_idx
        self.feature_dim = feature_dim
        self.use_id = use_id
        self.sparse_feature_idx = list(sparse_feature_idx)
        self.sparse_feature_max_ids = list(sparse_feature_max_ids)
        self.sparse_max_len = sparse_max_len
        self.num_negs = num_negs
        self.device_features = base.resolve_device_features(
            device_features, feature_idx, max_id,
            has_sparse=bool(sparse_feature_idx),
        )
        # the id-embedding path needs no feature table: device_sampling
        # composes with use_id alone (device_features only required when
        # dense features are configured)
        if device_sampling and not self.device_features and (
            feature_idx >= 0 or sparse_feature_idx
        ):
            raise ValueError(
                "device_sampling with dense/sparse features requires "
                "device_features=True (the tables must be HBM-resident)"
            )
        self.init_device_sampling(device_sampling, require_features=False)

    adj_sorted = False  # Node2Vec sets True for biased (p/q != 1) walks

    def build_consts(self, graph) -> dict:
        consts = super().build_consts(graph)
        if self.device_sampling:
            self.add_sampling_consts(
                consts, graph, [self.edge_type],
                negs_type=self.node_type,
                sorted=self.adj_sorted,
            )
        return consts

    def describe_state(self, state) -> None:
        """The id-embedding tables' width and what the device made of it:
        the gauges and the route-log line of ``base.describe_table``,
        read from the placed arrays. The tables are [max_id + 2, dim]
        parameters that every step gathers rows from and scatter-adds
        rows into; a TPU keeps a lane-multiple dim rows-major by itself,
        any other width is the case the line is there to show."""
        tables = [
            tower["Embedding_0"]["embeddings"]
            for _, tower in sorted(state["params"].items())
            if "Embedding_0" in tower
        ]
        if tables:
            base.describe_table(
                "embedding", tables[0], tables[0].shape[1],
                f"x {len(tables)} (id-embedding parameters, each under "
                "the optimizer's state)")

    def _pack(self, graph, src, pos, negs) -> dict:
        return {
            "src": self.node_inputs(graph, src),
            "pos": self.node_inputs(graph, pos),
            "negs": self.node_inputs(graph, negs),
        }

    def sample_embed(self, graph, inputs) -> dict:
        ids = np.asarray(inputs, dtype=np.int64).reshape(-1)
        return {"src": self.node_inputs(graph, ids)}


class LINE(_ShallowUnsupervised):
    """LINE (reference models/line.py:26): positives are direct neighbors;
    order 1 shares the target/context encoder, order 2 uses two towers."""

    def __init__(
        self,
        node_type: int,
        edge_type: Sequence[int],
        max_id: int,
        dim: int,
        order: int = 1,
        combiner: str = "add",
        xent_loss: bool = False,
        embedding_dim: int = 16,
        **kwargs,
    ):
        super().__init__(node_type, max_id, **kwargs)
        if order not in (1, 2, "first", "second"):
            raise ValueError(f"LINE order must be 1 or 2, got {order}")
        self.edge_type = list(edge_type)
        self.module = _ShallowUnsupModule(
            dim=dim,
            feature_dim=self.feature_dim if self.feature_idx >= 0 else 0,
            max_id=max_id if self.use_id else -1,
            embedding_dim=embedding_dim,
            sparse_feature_max_ids=tuple(self.sparse_feature_max_ids),
            combiner=combiner,
            xent_loss=xent_loss,
            num_negs=self.num_negs,
            share_context=order in (1, "first"),
            adj_key=self.adj_key(self.edge_type),
            has_features=self.device_features and self.feature_idx >= 0,
            has_sparse=self.device_features
            and bool(self.sparse_feature_idx),
        )

    def sample(self, graph, inputs) -> dict:
        src = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.device_sample_batch(src)
        pos, _, _ = graph.sample_neighbor(
            src, self.edge_type, 1, self.max_id + 1
        )
        negs = graph.sample_node(len(src) * self.num_negs, self.node_type)
        return self._pack(graph, src, pos.reshape(-1), negs)


class Node2Vec(_ShallowUnsupervised):
    """Node2Vec (reference models/node2vec.py:26): biased walks ->
    skip-gram pairs -> shallow encoders. batch_size_ratio is the pair count
    per root (the effective batch multiplier, reference node2vec.py:44-46).
    """

    def __init__(
        self,
        node_type: int,
        edge_type: Sequence[int],
        max_id: int,
        dim: int,
        walk_len: int = 3,
        walk_p: float = 1.0,
        walk_q: float = 1.0,
        left_win_size: int = 1,
        right_win_size: int = 1,
        combiner: str = "add",
        xent_loss: bool = False,
        embedding_dim: int = 16,
        walk_trials: int = 0,
        **kwargs,
    ):
        super().__init__(node_type, max_id, **kwargs)
        if walk_trials < 0:
            raise ValueError(
                f"walk_trials must be >= 0 (0 = library default), got "
                f"{walk_trials}"
            )
        self.edge_type = list(edge_type)
        self.walk_len = walk_len
        self.walk_p = walk_p
        self.walk_q = walk_q
        # biased walks reweight candidates by d_tx (reference
        # graph.cc:120-151); on device that membership test runs over
        # id-sorted slab rows. p=q=1 keeps the plain-draw fast path, the
        # same degeneration the reference takes (graph.cc:196-199).
        self.adj_sorted = self.device_sampling and (
            walk_p != 1.0 or walk_q != 1.0
        )
        self.left_win_size = left_win_size
        self.right_win_size = right_win_size
        self.batch_size_ratio = ops.walk.pair_count(
            walk_len + 1, left_win_size, right_win_size
        )
        self.module = _ShallowUnsupModule(
            dim=dim,
            feature_dim=self.feature_dim if self.feature_idx >= 0 else 0,
            max_id=max_id if self.use_id else -1,
            embedding_dim=embedding_dim,
            sparse_feature_max_ids=tuple(self.sparse_feature_max_ids),
            combiner=combiner,
            xent_loss=xent_loss,
            num_negs=self.num_negs,
            adj_key=self.adj_key(self.edge_type, sorted=self.adj_sorted),
            walk_len=walk_len,
            left_win=left_win_size,
            right_win=right_win_size,
            has_features=self.device_features and self.feature_idx >= 0,
            has_sparse=self.device_features
            and bool(self.sparse_feature_idx),
            walk_p=walk_p,
            walk_q=walk_q,
            walk_trials=walk_trials,
        )

    def sample(self, graph, inputs) -> dict:
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.device_sample_batch(roots)
        paths = graph.random_walk(
            roots,
            self.edge_type,
            self.walk_len,
            p=self.walk_p,
            q=self.walk_q,
            default_node=self.max_id + 1,
        )
        pairs = ops.gen_pair(paths, self.left_win_size, self.right_win_size)
        flat = pairs.reshape(-1, 2)  # [B*num_pairs, 2]
        src, pos = flat[:, 0], flat[:, 1]
        negs = graph.sample_node(len(src) * self.num_negs, self.node_type)
        return self._pack(graph, src, pos, negs)
