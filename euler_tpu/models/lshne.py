"""LsHNE: multi-view heterogeneous-graph walk embedding.

Reference equivalent: tf_euler/python/models/lshne.py:27-213. Semantics kept:
per-view metapath walks -> skip-gram pairs -> per-node-type DNN towers ->
cosine softmax loss against typed negatives, plus a cross-view attention
embedding trained jointly.

TPU adaptations:
- The reference gathers valid pairs with tf.where (dynamic shape,
  lshne.py:95-108); here every view keeps its static pair count and a
  validity mask, and the loss/MRR are masked sums — fixed shapes end to end.
- The reference computes all src_type_num towers for every node and selects
  by one-hot matmul (lshne.py:125-138); here the tower parameters live in a
  single [T, in, out] tensor and each row gathers its type's slice — one
  batched einsum instead of T dense passes.
- Typed negatives come from the engine's native sample_node_with_src.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from euler_tpu import ops
from euler_tpu.models import base
from euler_tpu.nn.layers import SparseEmbedding

EPS = 1e-8


class TypedDense(nn.Module):
    """Per-node-type dense layer: weight[T, in, out], row i uses slice
    type[i] (the reference's per-type tower stacks, lshne.py:62-77)."""

    num_types: int
    features: int

    @nn.compact
    def __call__(self, x, type_idx):
        w = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (self.num_types, x.shape[-1], self.features),
        )
        b = self.param(
            "bias", nn.initializers.zeros, (self.num_types, self.features)
        )
        type_idx = jnp.clip(type_idx, 0, self.num_types - 1)
        return (
            jnp.einsum("bi,bio->bo", x, jnp.take(w, type_idx, axis=0))
            + jnp.take(b, type_idx, axis=0)
        )


def _cosine(a, b):
    # sqrt(x + eps) keeps the gradient finite for exactly-zero embeddings
    # (masked/missing nodes whose features are all padding).
    prod = jnp.sum(a * b, axis=-1, keepdims=True)
    na = jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + EPS)
    nb = jnp.sqrt(jnp.sum(b * b, axis=-1, keepdims=True) + EPS)
    return prod / (na * nb)


class _LsHNEModule(nn.Module):
    view_num: int
    dim: int
    num_negs: int
    src_type_num: int
    sparse_feature_dims: Sequence[int]
    feature_embedding_dim: int = 16
    hidden_dim: int = 256
    gamma: float = 5.0
    # device-sampling mode: per view, a tuple of metapaths, each a tuple
    # of per-step consts["adj"] keys
    view_adj_keys: Sequence = ()
    left_win: int = 1
    right_win: int = 1
    default_node: int = -1

    def setup(self):
        self.feature_embeddings = [
            SparseEmbedding(d, self.feature_embedding_dim, combiner="sum")
            for d in self.sparse_feature_dims
        ]
        self.src_hidden = [
            TypedDense(self.src_type_num, self.hidden_dim)
            for _ in range(self.view_num)
        ]
        self.src_out = [
            TypedDense(self.src_type_num, self.dim)
            for _ in range(self.view_num)
        ]
        self.tar_hidden = TypedDense(self.src_type_num, self.hidden_dim)
        self.tar_out = TypedDense(self.src_type_num, self.dim)
        self.att_vec = self.param(
            "att_vec",
            nn.initializers.truncated_normal(stddev=0.1),
            (self.view_num, self.dim),
        )

    def _features(self, node):
        embs = [
            emb(ids, mask)
            for emb, (ids, mask) in zip(
                self.feature_embeddings, node["sparse"]
            )
        ]
        return jnp.concatenate(embs, axis=-1)

    def encode_src(self, node, view: int):
        x = self._features(node)
        t = node["types"]
        h = self.src_hidden[view](x, t)
        return self.src_out[view](h, t)

    def encode_tar(self, node):
        x = self._features(node)
        t = node["types"]
        h = self.tar_hidden(x, t)
        return self.tar_out(h, t)

    def att_embedding(self, node, view_emb=None, view: int = -1):
        """Attention-combine the per-view source encodings
        (reference get_att_embedding, lshne.py:163-175)."""
        views = []
        for i in range(self.view_num):
            if i == view and view_emb is not None:
                views.append(view_emb)
            else:
                views.append(self.encode_src(node, i))
        stack = jnp.stack(views, axis=1)  # [B, V, dim]
        logit = jnp.sum(stack * self.att_vec, axis=-1)  # [B, V]
        w = nn.softmax(logit, axis=-1)
        return jnp.einsum("bv,bvd->bd", w, stack)

    def _decode(self, emb, emb_pos, emb_negs, mask):
        """Masked cosine softmax-CE + MRR (reference decoder,
        lshne.py:140-161). emb/emb_pos [B, d]; emb_negs [B, negs, d]."""
        pos_cos = _cosine(emb, emb_pos)  # [B, 1]
        neg_cos = _cosine(emb[:, None, :], emb_negs)[..., 0]  # [B, negs]
        # gamma tempers the [-1,1] cosine range before the softmax so the
        # positive can dominate (reference lshne.py decoder scaling).
        logits = self.gamma * jnp.concatenate([pos_cos, neg_cos], axis=-1)
        logp = nn.log_softmax(logits, axis=-1)
        per_pair = -logp[:, 0]
        loss = jnp.sum(per_pair * mask)
        rank = 1.0 + jnp.sum(neg_cos >= pos_cos, axis=-1)
        mrr = jnp.sum(mask / rank) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss, mrr

    def _dev_node(self, ids, consts):
        """Node-input dict gathered from the device-resident tables."""
        t = consts["tsampler"]["types"][ids]
        return {
            "sparse": [
                (tab["ids"][ids], tab["mask"][ids])
                for tab in consts["sparse"]
            ],
            "types": jnp.clip(t, 0, None),
        }

    def _device_views(self, batch, consts):
        """(views, root) built inside jit: metapath walks -> skip-gram
        pairs per view, typed negatives per source — the device analog of
        LsHNE.sample."""
        import jax

        from euler_tpu import ops as _ops
        from euler_tpu.graph import device as device_graph

        roots = batch["roots"]
        key = jax.random.PRNGKey(batch["seed"][0])
        views = []
        for v, patterns in enumerate(self.view_adj_keys):
            kv = jax.random.fold_in(key, v)
            srcs, poss = [], []
            for pi, step_keys in enumerate(patterns):
                adjs = [consts["adj"][k] for k in step_keys]
                paths = device_graph.random_walk(
                    adjs, roots, jax.random.fold_in(kv, pi),
                    len(step_keys),
                )
                ti, ci = _ops.walk.pair_indices(
                    len(step_keys) + 1, self.left_win, self.right_win
                )
                srcs.append(paths[:, ti])
                poss.append(paths[:, ci])
            src = jnp.concatenate(srcs, axis=1).reshape(-1)
            pos = jnp.concatenate(poss, axis=1).reshape(-1)
            mask = (
                (src != self.default_node) & (pos != self.default_node)
            ).astype(jnp.float32)
            safe_src = jnp.where(src == self.default_node, 0, src)
            negs = device_graph.sample_node_with_src(
                consts["tsampler"], safe_src,
                jax.random.fold_in(kv, 1 << 20), self.num_negs,
            ).reshape(-1)
            views.append(
                {
                    "src": self._dev_node(src, consts),
                    "pos": self._dev_node(pos, consts),
                    "negs": self._dev_node(negs, consts),
                    "mask": mask,
                }
            )
        return views, self._dev_node(roots, consts)

    def _views_and_root(self, batch, consts):
        if "views" in batch:
            return batch["views"], batch["root"]
        return self._device_views(batch, consts)

    def embed(self, batch, consts=None):
        if "root" in batch:
            return self.att_embedding(batch["root"])
        return self.att_embedding(self._dev_node(batch["roots"], consts))

    def __call__(self, batch, consts=None):
        views, root = self._views_and_root(batch, consts)
        total = 0.0
        mrrs = []
        for v, view in enumerate(views):
            emb = self.encode_src(view["src"], v)
            emb_pos = self.encode_tar(view["pos"])
            B = emb.shape[0]
            emb_negs = self.encode_tar(
                {
                    "sparse": view["negs"]["sparse"],
                    "types": view["negs"]["types"],
                }
            ).reshape(B, self.num_negs, self.dim)
            mask = view["mask"]
            loss_v, _ = self._decode(emb, emb_pos, emb_negs, mask)
            emb_att = self.att_embedding(view["src"], emb, v)
            loss_att, mrr = self._decode(emb_att, emb_pos, emb_negs, mask)
            total = total + loss_v + loss_att
            mrrs.append(mrr)
        embedding = self.att_embedding(root)
        return base.ModelOutput(
            embedding=embedding,
            loss=total,
            metric_name="mrr",
            metric=jnp.mean(jnp.stack(mrrs)),
        )


class LsHNE(base.Model):
    """Multi-view LsHNE. path_patterns: per view, a list of metapaths; each
    metapath is a per-step list of edge-type lists (heterogeneous walks)."""

    metric_name = "mrr"

    def __init__(
        self,
        node_type: int,
        path_patterns: Sequence[Sequence[Sequence[Sequence[int]]]],
        max_id: int,
        dim: int,
        sparse_feature_dims: Sequence[int],
        feature_ids: Sequence[int],
        feature_embedding_dim: int = 16,
        sparse_max_len: int = 16,
        walk_len: int = 3,
        left_win_size: int = 1,
        right_win_size: int = 1,
        num_negs: int = 5,
        gamma: float = 5.0,
        src_type_num: int = 20,
        device_sampling: bool = False,
    ):
        super().__init__()
        if len(path_patterns) < 1:
            raise ValueError("need at least one view")
        self.node_type = node_type
        self.path_patterns = path_patterns
        self.max_id = max_id
        self.init_device_sampling(device_sampling, require_features=False)
        self.src_type_num = src_type_num
        self.walk_len = walk_len
        self.left_win_size = left_win_size
        self.right_win_size = right_win_size
        self.num_negs = num_negs
        self.feature_ids = list(feature_ids)
        self.sparse_max_len = sparse_max_len
        self.gamma = gamma
        # per view, per metapath: one adj key per STEP — the host walk's
        # metapath semantics (walk length = len(pattern), each step
        # restricted to its own edge-type set)
        self._view_adj_keys = tuple(
            tuple(
                tuple(self.adj_key(step) for step in pattern)
                for pattern in patterns
            )
            for patterns in path_patterns
        )
        self.module = _LsHNEModule(
            view_num=len(path_patterns),
            dim=dim,
            num_negs=num_negs,
            src_type_num=src_type_num,
            sparse_feature_dims=tuple(sparse_feature_dims),
            feature_embedding_dim=feature_embedding_dim,
            gamma=gamma,
            view_adj_keys=self._view_adj_keys,
            left_win=left_win_size,
            right_win=right_win_size,
            default_node=max_id + 1,
        )

    def build_consts(self, graph) -> dict:
        consts = super().build_consts(graph)
        if not self.device_sampling:
            return consts
        from euler_tpu.graph import device as device_graph

        step_sets = [
            step
            for patterns in self.path_patterns
            for pattern in patterns
            for step in pattern
        ]
        self.add_sampling_consts(consts, graph, step_sets)
        consts["tsampler"] = device_graph.build_typed_node_sampler(
            graph, self.src_type_num, self.max_id
        )
        consts["sparse"] = base.upload_sparse_tables(
            graph, self.max_id, self.feature_ids, self.sparse_max_len,
            [0] * len(self.feature_ids),
        )
        return consts

    def _node_inputs(self, graph, ids: np.ndarray) -> dict:
        ids = ids.reshape(-1)
        safe = np.where(ids < 0, 0, ids)
        types = graph.node_types(safe)
        return {
            "sparse": ops.get_sparse_feature(
                graph, safe, self.feature_ids, self.sparse_max_len,
                default_values=[0] * len(self.feature_ids),
            ),
            "types": np.clip(types, 0, None).astype(np.int32),
        }

    def sample(self, graph, inputs) -> dict:
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.device_sample_batch(roots)
        views = []
        for patterns in self.path_patterns:
            pair_list = []
            for pattern in patterns:
                paths = graph.random_walk(
                    roots, list(pattern), p=1.0, q=1.0, default_node=-1
                )
                pair_list.append(
                    ops.gen_pair(
                        paths, self.left_win_size, self.right_win_size
                    )
                )
            pairs = np.concatenate(pair_list, axis=1)  # [B, P, 2]
            flat = pairs.reshape(-1, 2)
            src, pos = flat[:, 0], flat[:, 1]
            mask = ((src != -1) & (pos != -1)).astype(np.float32)
            negs = graph.sample_node_with_src(
                np.where(src < 0, 0, src), self.num_negs
            )
            views.append(
                {
                    "src": self._node_inputs(graph, src),
                    "pos": self._node_inputs(graph, pos),
                    "negs": self._node_inputs(graph, negs),
                    "mask": mask,
                }
            )
        return {"views": views, "root": self._node_inputs(graph, roots)}

    def sample_embed(self, graph, inputs) -> dict:
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        return {"root": self._node_inputs(graph, roots)}
