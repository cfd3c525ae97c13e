"""Node encoders (device side, flax.linen).

Reference equivalent: tf_euler/python/encoders.py. The key architectural
change vs the reference: encoders are *pure device modules* — all graph
queries (fanout sampling, multi-hop expansion, feature gather) happen on the
host in the model's `sample()` phase, and the encoder consumes the resulting
fixed-shape arrays. That split is what lets the whole train step jit into a
single XLA program and lets sampling overlap device compute.

Host-side input conventions:
  feats dict (per node set): optional keys
    'ids'    [n] int32/int64  — for the id-embedding path
    'dense'  [n, sum(feature_dim)] float32
    'sparse' list of (ids [n, L], mask [n, L]) per sparse slot
  SageEncoder: list of per-hop feats dicts, hop h has n*prod(fanouts[:h]) rows.
  GCNEncoder: per-hop feats + adjacency dicts {src, dst, w, mask} — use
  MultiHop.adj from ops.get_multi_hop_neighbor.
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from euler_tpu.nn import aggregators as dense_aggs
from euler_tpu.nn import sparse_aggregators as sparse_aggs
from euler_tpu.nn.layers import Dense, Embedding, SparseEmbedding


class ShallowEncoder(nn.Module):
    """Id embedding + dense features + sparse-feature embeddings, combined
    by 'add' or 'concat' (reference encoders.py:30-162)."""

    dim: Optional[int] = None
    feature_dim: int = 0  # total host-gathered dense feature width
    max_id: int = -1  # >=0 enables the id-embedding path
    embedding_dim: int = 16
    sparse_feature_max_ids: Sequence[int] = ()
    combiner: str = "concat"

    @property
    def output_dim(self) -> int:
        if self.dim is not None:
            return self.dim
        out = self.feature_dim
        if self.max_id >= 0:
            out += self.embedding_dim
        out += self.embedding_dim * len(self.sparse_feature_max_ids)
        return out

    @nn.compact
    def __call__(self, feats: dict):
        embeddings = []
        emb_dim = self.dim if self.combiner == "add" else self.embedding_dim
        if self.max_id >= 0:
            embeddings.append(
                Embedding(self.max_id + 2, emb_dim)(feats["ids"])
            )
        if self.feature_dim:
            dense = feats["dense"]
            if self.combiner == "add":
                dense = Dense(self.dim, use_bias=False)(dense)
            embeddings.append(dense)
        for k, max_id in enumerate(self.sparse_feature_max_ids):
            ids, mask = feats["sparse"][k]
            embeddings.append(SparseEmbedding(max_id + 2, emb_dim)(ids, mask))
        if self.combiner == "add":
            return sum(embeddings)
        out = jnp.concatenate(embeddings, axis=-1)
        if self.dim is not None:
            out = Dense(self.dim, use_bias=False)(out)
        return out


class SageEncoder(nn.Module):
    """GraphSAGE aggregation over host-sampled fanouts
    (reference encoders.py:327-401). `hidden` is the per-hop encoded
    feature list; layer l aggregates hop h with hop h+1."""

    fanouts: Sequence[int]
    dim: int
    aggregator: str = "mean"
    concat: bool = False

    @nn.compact
    def __call__(self, hidden: list):
        num_layers = len(self.fanouts)
        assert len(hidden) == num_layers + 1
        agg_cls = dense_aggs.get(self.aggregator)
        aggs = [
            agg_cls(
                self.dim,
                activation=nn.relu if l < num_layers - 1 else None,
                concat=self.concat,
            )
            if agg_cls is not dense_aggs.GCNAggregator
            else agg_cls(
                self.dim,
                activation=nn.relu if l < num_layers - 1 else None,
            )
            for l in range(num_layers)
        ]
        for layer in range(num_layers):
            next_hidden = []
            for hop in range(num_layers - layer):
                d = hidden[hop].shape[-1]
                with jax.named_scope("aggregate"):
                    neigh = hidden[hop + 1].reshape(
                        -1, self.fanouts[hop], d)
                next_hidden.append(aggs[layer]((hidden[hop], neigh)))
            hidden = next_hidden
        return hidden[0]


class SparseSageEncoder(nn.Module):
    """Sparse-feature GraphSAGE (reference encoders.py:522-560): per-slot
    SparseEmbedding lookups (embedding_dim each, concatenated — the
    reference hardcodes 16) feed SageEncoder aggregation.

    ``hops`` is the per-hop list of per-slot (ids, mask) padded sparse
    features (hop h sized batch * prod(fanouts[:h]); the 'sparse' entry
    of the feats-dict convention above). Pass already-constructed
    SparseEmbedding modules via ``shared_embeddings`` to tie the tables
    across towers (the reference's shared_embeddings argument) — LasGNN
    shares one set across all its metapath towers this way."""

    fanouts: Sequence[int]
    dim: int
    feature_dims: Sequence[int] = ()  # per-slot max sparse id
    aggregator: str = "mean"
    concat: bool = False
    embedding_dim: int = 16
    shared_embeddings: Optional[Sequence[SparseEmbedding]] = None

    def setup(self):
        if self.shared_embeddings is not None:
            self.sparse_embeddings = list(self.shared_embeddings)
        else:
            # feature_dim + 1 sparse slots plus the padding id
            self.sparse_embeddings = [
                SparseEmbedding(d + 2, self.embedding_dim)
                for d in self.feature_dims
            ]
        self.sage = SageEncoder(
            tuple(self.fanouts), self.dim, self.aggregator, self.concat
        )

    def __call__(self, hops):
        hidden = [
            jnp.concatenate(
                [
                    emb(ids, mask)
                    for emb, (ids, mask) in zip(
                        self.sparse_embeddings, hop
                    )
                ],
                axis=-1,
            )
            for hop in hops
        ]
        return self.sage(hidden)


class GCNEncoder(nn.Module):
    """Full-neighbor multi-hop GCN over padded COO adjacency
    (reference encoders.py:165-215).

    ``first_neigh``, where given, is layer 0's neighbour input a hop in
    place of ``hidden[hop + 1]``: ``sparse_aggregators.SlotRows``, the
    messages' rows gathered one a slot from the stored feature table
    under ``gather_features`` (models/gcn.py ``_forward``). The last
    entry of ``hidden``, which only layer 0 would read as a neighbour
    input, may then be None. Later layers read the layer before through
    ``dst`` under ``segment_agg``."""

    num_layers: int
    dim: int
    aggregator: str = "gcn"
    use_residual: bool = False

    @nn.compact
    def __call__(self, hidden: list, adjs: list, first_neigh=None):
        assert len(hidden) == self.num_layers + 1
        assert len(adjs) == self.num_layers
        agg_cls = sparse_aggs.get(self.aggregator)
        aggs = [
            agg_cls(
                self.dim,
                activation=nn.relu if l < self.num_layers - 1 else None,
            )
            for l in range(self.num_layers)
        ]
        for layer in range(self.num_layers):
            next_hidden = []
            for hop in range(self.num_layers - layer):
                neigh = hidden[hop + 1]
                if layer == 0 and first_neigh is not None:
                    neigh = first_neigh[hop]
                h = aggs[layer]((hidden[hop], neigh, adjs[hop]))
                if self.use_residual:
                    h = hidden[hop] + h
                next_hidden.append(h)
            hidden = next_hidden
        return hidden[0]


class _AttHead(nn.Module):
    """One all-pairs attention head over [B, n, F]
    (reference encoders.py:587-598 att_head)."""

    out_size: int

    @nn.compact
    def __call__(self, seq, activation=nn.elu):
        seq_fts = nn.Dense(self.out_size, use_bias=False)(seq)  # [B, n, out]
        f1 = nn.Dense(1)(seq_fts)  # [B, n, 1]
        f2 = nn.Dense(1)(seq_fts)  # [B, n, 1]
        logits = f1 + jnp.swapaxes(f2, 1, 2)  # [B, n, n]
        coefs = nn.softmax(nn.leaky_relu(logits), axis=-1)
        vals = jnp.einsum("bij,bjd->bid", coefs, seq_fts)
        bias = self.param("bias", nn.initializers.zeros, (self.out_size,))
        out = vals + bias
        if activation is not None:
            out = activation(out)
        return out


class AttEncoder(nn.Module):
    """GAT-style attention over a sampled neighborhood
    (reference encoders.py:563-632): input is the [B, nb+1, F] feature
    sequence (self node at position 0 + nb sampled neighbors); two rounds of
    attention heads; output is position 0's features. All-pairs softmax
    attention on a tiny nb+1 axis — dense matmuls, MXU-friendly."""

    head_num: int = 1
    hidden_dim: int = 256
    out_dim: int = 1

    @nn.compact
    def __call__(self, seq):
        hidden = [
            _AttHead(self.hidden_dim)(seq) for _ in range(self.head_num)
        ]
        h1 = jnp.concatenate(hidden, axis=-1)
        outs = [_AttHead(self.out_dim)(h1) for _ in range(self.head_num)]
        out = sum(outs) / self.head_num  # [B, n, out_dim]
        return out[:, 0, :]


class ScalableSageEncoder(nn.Module):
    """GraphSAGE with historical-embedding stores: each layer >0 reads its
    neighbor embeddings from a store instead of recursive sampling, capping
    the receptive field at one hop per step
    (reference encoders.py:404-519). The store read/write and the
    two-optimizer store-gradient dance live in the model's train step; this
    module is the pure function: given per-layer neighbor embeddings
    (store_reads), produce the per-layer node embeddings."""

    fanout: int
    num_layers: int
    dim: int
    aggregator: str = "mean"
    concat: bool = False

    @nn.compact
    def __call__(self, node_feat, neigh_feat, store_reads: list):
        """node_feat [B, d0]; neigh_feat [B*fanout, d0]; store_reads: list of
        num_layers-1 arrays [B*fanout, dim] (stale neighbor embeddings).
        Returns (final [B, dim'], node_embeddings per layer)."""
        agg_cls = dense_aggs.get(self.aggregator)
        node_emb, neigh_emb = node_feat, neigh_feat
        node_embeddings = []
        for layer in range(self.num_layers):
            agg = agg_cls(
                self.dim,
                activation=nn.relu if layer < self.num_layers - 1 else None,
                **({} if agg_cls is dense_aggs.GCNAggregator
                   else {"concat": self.concat}),
            )
            d = node_emb.shape[-1]
            with jax.named_scope("aggregate"):
                neigh = neigh_emb.reshape(-1, self.fanout, d)
            node_emb = agg((node_emb, neigh))
            node_embeddings.append(node_emb)
            if layer < self.num_layers - 1:
                neigh_emb = store_reads[layer]
        return node_emb, node_embeddings
