"""Segment-op aggregators over padded COO adjacency (full-neighbor GCN path).

Reference equivalent: tf_euler/python/sparse_aggregators.py:20-146, which
uses tf.SparseTensor matmul/softmax. Here the adjacency is the padded COO
from ops.get_multi_hop_neighbor (adj_src/adj_dst index the current/next hop
node arrays). Padding edges carry edge_mask 0 and contribute nothing.

The degree and the sum of an edge list take one of two forms, chosen by
what ``src`` is seen to be while tracing (``_row_width``). A REGULAR
list, whose ``src`` is a constant equal to ``repeat(arange(n), W)`` (the
device expansions': graph/device.py ``multi_hop_neighbor``, models/gcn.py
``ScalableGCN._expand_batch``), is ``[n, W]`` rows as it lies, and is
reduced along its row axis: ``mask.reshape(n, W).sum(1)``,
``messages.reshape(n, W, F).sum(1)``. Every other list (a host-expanded
batch's compacted COO, which arrives as a jit argument; any irregular
``src``) keeps jax.ops.segment_sum with static segment counts, the
XLA-native form of sparse x dense. The form taken is said while tracing,
once a shape and outcome ("aggregate path: ...", OBSERVABILITY.md).

The work over the edge list (the gather by ``dst``, the mask, the degree,
the segment sum, the division) carries the ``segment_agg`` named scope
(trace.STEP_SCOPES); the matmuls stay under ``dense`` (nn/layers.py).

An aggregator's neighbour input is the hop's rows ``[m, F]``, which it
reads through ``dst``, or ``SlotRows``: rows that already lie one a slot
of the edge list. ``GCNAggregator`` and ``MeanAggregator`` take either
(``reads_slot_rows``). Layer 0 of the device-expanded full-neighbourhood
step hands them ``SlotRows`` where a hop's rows are rows of the
device-resident feature table (models/gcn.py ``_forward``): the slots'
node ids ``nodes[dst]`` are composed here (``slot_ids``, under
``segment_agg``), the rows are gathered from the stored table by those
ids in one pass under ``gather_features``, and the hop's set rows are
not gathered for the messages' sake at all. Attention projects the hop's
rows before its gather by ``dst`` and keeps the hop's rows.
"""

from __future__ import annotations

import functools
import logging
from typing import Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu.nn.layers import Dense

log = logging.getLogger("euler_tpu")


@functools.lru_cache(maxsize=64)
def _log_aggregate_route(slots: int, route: str) -> None:
    """One line per distinct shape and outcome, said while tracing (as
    models/gcn.py says its message path and graph/device.py its draw and
    expand paths): the form an edge list's degree and sum take."""
    log.info("aggregate path: %d slots -> %s", slots, route)


def _row_width(adj_src, num_nodes):
    """W where ``adj_src`` is a CONCRETE array (a constant of the trace,
    not a tracer) of ``num_nodes * W`` entries equal to
    ``repeat(arange(num_nodes), W)``: slot ``i`` then belongs to row
    ``i // W`` and the list is ``[num_nodes, W]`` as it lies. Else None,
    and the list keeps the segment sum."""
    slots = adj_src.shape[0]
    width = None
    if isinstance(adj_src, jax.core.Tracer):
        why = "traced src"
    else:
        why = "src not repeat(arange)"
        if num_nodes and slots % num_nodes == 0:
            rows = np.asarray(adj_src).reshape(
                num_nodes, slots // num_nodes)
            if (rows == np.arange(num_nodes)[:, None]).all():
                width = rows.shape[1]
    _log_aggregate_route(
        slots,
        f"segment sum ({why})" if width is None
        else f"row sum over {width}",
    )
    return width


def _degree(adj_src, edge_mask, num_nodes):
    width = _row_width(adj_src, num_nodes)
    if width is None:
        return jax.ops.segment_sum(
            edge_mask, adj_src, num_segments=num_nodes)
    return edge_mask.reshape(num_nodes, width).sum(1)


def _gather_sum(values, adj_src, num_nodes):
    width = _row_width(adj_src, num_nodes)
    if width is None:
        return jax.ops.segment_sum(
            values, adj_src, num_segments=num_nodes)
    return values.reshape((num_nodes, width) + values.shape[1:]).sum(1)


class SlotRows(NamedTuple):
    """Neighbour rows handed to an aggregator one a SLOT of the edge list
    ([slots, F], in the adjacency's own order) in place of one a node of
    the hop's set: the aggregator reads them as they lie, with no gather
    by ``dst``. Where a hop's rows are nothing but rows of a stored table
    the caller gathers them by ``slot_ids`` in one pass."""

    rows: jax.Array


def slot_ids(nodes, adj):
    """The id of every slot's neighbour, ``nodes[dst]``: two chained row
    gathers (a table by ``nodes``, the result by ``dst``) are one gather
    by these ids. A masked slot names whatever node its clipped ``dst``
    points at; its row is multiplied by the mask's nought as before."""
    with jax.named_scope("segment_agg"):
        return nodes[adj["dst"]]


def _messages(neigh_emb, adj):
    """[slots, F] masked messages of an edge list (inside
    ``segment_agg``): the hop's rows read through ``dst``, or rows that
    already lie one a slot (``SlotRows``)."""
    if isinstance(neigh_emb, SlotRows):
        rows = neigh_emb.rows
    else:
        rows = neigh_emb[adj["dst"]]
    return rows * adj["mask"][:, None]


class GCNAggregator(nn.Module):
    """(self + sum(neigh)/deg) @ W, or renorm (self + sum)/(1+deg) @ W
    (reference sparse_aggregators.py:37-55 uses binary adjacency)."""

    dim: int
    activation: Optional[Callable] = nn.relu
    renorm: bool = False
    reads_slot_rows = True

    @nn.compact
    def __call__(self, inputs):
        self_emb, neigh_emb, adj = inputs
        src, edge_mask = adj["src"], adj["mask"]
        n = self_emb.shape[0]
        with jax.named_scope("segment_agg"):
            deg = _degree(src, edge_mask, n)[:, None]
            agg = _gather_sum(_messages(neigh_emb, adj), src, n)
            if self.renorm:
                agg = (self_emb + agg) / (1.0 + deg)
            else:
                agg = self_emb + agg / jnp.maximum(deg, 1e-7)
        return Dense(self.dim, self.activation, use_bias=False)(agg)


class MeanAggregator(nn.Module):
    dim: int
    activation: Optional[Callable] = nn.relu
    concat: bool = False
    reads_slot_rows = True

    @nn.compact
    def __call__(self, inputs):
        self_emb, neigh_emb, adj = inputs
        src, edge_mask = adj["src"], adj["mask"]
        n = self_emb.shape[0]
        dim = self.dim // 2 if self.concat else self.dim
        with jax.named_scope("segment_agg"):
            deg = _degree(src, edge_mask, n)[:, None]
            agg = _gather_sum(_messages(neigh_emb, adj), src, n)
            agg = agg / jnp.maximum(deg, 1e-7)
        from_self = Dense(dim, self.activation, use_bias=False)(self_emb)
        from_neigh = Dense(dim, self.activation, use_bias=False)(agg)
        if self.concat:
            return jnp.concatenate([from_self, from_neigh], axis=1)
        return from_self + from_neigh


@jax.named_scope("segment_agg")
def segment_softmax(logits, segments, num_segments, mask):
    """Numerically-stable softmax of edge logits within each src segment.
    Masked edges get zero probability."""
    neg = jnp.finfo(logits.dtype).min
    masked = jnp.where(mask > 0, logits, neg)
    seg_max = jax.ops.segment_max(masked, segments, num_segments=num_segments)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    e = jnp.exp(masked - seg_max[segments]) * mask
    denom = jax.ops.segment_sum(e, segments, num_segments=num_segments)
    return e / jnp.maximum(denom[segments], 1e-16)


class SingleAttentionAggregator(nn.Module):
    """GAT-style single head over COO adjacency
    (reference sparse_aggregators.py:84-116). With renorm, a virtual
    self-edge is added to each row's softmax."""

    dim: int
    activation: Optional[Callable] = nn.relu
    renorm: bool = False

    @nn.compact
    def __call__(self, inputs):
        self_emb, neigh_emb, adj = inputs
        src, dst, edge_mask = adj["src"], adj["dst"], adj["mask"]
        n = self_emb.shape[0]
        dense = Dense(self.dim, use_bias=False)
        self_gate = Dense(1, use_bias=False)
        all_gate = Dense(1, use_bias=False)
        from_self = dense(self_emb)          # [n, dim]
        from_all = dense(neigh_emb)          # [m, dim]
        self_w = self_gate(from_self)[:, 0]  # [n]
        all_w = all_gate(from_all)[:, 0]     # [m]

        logits = nn.leaky_relu(self_w[src] + all_w[dst])
        if self.renorm:
            # Append one self-edge per node to the softmax support; its
            # "context" logit is the all-gate applied to the self projection
            # (the reference concatenates self rows into the `all` set,
            # sparse_aggregators.py:96-101).
            self_logits = nn.leaky_relu(self_w + all_gate(from_self)[:, 0])
            ext_logits = jnp.concatenate([logits, self_logits])
            ext_src = jnp.concatenate([src, jnp.arange(n, dtype=src.dtype)])
            ext_mask = jnp.concatenate([edge_mask, jnp.ones(n)])
            coef = segment_softmax(ext_logits, ext_src, n, ext_mask)
            msgs = jnp.concatenate([from_all[dst], from_self]) * coef[:, None]
            out = jax.ops.segment_sum(msgs, ext_src, num_segments=n)
        else:
            coef = segment_softmax(logits, src, n, edge_mask)
            msgs = from_all[dst] * coef[:, None]
            out = jax.ops.segment_sum(msgs, src, num_segments=n)
            out = from_self + out
        if self.activation is not None:
            out = self.activation(out)
        return out


class AttentionAggregator(nn.Module):
    """Multi-head concat (reference sparse_aggregators.py:119-133)."""

    dim: int
    num_heads: int = 4
    activation: Optional[Callable] = nn.relu
    renorm: bool = False
    # a head projects the hop's rows BEFORE its gather by ``dst``
    reads_slot_rows = False

    @nn.compact
    def __call__(self, inputs):
        head_dim = self.dim // self.num_heads
        outs = [
            SingleAttentionAggregator(
                head_dim, self.activation, self.renorm
            )(inputs)
            for _ in range(self.num_heads)
        ]
        return jnp.concatenate(outs, axis=1)


AGGREGATORS = {
    "gcn": GCNAggregator,
    "mean": MeanAggregator,
    "attention": AttentionAggregator,
}


def get(name: str):
    return AGGREGATORS.get(name)
