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

The attention aggregator's edge softmax takes the same two forms by the
same test: the max and the sums of a regular list go along its rows
(``_gather_max`` beside ``_gather_sum``; a node's own logit term is a
broadcast along the row, ``_spread``), any other list keeps
jax.ops.segment_max and segment_sum ("attention path: ...").

The work over the edge list (the gather by ``dst``, the mask, the degree,
the segment sum, the division) carries the ``segment_agg`` named scope
(trace.STEP_SCOPES); the attention's (logits, max, exp, sum, the
weighting of the messages and the normalisation) the ``edge_softmax``
scope; the matmuls, a head's gates among them, stay under ``dense``
(nn/layers.py).

An aggregator's neighbour input is the hop's rows ``[m, F]``, which it
reads through ``dst``, or ``SlotRows``: rows that already lie one a slot
of the edge list. Every aggregator takes either. Layer 0 of the
device-expanded full-neighbourhood step hands them ``SlotRows`` where a
hop's rows are rows of the device-resident feature table (models/gcn.py
``_forward``): the rows are gathered from the stored table in one pass
under ``gather_features``, by the slots' own neighbour ids, which the
expansion hands out beside its COO (graph/device.py
``multi_hop_neighbor``, ``ids``: ``nodes[dst]`` on every unmasked slot,
so nothing composes them here), and the hop's set rows are not gathered
for the messages' sake at all. Attention projects a slot's row where it
lies (the same dot product as projecting the hop's set and gathering
after).
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
    the caller gathers them by the slots' own ids in one pass; a masked
    slot's row is multiplied by the mask's nought."""

    rows: jax.Array


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


def _gather_max(values, adj_src, num_nodes):
    width = _row_width(adj_src, num_nodes)
    if width is None:
        return jax.ops.segment_max(
            values, adj_src, num_segments=num_nodes)
    return values.reshape((num_nodes, width) + values.shape[1:]).max(1)


def _spread(per_node, adj_src, num_nodes):
    """``per_node[adj_src]``: on a regular list a broadcast along the
    row, not a gather."""
    width = _row_width(adj_src, num_nodes)
    if width is None:
        return per_node[adj_src]
    return jnp.repeat(per_node, width, axis=0)


@functools.lru_cache(maxsize=64)
def _log_attention_route(slots: int, heads: int, route: str) -> None:
    """One line per distinct shape and outcome, said while tracing: the
    form the edge softmax of an edge list takes (the same choice as the
    sum's, said beside its ``aggregate path:`` line)."""
    log.info("attention path: %d slots x %d heads -> %s", slots, heads,
             route)


def _edge_weights(logits, segments, num_segments, mask, self_logits=None):
    """(e, denom): the softmax of the edge logits within each src
    segment, unnormalised. ``e`` [slots, ...] is exp(logit - the
    segment's max), nought on a masked slot; ``denom`` [n, ...] its sum
    over the segment. ``self_logits`` [n, ...] adds one virtual edge a
    node to the softmax's support: returns its ``e`` as a third. The max
    and the sum go along the rows of a regular list, by segment over any
    other (``_gather_max``, ``_gather_sum``). The max is a constant of
    the softmax (a shift of all of a node's logits changes nothing), so
    no gradient is sent through it."""
    heads = int(np.prod(logits.shape[1:], dtype=np.int64))
    width = _row_width(segments, num_segments)
    _log_attention_route(
        logits.shape[0], heads,
        "segment softmax" if width is None else f"row softmax over {width}")
    live = mask.reshape(mask.shape + (1,) * (logits.ndim - 1)) > 0
    masked = jnp.where(live, logits, jnp.finfo(logits.dtype).min)
    top = _gather_max(masked, segments, num_segments)
    if self_logits is not None:
        top = jnp.maximum(top, self_logits)
    # a segment no slot names has a max of -inf
    top = jax.lax.stop_gradient(jnp.where(jnp.isfinite(top), top, 0.0))
    e = jnp.where(
        live, jnp.exp(masked - _spread(top, segments, num_segments)), 0.0)
    denom = _gather_sum(e, segments, num_segments)
    if self_logits is None:
        return e, denom
    e_self = jnp.exp(self_logits - top)
    return e, denom + e_self, e_self


@jax.named_scope("edge_softmax")
def segment_softmax(logits, segments, num_segments, mask):
    """Numerically-stable softmax of edge logits within each src segment.
    Masked edges get zero probability."""
    e, denom = _edge_weights(logits, segments, num_segments, mask)
    return e / jnp.maximum(_spread(denom, segments, num_segments), 1e-16)


class _Kernel(nn.Module):
    """The kernel of a bias-free ``Dense(dim)`` as a value, at the tree
    path that layer keeps it at (``<name>/Dense_0/kernel``) and with its
    initialiser: the attention heads' parameters keep the tree they had
    as four modules of three Dense layers each, while their kernels are
    laid side by side into one projection."""

    dim: int
    leaf: bool = False

    @nn.compact
    def __call__(self, fan_in):
        if not self.leaf:
            return _Kernel(self.dim, leaf=True, name="Dense_0")(fan_in)
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (fan_in, self.dim))


# a dot whose operands are not rounded to bfloat16 on the way in
_exact_dot = functools.partial(jnp.dot, precision="highest")


def _head_blocks(per_head):
    """[K, D] values a head -> the block-diagonal [K * D, K] matrix that
    holds head ``k``'s values in rows ``k * D .. (k + 1) * D`` of column
    ``k``: ``x [., K * D] @ blocks`` is every head's own dot product at
    once, ``y [., K] @ blocks.T`` every head's value along its lanes."""
    heads, dim = per_head.shape
    eye = jnp.eye(heads, dtype=per_head.dtype)
    return (per_head[:, :, None] * eye[:, None, :]).reshape(
        heads * dim, heads)


def _attend(kernels, inputs, activation, renorm):
    """GAT-style heads over one edge list, side by side [n, K * D].
    ``kernels``: a head's (W [F, D], self gate u [D], neighbour gate v
    [D]). One projection [F, K * D] and one pass over the list for all
    heads. The messages stay [slots, K * D] rows throughout (K * D lanes:
    a [slots, K, D] view costs a copy of the whole product on the TPU):
    the gates are matmuls with block-diagonal [K * D, K] kernels, the
    logits and the softmax [slots, K], and a head's weight reaches its D
    lanes by the transposed block of ones. Max and sums go along the
    rows of a regular list, by segment over any other. With renorm, a
    virtual self-edge is added to each row's softmax, its neighbour-side
    logit the neighbour gate applied to the self projection (the
    reference concatenates self rows into the ``all`` set,
    sparse_aggregators.py:96-101), in place of the self term."""
    self_emb, neigh_emb, adj = inputs
    src, mask = adj["src"], adj["mask"]
    n = self_emb.shape[0]
    w = jnp.concatenate([k[0] for k in kernels], axis=1)   # [F, K * D]
    gate_self = _head_blocks(jnp.stack([k[1] for k in kernels]))
    gate_all = _head_blocks(jnp.stack([k[2] for k in kernels]))
    slot_rows = isinstance(neigh_emb, SlotRows)
    with jax.named_scope("dense"):
        from_self = jnp.dot(self_emb, w)                   # [n, K * D]
        # a slot's message is W applied to that slot's row: the same dot
        # product as projecting the hop's set and gathering after
        from_all = jnp.dot(neigh_emb.rows if slot_rows else neigh_emb, w)
        # the gates feed a softmax: a 64-wide dot a head, in float32
        # whatever the platform makes of a default-precision matmul
        self_w = _exact_dot(from_self, gate_self)          # [n, K]
        all_w = _exact_dot(from_all, gate_all)             # [slots or m, K]
        if renorm:
            own_w = _exact_dot(from_self, gate_all)
    if not slot_rows:
        with jax.named_scope("segment_agg"):
            from_all, all_w = from_all[adj["dst"]], all_w[adj["dst"]]
    with jax.named_scope("edge_softmax"):
        ones = _head_blocks(
            jnp.ones((len(kernels),) + kernels[0][1].shape, w.dtype)).T

        def lanes(per_head):
            """A head's [., K] value on each of its D lanes, to the bit."""
            return _exact_dot(per_head, ones)

        logits = nn.leaky_relu(_spread(self_w, src, n) + all_w)
        if renorm:
            e, denom, e_self = _edge_weights(
                logits, src, n, mask, nn.leaky_relu(self_w + own_w))
            out = _gather_sum(from_all * lanes(e), src, n) \
                + from_self * lanes(e_self)
        else:
            e, denom = _edge_weights(logits, src, n, mask)
            out = _gather_sum(from_all * lanes(e), src, n)
        out = out / lanes(jnp.maximum(denom, 1e-16))
        if not renorm:
            out = from_self + out
        if activation is not None:
            out = activation(out)
    return out


class SingleAttentionAggregator(nn.Module):
    """GAT-style single head over COO adjacency
    (reference sparse_aggregators.py:84-116): ``_attend`` with one head.
    Its parameters are the three bias-free Dense kernels of a head (the
    projection, the self gate, the neighbour gate)."""

    dim: int
    activation: Optional[Callable] = nn.relu
    renorm: bool = False

    @nn.compact
    def kernels(self, fan_in):
        return (
            _Kernel(self.dim, name="Dense_0")(fan_in),
            _Kernel(1, name="Dense_1")(self.dim)[:, 0],
            _Kernel(1, name="Dense_2")(self.dim)[:, 0],
        )

    def __call__(self, inputs):
        return _attend([self.kernels(inputs[0].shape[-1])], inputs,
                       self.activation, self.renorm)


class AttentionAggregator(nn.Module):
    """Multi-head concat (reference sparse_aggregators.py:119-133): the
    heads' kernels (each head's own sub-module's leaves) side by side in
    one projection and one pass over the edge list (``_attend``)."""

    dim: int
    num_heads: int = 4
    activation: Optional[Callable] = nn.relu
    renorm: bool = False

    @nn.compact
    def __call__(self, inputs):
        head_dim = self.dim // self.num_heads
        kernels = [
            SingleAttentionAggregator(
                head_dim, self.activation, self.renorm
            ).kernels(inputs[0].shape[-1])
            for _ in range(self.num_heads)
        ]
        return _attend(kernels, inputs, self.activation, self.renorm)


AGGREGATORS = {
    "gcn": GCNAggregator,
    "mean": MeanAggregator,
    "attention": AttentionAggregator,
}


def get(name: str):
    return AGGREGATORS.get(name)
