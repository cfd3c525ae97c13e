"""Operations and bytes one full-neighbourhood graph-attention training
step needs, from the cell's shapes and the graph function's degree law:
the cost function of ``gcn_attention_ppi`` (named in its file under
``"costs"``; see ``benchmark/costs.py`` for the keys the harness and the
readers ask for).

What the algorithm needs, whatever implements it, per step and per chip,
for ``b`` roots: the whole neighbourhood of every root and of every node
of the first hop, each hop's nodes made unique (the expansion of
``gcn_costs.py``: ``expected_expansion`` is that file's, loaded by path);
two layers of ``K`` attention heads ``D = dim / K`` wide, a linear
classifier, Adam over the parameters:

* a head projects every UNIQUE node's row once a layer (one ``W_k``
  serves a node as itself and as a neighbour);
* two gates a node and head (``D`` multiply-adds each);
* per TRUE edge and head one logit (an add and a leaky_relu), one exp,
  ``D`` multiply-adds of the weighted sum.

Nothing here knows of padding, caps, a sort or a mask: a step of the
program that works on 1.75M padded slots is held against the 0.41M true
edges those slots carry. ``edges`` is the mean cell's constant, the
expected true edges of both hops (414,371 at the cell's sizes).

Bytes: ``gather_bytes`` as in ``gcn_costs.py`` (every unique node's
feature row once, the roots' labels). The attention's own traffic is
under ``attention_bytes``, not ``message_bytes``: per true edge and head
the ``D``-wide projected message read once and two scalars (the logit's
two terms); layer 2's once more for its gradient, written back. So
``segment.traffic_roofline``, which asks for ``message_bytes``, stays
silent in this cell, and ``attention.traffic_roofline`` reads this key.
"""

from __future__ import annotations

import importlib.util
import os


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location(
        "benchmark_costs_" + name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


expected_expansion = _beside("gcn_costs.py").expected_expansion


def step_costs(cfg: dict, per_chip_batch: int, device_sampling: bool) -> dict:
    b = int(per_chip_batch)
    if cfg["aggregator"] != "attention" or len(cfg["fanouts"]) != 2:
        raise ValueError("the cost function covers two attention layers")
    feat, dim, classes = cfg["feature_dim"], cfg["dim"], cfg["num_classes"]
    heads = int(cfg["num_heads"])
    head_dim = dim // heads
    itemsize = 4  # float32 tables, int32 ids
    x = expected_expansion(cfg, b)
    e1, u1, e2, u2 = x["e1"], x["u1"], x["e2"], x["u2"]

    # projections: 2*m*k*n a matmul, every unique node once a layer.
    # Backward: dW everywhere; dX only where the input has a gradient
    # (layer 1 reads constant features)
    fwd0 = 2 * (b + u1 + u2) * feat * dim
    fwd1 = 2 * (b + u1) * dim * dim
    fwd_out = 2 * b * dim * classes
    # the gates: a self gate where a node is aggregated into, a neighbour
    # gate where it is a neighbour
    gates = 2 * head_dim * heads * ((b + u1) + (u1 + u2) + b + u1)
    # per true edge and head: logit (2), exp (1), D multiply-adds
    per_edge = heads * (3 + 2 * head_dim)
    edge_ops = (e1 + e2) * per_edge + e1 * per_edge
    flops = 2 * fwd0 + 3 * fwd1 + 3 * fwd_out + 3 * (gates + edge_ops)

    gather_bytes = (b + u1 + u2) * feat * itemsize \
        + b * cfg["label_dim"] * itemsize
    edge_bytes = heads * (head_dim + 2) * itemsize
    attention_bytes = (e1 + e2) * edge_bytes + 2 * e1 * edge_bytes
    expand_bytes = (e1 + e2) * itemsize
    params = (feat * dim + 2 * dim) + (dim * dim + 2 * dim) \
        + dim * classes + classes
    # Adam: read p, m, v and the gradient, write p, m, v
    opt_bytes = 7 * params * itemsize
    return {
        "flops": float(flops),
        "bytes": float(gather_bytes + attention_bytes + expand_bytes
                       + opt_bytes),
        "gather_bytes": float(gather_bytes),
        "attention_bytes": float(attention_bytes),
        "expand_bytes": float(expand_bytes),
        "draw_bytes": 0.0,
        "opt_bytes": float(opt_bytes),
        "params": int(params),
        "unique_nodes": int(round(b + u1 + u2)),
        "edges": int(round(e1 + e2)),
    }
