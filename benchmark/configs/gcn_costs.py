"""Operations and bytes one full-neighbourhood GCN training step needs,
from the cell's shapes and the graph function's degree law: the cost
function of ``gcn_ppi`` (named in its file under ``"costs"``; see
``benchmark/costs.py`` for the keys the harness and the readers ask for).

What the algorithm needs, whatever implements it, per step and per chip,
for ``b`` roots: the WHOLE neighbourhood of every root and of every node
of the first hop (upstream's ``get_multi_hop_neighbor``), each hop's
nodes made unique; every unique node's feature row read once; every true
edge's message read once; two mean-aggregator layers and a linear
classifier; Adam over the parameters. Nothing is drawn (``draw_bytes``
0), and nothing here knows of padding, caps, a sort or a mask: a step of
the program that works on 1.87M padded slots is held against the 0.41M
true edges those slots carry.

``edges`` is the EXPECTED number of true edges of both hops, one number
a configuration, from the degree law alone (out-degree Poisson(avg)
clipped to [1, max], neighbours uniform over the ``N`` nodes):

* ``d`` = E[degree] = sum over k of clip(k, 1, max) x Poisson(avg)(k);
* hop 1: ``b`` roots (each row counts, a root drawn twice too) x ``d``
  edges = ``e1``; they land on ``u1`` = N (1 - (1 - 1/N)^e1) distinct
  nodes (e1 uniform throws into N boxes);
* hop 2: the ``u1`` unique nodes x ``d`` = ``e2`` edges (a node's degree
  does not depend on who points at it), on ``u2`` = N (1 - (1 - 1/N)^e2)
  distinct nodes.

At the cell's sizes (b 512, avg 28, max 60, N 2,090,000): d 28.000,
e1 14,336, u1 14,287, e2 400,035, u2 364,081; ``edges`` 414,371. The
tests hold it within 1% of the mean count of unmasked edges over 200
seeded steps of the program at toy size.
"""

from __future__ import annotations

import math


def mean_degree(avg: float, max_degree: int) -> float:
    """E[clip(Poisson(avg), 1, max_degree)]."""
    total, below = 0.0, 0.0
    for k in range(max_degree):
        p = math.exp(k * math.log(avg) - avg - math.lgamma(k + 1.0))
        total += max(k, 1) * p
        below += p
    return total + max_degree * (1.0 - below)


def distinct(throws: float, boxes: int) -> float:
    """Expected boxes hit by ``throws`` uniform throws into ``boxes``."""
    return boxes * -math.expm1(throws * math.log1p(-1.0 / boxes))


def expected_expansion(cfg: dict, b: int) -> dict:
    """e1, u1, e2, u2 of the docstring for ``b`` roots."""
    g = cfg["graph"]
    d = mean_degree(float(g["avg_degree"]), int(g["max_degree"]))
    e1 = b * d
    u1 = distinct(e1, int(g["num_nodes"]))
    e2 = u1 * d
    u2 = distinct(e2, int(g["num_nodes"]))
    return {"d": d, "e1": e1, "u1": u1, "e2": e2, "u2": u2}


def step_costs(cfg: dict, per_chip_batch: int, device_sampling: bool) -> dict:
    b = int(per_chip_batch)
    if cfg["aggregator"] != "mean" or len(cfg["fanouts"]) != 2:
        raise ValueError("the cost function covers two mean layers")
    feat, dim, classes = cfg["feature_dim"], cfg["dim"], cfg["num_classes"]
    itemsize = 4  # float32 tables, int32 ids
    x = expected_expansion(cfg, b)
    e1, u1, e2, u2 = x["e1"], x["u1"], x["e2"], x["u2"]

    # dense layers: 2*m*k*n a matmul, two branches (self, neighbour mean).
    # Layer 1 on the roots and on hop 1's unique nodes, layer 2 on the
    # roots. Backward: dW everywhere; dX only where the input has a
    # gradient (layer 1 reads constant features)
    fwd0 = 2 * (b + u1) * feat * dim * 2
    fwd1 = 2 * b * dim * dim * 2
    fwd_out = 2 * b * dim * classes
    # the means: one add an element of every message, forward; layer 2's
    # messages once more for their gradient
    means = (e1 + e2) * feat + 2 * e1 * dim
    flops = 2 * fwd0 + 3 * fwd1 + 3 * fwd_out + means

    # every unique node's feature row once, the roots' labels
    gather_bytes = (b + u1 + u2) * feat * itemsize \
        + b * cfg["label_dim"] * itemsize
    # every true edge's message once: layer 1 reads a feature row an edge
    # of either hop, layer 2 a hidden row an edge of hop 1 (and writes its
    # gradient back)
    message_bytes = (e1 + e2) * feat * itemsize + 2 * e1 * dim * itemsize
    # the neighbour lists of the roots and of hop 1's nodes, an id an edge
    expand_bytes = (e1 + e2) * itemsize
    params = 2 * feat * dim + 2 * dim * dim + dim * classes + classes
    # Adam: read p, m, v and the gradient, write p, m, v
    opt_bytes = 7 * params * itemsize
    return {
        "flops": float(flops),
        "bytes": float(gather_bytes + message_bytes + expand_bytes
                       + opt_bytes),
        "gather_bytes": float(gather_bytes),
        "message_bytes": float(message_bytes),
        "expand_bytes": float(expand_bytes),
        "draw_bytes": 0.0,
        "opt_bytes": float(opt_bytes),
        "params": int(params),
        "unique_nodes": int(round(b + u1 + u2)),
        "edges": int(round(e1 + e2)),
    }
