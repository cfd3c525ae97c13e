"""Operations and bytes one ScalableSage (historical-store) training step
needs, from the cell's shapes: the cost function of
``scalable_sage_reddit`` (named in its file under ``"costs"``; see
``benchmark/costs.py`` for the keys the harness and the readers ask for).

What the algorithm needs, whatever implements it, per step and per chip,
for ``b`` roots: one drawn hop of ``fanouts[0]`` (``fanouts`` counts
layers in this family); layer 0 on the roots alone (the neighbours'
features are averaged first); layer 1 on the roots with the store rows
of the neighbours; the classifier; two Adams; and the stores' traffic
(``store_bytes``, read by ``store.traffic_roofline``).
"""

from __future__ import annotations


def step_costs(cfg: dict, per_chip_batch: int, device_sampling: bool) -> dict:
    b, fan = int(per_chip_batch), cfg["fanouts"][0]
    feat, dim, classes = cfg["feature_dim"], cfg["dim"], cfg["num_classes"]
    half = dim // 2 if cfg["concat"] else dim
    width = cfg["graph"]["max_degree"]  # slab width W the draw reads
    itemsize = 4  # float32 tables and stores, int32 ids
    n0, n1 = b, b * fan

    # dense layers: 2*m*k*n a matmul, two branches (self, neighbour mean)
    fwd0 = 2 * n0 * feat * half * 2
    fwd1 = 2 * n0 * dim * half * 2
    fwd_out = 2 * n0 * dim * classes
    # layer 0 reads constant features: forward once, dW for the loss and
    # dW for the store loss (which reaches layer 0 alone); layer 1 and
    # the classifier: forward, dW and dX (the rows read have a gradient)
    flops = 3 * fwd0 + 3 * fwd1 + 3 * fwd_out

    gather_bytes = ((n0 + n1) * feat + n0 * cfg["label_dim"]) * itemsize
    # store rows read at the neighbours; stale gradient rows read and
    # cleared and fresh rows written at the roots; the scatter-add reads
    # and writes a row per neighbour
    store_bytes = (n1 + 3 * n0 + 2 * n1) * dim * itemsize
    # the draw reads W ids and W cumulative weights per root, writes picks
    draw_bytes = n0 * width * 2 * itemsize + n1 * itemsize
    params = 2 * feat * half + 2 * dim * half + dim * classes + classes
    # each Adam: read p, m, v and the gradient, write p, m, v
    opt_bytes = 2 * 7 * params * itemsize
    id_bytes = 0 if device_sampling else (n0 + n1) * itemsize
    return {
        "flops": float(flops),
        "bytes": float(gather_bytes + store_bytes + opt_bytes + (
            draw_bytes if device_sampling else id_bytes)),
        "gather_bytes": float(gather_bytes),
        "store_bytes": float(store_bytes),
        "draw_bytes": float(draw_bytes if device_sampling else 0),
        "opt_bytes": float(opt_bytes),
        "params": int(params),
        "edges": int(n1),
    }
