"""Operations and bytes one historical-store training step needs, from
the cell's shapes (the toy store configuration's cost function; see
``benchmark/costs.py`` for the keys). Per step and per chip, for ``b``
roots: one drawn hop of ``fanouts[0]``, layer 0 on the roots alone (the
neighbours' features are averaged first), layer 1 on the roots with the
store rows of the neighbours, two Adams."""

from __future__ import annotations


def step_costs(cfg: dict, per_chip_batch: int, device_sampling: bool) -> dict:
    b, fan = int(per_chip_batch), cfg["fanouts"][0]
    feat, dim, classes = cfg["feature_dim"], cfg["dim"], cfg["num_classes"]
    half = dim // 2 if cfg["concat"] else dim
    width = cfg["graph"]["max_degree"]
    itemsize = 4
    n0, n1 = b, b * fan

    fwd0 = 2 * n0 * feat * half * 2     # self and neighbour-mean branch
    fwd1 = 2 * n0 * dim * half * 2
    fwd_out = 2 * n0 * dim * classes
    # layer 0 reads constants: forward once, dW for the loss and dW for
    # the store loss; layer 1 and the classifier: forward, dW and dX
    flops = 3 * fwd0 + 3 * fwd1 + 3 * fwd_out

    gather_bytes = (n0 + n1) * feat * itemsize + n0 * cfg["label_dim"] * itemsize
    # store rows read at the neighbours; stale rows read and cleared and
    # fresh rows written at the roots; scatter-add reads and writes
    store_bytes = (n1 + 3 * n0 + 2 * n1) * dim * itemsize
    draw_bytes = n0 * width * 2 * itemsize + n1 * itemsize
    params = 2 * feat * half + 2 * dim * half + dim * classes + classes
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
