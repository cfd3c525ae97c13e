"""Operations and bytes one supervised GraphSAGE training step needs, from
the cell's shapes: two drawn hops, mean aggregator, a linear classifier,
Adam. The cost function of ``graphsage_reddit`` and ``graphsage_ppi``
(named in their files under ``"costs"``).

What the algorithm needs, whatever implements it: the same numbers for a
fused kernel, an XLA chain or a host sampler. Per step and per chip, for
a per-chip batch of ``b`` roots. ``benchmark/costs.py`` says which keys
the harness and the readers ask for.
"""

from __future__ import annotations


def step_costs(cfg: dict, per_chip_batch: int, device_sampling: bool) -> dict:
    b = int(per_chip_batch)
    f1, f2 = cfg["fanouts"]
    feat, dim, classes = cfg["feature_dim"], cfg["dim"], cfg["num_classes"]
    half = dim // 2 if cfg["concat"] else dim
    width = cfg["graph"]["max_degree"]  # slab width W the draws read
    itemsize = 4  # float32 tables, int32 ids

    n0, n1, n2 = b, b * f1, b * f1 * f2
    # dense layers: 2*m*k*n a matmul, two branches (self, neighbour mean)
    fwd0 = 2 * (n0 + n1) * feat * half * 2      # layer 0 on hops 0 and 1
    fwd1 = 2 * n0 * dim * half * 2              # layer 1 on hop 0
    fwd_out = 2 * n0 * dim * classes            # classifier
    # backward: dW everywhere; dX only where the input has a gradient
    # (layer 0 reads constant features)
    flops = 2 * fwd0 + 3 * fwd1 + 3 * fwd_out

    gather_bytes = (n0 + n1 + n2) * feat * itemsize + n0 * cfg["label_dim"] * itemsize
    # a draw reads, for every row drawn from, W ids and W cumulative
    # weights, and writes the picks
    draw_bytes = (n0 + n1) * width * 2 * itemsize + (n1 + n2) * itemsize
    params = (2 * feat * half + 2 * dim * half + dim * classes + classes)
    # Adam: read p, m, v and the gradient, write p, m, v
    opt_bytes = 7 * params * itemsize
    id_bytes = 0 if device_sampling else (n0 + n1 + n2) * itemsize
    step_bytes = gather_bytes + opt_bytes + (
        draw_bytes if device_sampling else id_bytes
    )
    return {
        "flops": float(flops),
        "bytes": float(step_bytes),
        "gather_bytes": float(gather_bytes),
        "draw_bytes": float(draw_bytes if device_sampling else 0),
        "opt_bytes": float(opt_bytes),
        "params": int(params),
        "edges": int(n1 + n2),
    }
