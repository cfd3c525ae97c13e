"""Operations and bytes one node2vec training step needs, from the
cell's shapes: the cost function of ``node2vec_ppi`` (named in its file
under ``"costs"``; see ``benchmark/costs.py`` for the keys the harness
and the readers ask for).

What the algorithm needs, whatever implements it, per step and per chip,
for ``b`` roots: ``walk_len`` chained single-neighbour draws a root; the
skip-gram pairs of a path (``P`` a root by the window rule) with
``num_negs`` negatives each from the node sampler; a row of ``dim``
floats gathered per pair member (source, context, negatives) from two
id-embedding tables and its gradient scatter-added back
(``pair_row_bytes``, read by ``embed.traffic_roofline``); and the dense
Adam of the recipe over both whole tables (``opt_bytes``, read by
``optimizer.traffic_roofline``): upstream's TensorFlow 1 Adam decays and
applies ``m`` and ``v`` of every row at every step.
"""

from __future__ import annotations


def pair_count(path_len: int, left_win: int, right_win: int) -> int:
    """Pairs of one path: every position with its contexts in reach."""
    return sum(min(j, left_win) + min(path_len - 1 - j, right_win)
               for j in range(path_len))


def step_costs(cfg: dict, per_chip_batch: int, device_sampling: bool) -> dict:
    b, steps = int(per_chip_batch), int(cfg["walk_len"])
    dim, negs = int(cfg["dim"]), int(cfg["num_negs"])
    rows = cfg["graph"]["num_nodes"] + 1
    width = cfg["graph"]["max_degree"]  # slab width W a draw reads
    itemsize = 4  # float32 tables and moments, int32 ids
    pairs = b * pair_count(
        steps + 1, int(cfg["left_win_size"]), int(cfg["right_win_size"]))
    pair_rows = pairs * (2 + negs)      # source, context, negatives

    # logits: one dot of dim a positive and a negative; forward, and the
    # gradient by either side
    flops = 3 * 2 * dim * pairs * (1 + negs)
    # Adam on every element of both tables: two moments, two
    # corrections, root, divide, step
    flops += 2 * rows * dim * 12
    # each gathered row is read once, and its gradient is added into a
    # row (read and written)
    gather_bytes = pair_rows * dim * itemsize
    pair_row_bytes = 3 * gather_bytes
    # the dense Adam reads p, m, v and the gradient, writes p, m, v
    opt_bytes = 2 * 7 * rows * dim * itemsize
    # a draw reads W ids and W cumulative weights of its row, writes a pick
    draw_bytes = b * steps * (width * 2 + 1) * itemsize
    # a negative: a bisection over the sampler's cumulative weights
    # (a segment first, then inside it) and the id it lands on
    neg_bytes = pairs * negs * (rows.bit_length() + 2) * itemsize
    id_bytes = 0 if device_sampling else pair_rows * itemsize
    return {
        "flops": float(flops),
        "bytes": float(opt_bytes + pair_row_bytes + (
            draw_bytes + neg_bytes if device_sampling else id_bytes)),
        "gather_bytes": float(gather_bytes),
        "pair_row_bytes": float(pair_row_bytes),
        "draw_bytes": float(draw_bytes if device_sampling else 0),
        "neg_bytes": float(neg_bytes if device_sampling else 0),
        "opt_bytes": float(opt_bytes),
        "params": int(2 * rows * dim),
        "pair_rows": int(pair_rows),
        "edges": int(b * steps),
    }
