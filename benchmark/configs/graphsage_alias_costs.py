"""Operations and bytes one supervised GraphSAGE training step needs where
its draws are exact alias draws over flat-CSR tables: the cost function of
``graphsage_reddit_powerlaw`` (named in its file under ``"costs"``).

Every key is ``graphsage_costs.py``'s (loaded beside this file), but the
draws'. ``graphsage_costs.py`` counts a padded slab row, ``max_degree``
ids and as many cumulative weights, for every row drawn from; here that
width is the graph's largest degree, tens of thousands. An O(1) alias
draw reads, for every row drawn from, its start, its degree and whether
it can be drawn from (``off``, ``deg`` int32, ``sampleable`` bool: 9 B),
and for every draw one slot's threshold, neighbour and alias (``prob``,
``nbr``, ``alias``: 12 B) and writes the pick (4 B): the least such a
draw moves, whatever implements it. ``bytes`` is the whole step's with
these draw bytes in it; ``alias_draws`` (the draws of a step) names the
draw's form for ``layers/alias.traffic_roofline.py``.
"""

from __future__ import annotations

import importlib.util
import os

ROW_BYTES = 4 + 4 + 1       # off, deg, sampleable
DRAW_BYTES = 4 + 4 + 4 + 4  # prob, nbr, alias read; the pick written


def _graphsage_costs():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "graphsage_costs.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_costs_graphsage_beside_alias", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def step_costs(cfg: dict, per_chip_batch: int, device_sampling: bool) -> dict:
    b = int(per_chip_batch)
    f1, f2 = cfg["fanouts"]
    # the slab cost function at width 0: no row read, the picks written
    slab = _graphsage_costs().step_costs(
        dict(cfg, graph=dict(cfg["graph"], max_degree=0)), b,
        device_sampling)
    rows, draws = b + b * f1, b * f1 + b * f1 * f2
    draw_bytes = rows * ROW_BYTES + draws * DRAW_BYTES if device_sampling \
        else 0
    out = dict(slab)
    out["bytes"] = slab["bytes"] - slab["draw_bytes"] + draw_bytes
    out["draw_bytes"] = float(draw_bytes)
    out["alias_draws"] = draws if device_sampling else 0
    return out
