"""The graph of ``graphsage_reddit_powerlaw``: Reddit's node count, edge
budget and table widths under a heavy-tailed degree law, every quantity
a pure function of (graph_seed, node id) as ``benchmark/graphgen.py``'s
protocol states (its docstring), written through ``graphgen.write_graph``
and ``graphgen.pack_blocks``:

* out-degree: a discrete power law P(d) ~ d^-alpha on [degree_floor,
  degree_cap] (alpha 1.8, ``euler_tpu.datasets.REDDIT_HEAVYTAIL``'s), by
  the inverse of its exact cumulative at a hash of the node. Truncated,
  not clipped: no spike at the cap;
* neighbours: slot c of node i is the node whose share of the summed
  out-degrees holds a hash of (i, c) (preferential, as
  ``datasets.build_powerlaw`` draws them): hubs are hubs both ways, and a
  row may list a node twice;
* edge weights 1 to 8 from a hash of (i, c);
* features and labels: ``graphgen``'s hashes.

The pair questions (``are_edges``, ``edge_quantiles``) expand each
distinct parent's row once a call, in blocks of at most ``BLOCK_ENTRIES``
list entries: hop 2's parents were drawn in proportion to in-weight, so
they are mostly hubs and come back many times in a step. The answer of
the last call is kept: ``check.draw_numbers`` asks both questions of the
same pairs.

``edge_quantiles`` ranks a row's edges by weight, ties by slot, and gives
the midpoint of the child's edge in that order as a share of the row's
weight; where the row lists the child more than once, the midpoints of
its copies averaged with their weights. A draw in proportion to weight
then reads a mean of 0.5; a uniform draw over slots about 0.354 on a long
row (the eight weights alike: sum of k^2 / 2 over k = 1..8, over 36 x 8);
a draw kept to a row's 60 heaviest edges near 1 on every longer row.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark import graphgen

_U = np.uint64
# streams of this module's own hashes (graphgen's are 0xD1..0x1B4, the
# toy power-law graph's 0x2A2..0x2E5)
_DEG, _NBR, _W = (_U(c) for c in (0x3D1, 0x3A2, 0x3C7))
WEIGHTS = 8  # edge weights are 1..WEIGHTS
# (parent, child) pairs are judged in blocks of at most this many of the
# distinct parents' list entries
BLOCK_ENTRIES = 1 << 22
# a slot's neighbour is looked up from the first node of its run of
# 2^_RUN slots of the summed out-degrees (a few steps), where a binary
# search over 2M nodes misses the cache at each of its 21 (6.7 s against
# 0.8 s for a step's 13M list entries on the builder's host)
_RUN = 3


def _uniform(h) -> np.ndarray:
    """[0, 1) from the top 53 bits of a hash."""
    return (h >> _U(11)).astype(np.float64) / float(1 << 53)


class PowerLawGraph:
    def __init__(self, num_nodes, alpha, degree_floor, degree_cap,
                 feature_dim, label_dim, multilabel, graph_seed,
                 num_partitions=16):
        self.num_nodes = int(num_nodes)
        self.alpha = float(alpha)
        self.degree_floor = int(degree_floor)
        self.degree_cap = int(degree_cap)
        self.feature_dim = int(feature_dim)
        self.label_dim = int(label_dim)
        self.multilabel = bool(multilabel)
        self.graph_seed = int(graph_seed)
        self.num_partitions = int(num_partitions)
        d = np.arange(self.degree_floor, self.degree_cap + 1,
                      dtype=np.float64)
        cdf = np.cumsum(d ** -self.alpha)
        self._degree_cdf = cdf / cdf[-1]
        # the out-degrees' running sum, the one table: where a slot's
        # hash lands picks its neighbour in proportion to out-degree
        self._out_cum = np.cumsum(
            self.degrees(np.arange(self.num_nodes)), dtype=np.int64)
        self.num_edges = int(self._out_cum[-1])
        self._run_first = np.searchsorted(
            self._out_cum, np.arange(0, self.num_edges, 1 << _RUN),
            side="right").astype(np.int32)
        self._last = None

    def key(self) -> str:
        return json.dumps(
            dict(v=graphgen.FORMAT_VERSION, law="powerlaw-preferential",
                 n=self.num_nodes, a=self.alpha, lo=self.degree_floor,
                 hi=self.degree_cap, f=self.feature_dim, l=self.label_dim,
                 m=self.multilabel, s=self.graph_seed,
                 p=self.num_partitions),
            sort_keys=True,
        )

    # ---- per node ----
    def degrees(self, ids) -> np.ndarray:
        ids = np.asarray(ids, dtype=np.int64).reshape(-1)
        u = _uniform(graphgen.hash64(self.graph_seed, _DEG, ids, ids * 0))
        k = np.searchsorted(self._degree_cdf, u, side="right")
        k = np.minimum(k, len(self._degree_cdf) - 1)
        return (self.degree_floor + k).astype(np.int32)

    def features(self, ids) -> np.ndarray:
        return graphgen.node_features(self.graph_seed, ids, self.feature_dim)

    def labels(self, ids) -> np.ndarray:
        return graphgen.node_labels(self.graph_seed, ids, self.label_dim,
                                    self.multilabel)

    def neighbours(self, ids) -> tuple:
        """(offsets [n + 1], ids, weights), ragged, in slot order."""
        ids = np.asarray(ids, dtype=np.int64).reshape(-1)
        deg = self.degrees(ids).astype(np.int64)
        offsets = np.zeros(len(ids) + 1, np.int64)
        np.cumsum(deg, out=offsets[1:])
        rows = np.repeat(ids, deg)
        cols = np.arange(offsets[-1]) - np.repeat(offsets[:-1], deg)
        at = (graphgen.hash64(self.graph_seed, _NBR, rows, cols)
              % _U(self.num_edges)).astype(np.int64)
        # the node whose run of the summed out-degrees holds the slot
        nbr = self._run_first[at >> _RUN].astype(np.int64)
        while True:
            past = self._out_cum[nbr] <= at
            if not past.any():
                break
            nbr += past
        w = 1 + (graphgen.hash64(self.graph_seed, _W, rows, cols)
                 & _U(WEIGHTS - 1))
        return offsets, nbr, w.astype(np.float32)

    def degree_law(self) -> dict:
        """The out-degrees as generated: what the configuration's
        ``assumed`` states, and the share of the edges in rows longer than
        a slab of Reddit's width (60) or of the draw kernel's (512)
        would hold."""
        deg = np.diff(self._out_cum, prepend=0)
        return {
            "edges": self.num_edges,
            "mean": float(deg.mean()),
            "p50": float(np.percentile(deg, 50)),
            "p99": float(np.percentile(deg, 99)),
            "max": int(deg.max()),
            "nodes_at_cap": int((deg == self.degree_cap).sum()),
            "edge_share_over_60": float(deg[deg > 60].sum() / deg.sum()),
            "edge_share_over_512": float(deg[deg > 512].sum() / deg.sum()),
        }

    # ---- per (parent, child) pair: the ragged questions ----
    def _edge_places(self, parents, children) -> tuple:
        """(found, quantile) of each pair: whether the parent lists the
        child, and where its edge lies in the parent's weight-ranked list
        (the module's docstring)."""
        parents = np.asarray(parents, dtype=np.int64).reshape(-1)
        children = np.asarray(children, dtype=np.int64).reshape(-1)
        asked = (parents.tobytes(), children.tobytes())
        if self._last is not None and self._last[0] == asked:
            return self._last[1]
        found = np.zeros(len(parents), bool)
        quantile = np.full(len(parents), np.nan)
        rows, row_of = np.unique(parents, return_inverse=True)
        order = np.argsort(row_of, kind="stable")
        ends = np.cumsum(self.degrees(rows).astype(np.int64))
        lo = 0
        while lo < len(rows):
            base = ends[lo - 1] if lo else 0
            hi = max(lo + 1, int(np.searchsorted(
                ends, base + BLOCK_ENTRIES, side="right")))
            a, b = np.searchsorted(row_of[order], [lo, hi])
            pairs = order[a:b]
            found[pairs], quantile[pairs] = self._block_places(
                rows[lo:hi], row_of[pairs] - lo, children[pairs])
            lo = hi
        self._last = (asked, (found, quantile))
        return found, quantile

    def _block_places(self, rows, pair_row, children) -> tuple:
        offsets, nbr, w = self.neighbours(rows)
        n = len(rows)
        entry_row = np.repeat(np.arange(n, dtype=np.int64), np.diff(offsets))
        w = w.astype(np.int64)
        # each row's weight by class (column k - 1: its edges of weight
        # k), the weight of its lighter classes, and its total
        mass = np.bincount(entry_row * WEIGHTS + w - 1,
                           minlength=n * WEIGHTS).reshape(n, WEIGHTS) \
            * np.arange(1, WEIGHTS + 1)
        lighter = np.cumsum(mass, axis=1) - mass
        total = mass.sum(axis=1)
        # the entries that answer a pair, by (row, id) key
        span = self.num_nodes + 1
        ok = (children >= 0) & (children < self.num_nodes)
        keys, key_of = np.unique(np.where(ok, pair_row * span + children, -1),
                                 return_inverse=True)
        entry_key = entry_row * span + nbr
        at = np.minimum(np.searchsorted(keys, entry_key), len(keys) - 1)
        hit = np.nonzero(keys[at] == entry_key)[0]
        hw = w[hit]
        # a hit's rank among its row's edges of its weight, by slot
        rank = np.empty(len(hit), np.int64)
        for k in np.unique(hw):
            run = np.cumsum(w == k)
            sel = hw == k
            e = hit[sel]
            start = offsets[entry_row[e]]
            rank[sel] = run[e] - 1 - np.where(start > 0, run[start - 1], 0)
        r = entry_row[hit]
        mid = (lighter[r, hw - 1] + hw * rank + 0.5 * hw) / total[r]
        den = np.bincount(at[hit], weights=hw, minlength=len(keys))
        num = np.bincount(at[hit], weights=hw * mid, minlength=len(keys))
        listed = den > 0
        q = np.full(len(keys), np.nan)
        q[listed] = num[listed] / den[listed]
        found = listed[key_of] & ok
        return found, np.where(found, q[key_of], np.nan)

    def are_edges(self, parents, children) -> np.ndarray:
        return self._edge_places(parents, children)[0]

    def edge_quantiles(self, parents, children) -> np.ndarray:
        return self._edge_places(parents, children)[1]

    # ---- the files: graphgen's format, this module's nodes ----
    def _chunk_bytes(self, lo: int, hi: int) -> bytes:
        ids = np.arange(lo, hi, dtype=np.int64)
        _, nbr, w = self.neighbours(ids)
        return graphgen.pack_blocks(ids, self.degrees(ids), nbr, w,
                                    self.labels(ids), self.features(ids))

    def write(self, out_dir: str) -> str:
        return graphgen.write_graph(out_dir, self.key(), self.num_nodes,
                                    self.num_partitions, self._chunk_bytes)


def spec_from_config(cfg: dict) -> PowerLawGraph:
    g = cfg["graph"]
    return PowerLawGraph(
        num_nodes=g["num_nodes"], alpha=g["alpha"],
        degree_floor=g["degree_floor"], degree_cap=g["degree_cap"],
        feature_dim=cfg["feature_dim"], label_dim=cfg["label_dim"],
        multilabel=cfg["sigmoid_loss"], graph_seed=g["graph_seed"],
        num_partitions=g.get("num_partitions", 16),
    )
