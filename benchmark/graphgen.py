"""The benchmark's graph: a pure function of (graph_seed, node id).

Every quantity of a node (out-degree, neighbor list, feature row, label
row) is a hash of the graph seed and the node's id, so the bulk writer
below and the plain reference (which asks for a few thousand rows after
the window) compute the same values without sharing a table, and without
the program having made either.

The bulk writer emits the program's on-disk format (euler_tpu/graph/
convert.py pack_block: one framed block per node) with numpy structured
arrays, one dtype per out-degree, so that the graph still enters the
program through ``euler_tpu.Graph(directory=...)``.

Shapes follow the program's own generator (euler_tpu/datasets.py
build_synthetic): out-degree Poisson(avg_degree) clipped to
[1, max_degree], neighbors uniform over the nodes, all edge and node
weights 1, one node type, one edge type, float slot 0 = labels (one-hot
or independent bits), float slot 1 = features of unit variance.
"""

from __future__ import annotations

import json
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_U = np.uint64
_M1 = _U(0xBF58476D1CE4E5B9)
_M2 = _U(0x94D049BB133111EB)
_GOLD = _U(0x9E3779B97F4A7C15)
# stream constants: which quantity of the node a hash feeds
_DEG, _NBR, _FEAT, _LAB = (_U(c) for c in (0xD1, 0xA2, 0xF3, 0x1B4))
FORMAT_VERSION = 1
ROWS_PER_CHUNK = 4096


def _mix(x):
    """splitmix64 finalizer over a uint64 array (wraps by design)."""
    x = (x ^ (x >> _U(30))) * _M1
    x = (x ^ (x >> _U(27))) * _M2
    return x ^ (x >> _U(31))


def _hash(seed: int, stream, rows, cols):
    """uint64 hash of (seed, stream, row, col); rows [n, 1], cols [1, m]."""
    base = _mix(np.array([(seed * int(_GOLD) + int(stream)) % (1 << 64)],
                         dtype=_U))[0]
    return _mix((rows.astype(_U) * _GOLD + base) ^ _mix(cols.astype(_U) + base))


class GraphSpec:
    """Shape parameters of one generated graph (the ``graph`` group of a
    configuration file)."""

    def __init__(self, num_nodes, avg_degree, max_degree, feature_dim,
                 label_dim, multilabel, graph_seed, num_partitions=16):
        self.num_nodes = int(num_nodes)
        self.avg_degree = float(avg_degree)
        self.max_degree = int(max_degree)
        self.feature_dim = int(feature_dim)
        self.label_dim = int(label_dim)
        self.multilabel = bool(multilabel)
        self.graph_seed = int(graph_seed)
        self.num_partitions = int(num_partitions)
        # Poisson inverse CDF as a table over the clipped support
        k = np.arange(self.max_degree + 1)
        logp = (k * math.log(self.avg_degree) - self.avg_degree
                - np.array([math.lgamma(i + 1.0) for i in k]))
        self._cdf = np.cumsum(np.exp(logp))

    def key(self) -> str:
        return json.dumps(
            dict(v=FORMAT_VERSION, n=self.num_nodes, d=self.avg_degree,
                 w=self.max_degree, f=self.feature_dim, l=self.label_dim,
                 m=self.multilabel, s=self.graph_seed,
                 p=self.num_partitions),
            sort_keys=True,
        )

    # ---- the per-node functions (what the reference reads) ----
    def degrees(self, ids) -> np.ndarray:
        ids = np.asarray(ids, dtype=np.int64).reshape(-1, 1)
        h = _hash(self.graph_seed, _DEG, ids, np.zeros((1, 1), np.int64))
        u = (h[:, 0] >> _U(11)).astype(np.float64) / float(1 << 53)
        d = np.searchsorted(self._cdf, u, side="right")
        return np.clip(d, 1, self.max_degree).astype(np.int32)

    def neighbor_slab(self, ids) -> np.ndarray:
        """[n, max_degree] int64 neighbor ids; columns >= degree hold
        draws that are NOT edges (callers mask with ``degrees``)."""
        ids = np.asarray(ids, dtype=np.int64).reshape(-1, 1)
        cols = np.arange(self.max_degree, dtype=np.int64).reshape(1, -1)
        h = _hash(self.graph_seed, _NBR, ids, cols)
        return (h % _U(self.num_nodes)).astype(np.int64)

    def features(self, ids) -> np.ndarray:
        """[n, feature_dim] float32, unit variance: each 64-bit hash
        gives two values, each the centred sum of two 16-bit fields."""
        ids = np.asarray(ids, dtype=np.int64).reshape(-1, 1)
        half = (self.feature_dim + 1) // 2
        h = _hash(self.graph_seed, _FEAT, ids,
                  np.arange(half, dtype=np.int64).reshape(1, -1))
        m = _U(0xFFFF)
        a = ((h & m) + ((h >> _U(16)) & m)).astype(np.float32)
        b = (((h >> _U(32)) & m) + (h >> _U(48))).astype(np.float32)
        out = np.empty((len(ids), 2 * half), np.float32)
        out[:, 0::2] = a
        out[:, 1::2] = b
        # sum of two uniforms on [0, 65535]: mean 65535, std 65536/sqrt(6)
        out -= np.float32(65535.0)
        out *= np.float32(math.sqrt(6.0) / 65536.0)
        return out[:, : self.feature_dim]

    def labels(self, ids) -> np.ndarray:
        """[n, label_dim] float32: independent bits, or one-hot."""
        ids = np.asarray(ids, dtype=np.int64).reshape(-1, 1)
        if self.multilabel:
            words = (self.label_dim + 63) // 64
            h = _hash(self.graph_seed, _LAB, ids,
                      np.arange(words, dtype=np.int64).reshape(1, -1))
            bits = (h[:, :, None] >> np.arange(64, dtype=_U)) & _U(1)
            return bits.reshape(len(ids), -1)[:, : self.label_dim].astype(
                np.float32)
        h = _hash(self.graph_seed, _LAB, ids, np.zeros((1, 1), np.int64))
        cls = (h[:, 0] % _U(self.label_dim)).astype(np.int64)
        out = np.zeros((len(ids), self.label_dim), np.float32)
        out[np.arange(len(ids)), cls] = 1.0
        return out

    # ---- the bulk writer (what the program loads) ----
    def _block_dtype(self, deg: int) -> np.dtype:
        return np.dtype([
            ("block_bytes", "<i4"), ("node_bytes", "<i4"),
            ("id", "<u8"), ("type", "<i4"), ("weight", "<f4"),
            ("edge_types", "<i4"), ("group_size", "<i4"),
            ("group_weight", "<f4"),
            ("nbr", "<u8", (deg,)), ("nbr_w", "<f4", (deg,)),
            ("u64_slots", "<i4"),
            ("f32_slots", "<i4"), ("f32_sizes", "<i4", (2,)),
            ("labels", "<f4", (self.label_dim,)),
            ("features", "<f4", (self.feature_dim,)),
            ("bin_slots", "<i4"),
            ("edge_num", "<i4"),
        ])

    def _chunk_bytes(self, lo: int, hi: int) -> bytes:
        ids = np.arange(lo, hi, dtype=np.int64)
        deg = self.degrees(ids)
        slab = self.neighbor_slab(ids)
        feats = self.features(ids)
        labs = self.labels(ids)
        parts = []
        for d in np.unique(deg):
            sel = np.nonzero(deg == d)[0]
            dt = self._block_dtype(int(d))
            rec = np.zeros(len(sel), dt)
            node_bytes = dt.itemsize - 12  # less the two frame ints + edge_num
            rec["block_bytes"] = 4 + node_bytes + 4
            rec["node_bytes"] = node_bytes
            rec["id"] = ids[sel]
            rec["weight"] = 1.0
            rec["edge_types"] = 1
            rec["group_size"] = d
            rec["group_weight"] = float(d)
            rec["nbr"] = slab[sel, :d]
            rec["nbr_w"] = 1.0
            rec["f32_slots"] = 2
            rec["f32_sizes"] = (self.label_dim, self.feature_dim)
            rec["labels"] = labs[sel]
            rec["features"] = feats[sel]
            parts.append(rec.tobytes())
        return b"".join(parts)

    def write(self, out_dir: str, threads: int | None = None) -> str:
        """Write the graph under ``out_dir`` unless a finished copy with
        the same key is there. Chunks of rows go round-robin to the
        partition files (the engine indexes nodes by id, whatever file
        they are in), generated by a few threads (numpy releases the
        GIL) and written in order."""
        marker = os.path.join(out_dir, "done")
        if os.path.exists(marker):
            with open(marker) as f:
                if f.read() == self.key():
                    return out_dir
        os.makedirs(out_dir, exist_ok=True)
        for name in os.listdir(out_dir):
            if name.endswith(".dat") or name in ("done", "meta.json"):
                os.unlink(os.path.join(out_dir, name))
        meta = {
            "node_type_num": 1, "edge_type_num": 1,
            "node_uint64_feature_num": 0, "node_float_feature_num": 2,
            "node_binary_feature_num": 0, "edge_uint64_feature_num": 0,
            "edge_float_feature_num": 0, "edge_binary_feature_num": 0,
        }
        with open(os.path.join(out_dir, "meta.json"), "w") as f:
            json.dump(meta, f)
        spans = [
            (lo, min(lo + ROWS_PER_CHUNK, self.num_nodes))
            for lo in range(0, self.num_nodes, ROWS_PER_CHUNK)
        ]
        threads = threads or min(os.cpu_count() or 1, 12)
        outs = [
            open(os.path.join(out_dir, "part_%d.dat" % p), "wb")
            for p in range(self.num_partitions)
        ]
        try:
            with ThreadPoolExecutor(threads) as pool:
                # bounded look-ahead: at most 2*threads chunks in memory
                pending = []
                it = iter(enumerate(spans))
                for _ in range(2 * threads):
                    nxt = next(it, None)
                    if nxt is not None:
                        pending.append(
                            (nxt[0], pool.submit(self._chunk_bytes, *nxt[1])))
                while pending:
                    i, fut = pending.pop(0)
                    outs[i % self.num_partitions].write(fut.result())
                    nxt = next(it, None)
                    if nxt is not None:
                        pending.append(
                            (nxt[0], pool.submit(self._chunk_bytes, *nxt[1])))
        finally:
            for o in outs:
                o.close()
        with open(marker, "w") as f:
            f.write(self.key())
        return out_dir


def spec_from_config(cfg: dict) -> GraphSpec:
    g = cfg["graph"]
    return GraphSpec(
        num_nodes=g["num_nodes"], avg_degree=g["avg_degree"],
        max_degree=g["max_degree"], feature_dim=cfg["feature_dim"],
        label_dim=cfg["label_dim"], multilabel=cfg["sigmoid_loss"],
        graph_seed=g["graph_seed"],
        num_partitions=g.get("num_partitions", 16),
    )
