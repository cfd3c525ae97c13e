"""Synthetic benchmark datasets at reference scales.

The reference's data prep (reference examples/ppi_data.py:40-150 downloads
GraphSAGE-format PPI; reddit_data.py:42-58 converts DGL's reddit npz) pulls
real datasets over the network; this environment has zero egress, so these
generators emit synthetic graphs with the SAME scale constants (node count,
degree, feature/label dims — reference tf_euler/python/ppi_main.py:24-33 and
reddit_main.py:24-34) and the same .dat layout, making sampling + compute
cost representative while remaining fully reproducible.

Layout convention (matches the examples' training flags):
  float_feature slot 0 = labels (multi-/one-hot), slot 1 = input features.
"""

from __future__ import annotations

import json
import os

import numpy as np

PPI = dict(num_nodes=56944, avg_degree=15, feature_dim=50, label_dim=121,
           multilabel=True)
REDDIT = dict(num_nodes=232965, avg_degree=50, feature_dim=602, label_dim=41,
              multilabel=False)


def _cache_begin(out_dir: str, params: str,
                 protect_unmarked: bool = False) -> bool:
    """Shared done-marker protocol for every synthetic builder. True =
    a finished build with IDENTICAL params is already there (caller
    returns immediately). False = stale/partial/absent: stale outputs
    are cleared, the in-progress marker is written (so an interrupted
    build is detected and regenerated next time), and the caller must
    generate then call _cache_finish. ``protect_unmarked``: .dat
    partitions with NO marker at all are a real converted dataset —
    treated as cached rather than overwritten (build_synthetic's
    contract)."""
    os.makedirs(out_dir, exist_ok=True)
    marker = os.path.join(out_dir, "done")
    wip = os.path.join(out_dir, "synthetic-in-progress")
    if os.path.exists(marker):
        with open(marker) as f:
            if f.read() == params:
                return True
    elif (
        protect_unmarked
        and not os.path.exists(wip)
        and any(n.endswith(".dat") for n in os.listdir(out_dir))
    ):
        return True
    with open(wip, "w") as f:
        f.write(params)
    for name in os.listdir(out_dir):
        if name.endswith(".dat") or name in ("done", "meta.json"):
            os.unlink(os.path.join(out_dir, name))
    return False


def _cache_finish(out_dir: str, params: str) -> None:
    with open(os.path.join(out_dir, "done"), "w") as f:
        f.write(params)
    os.unlink(os.path.join(out_dir, "synthetic-in-progress"))


def build_synthetic(
    out_dir: str,
    num_nodes: int,
    avg_degree: int,
    feature_dim: int,
    label_dim: int,
    multilabel: bool = True,
    num_partitions: int = 4,
    max_degree: int = 60,
    seed: int = 7,
) -> str:
    """Write a synthetic graph as .dat partitions + meta.json (cached: a
    'done' marker records the generation params and skips regeneration only
    when they match). Returns out_dir."""
    params = json.dumps(
        dict(num_nodes=num_nodes, avg_degree=avg_degree,
             feature_dim=feature_dim, label_dim=label_dim,
             multilabel=multilabel, num_partitions=num_partitions,
             max_degree=max_degree, seed=seed),
        sort_keys=True,
    )
    if _cache_begin(out_dir, params, protect_unmarked=True):
        return out_dir
    from euler_tpu.graph.convert import pack_block

    rng = np.random.default_rng(seed)
    meta = {
        "node_type_num": 1,
        "edge_type_num": 1,
        "node_uint64_feature_num": 0,
        "node_float_feature_num": 2,
        "node_binary_feature_num": 0,
        "edge_uint64_feature_num": 0,
        "edge_float_feature_num": 0,
        "edge_binary_feature_num": 0,
    }
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f)
    paths = [
        os.path.join(out_dir, "part_%d.dat" % p)
        for p in range(num_partitions)
    ]
    outs = [open(p, "wb") for p in paths]
    degrees = rng.poisson(avg_degree, num_nodes).clip(1, max_degree)
    for nid in range(num_nodes):
        nbrs = rng.integers(0, num_nodes, degrees[nid])
        if multilabel:
            labels = rng.integers(0, 2, label_dim).astype(float)
        else:
            labels = np.zeros(label_dim)
            labels[rng.integers(0, label_dim)] = 1.0
        node = {
            "node_id": nid,
            "node_type": 0,
            "node_weight": 1.0,
            "neighbor": {"0": {str(int(d)): 1.0 for d in nbrs}},
            "uint64_feature": {},
            "float_feature": {
                "0": labels.tolist(),
                "1": rng.standard_normal(feature_dim).round(3).tolist(),
            },
            "binary_feature": {},
            "edge": [],
        }
        outs[nid % num_partitions].write(pack_block(node, meta))
    for o in outs:
        o.close()
    _cache_finish(out_dir, params)
    return out_dir


def build_planted(
    out_dir: str,
    num_nodes: int = 2000,
    num_communities: int = 4,
    feature_dim: int = 16,
    avg_degree: int = 10,
    intra_p: float = 0.9,
    noise: float = 1.0,
    num_partitions: int = 2,
    max_degree: int = 30,
    seed: int = 11,
    alpha: float | None = None,
):
    """Planted-community graph: the convergence gate for supervised GNNs.

    ``alpha`` switches the degree distribution from
    Poisson(avg_degree).clip(1, max_degree) to the heavy-tailed power
    law of ``powerlaw_degrees`` (d_cap = max_degree) — the form the
    max_degree-truncation cost study trains on: same planted labels,
    same centroids, but hub nodes whose slab rows must truncate.

    Each node belongs to one of ``num_communities`` hidden communities;
    its label (float_feature slot 0, one-hot) IS the community, its input
    features (slot 1) are the community centroid plus ``noise`` * N(0,1),
    and a fraction ``intra_p`` of its edges stay inside the community.
    With the default noise the single-node nearest-centroid accuracy is
    mediocre while averaging the ~``avg_degree`` mostly-intra-community
    neighbor features denoises by ~sqrt(degree) and makes the label nearly
    perfectly recoverable — exactly the function a neighborhood-aggregating
    GNN (GraphSAGE/GCN/GAT) should learn. Tests compute both
    nearest-centroid accuracies numerically from the returned arrays to
    derive the F1 target instead of hard-coding folklore numbers.

    Returns (out_dir, info) where info holds the generation arrays:
    ``communities`` [N], ``features`` [N, F], ``centroids`` [K, F] and
    ``neighbors`` (list of per-node neighbor id arrays). The graph is
    written as .dat partitions + meta.json (cached like build_synthetic);
    info is regenerated deterministically from the seed either way.
    """
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    centroids = rng.standard_normal((num_communities, feature_dim))
    centroids /= np.linalg.norm(centroids, axis=1, keepdims=True)
    communities = rng.integers(0, num_communities, num_nodes)
    features = (
        centroids[communities]
        + noise * rng.standard_normal((num_nodes, feature_dim))
    ).astype(np.float32)
    by_comm = [
        np.flatnonzero(communities == c) for c in range(num_communities)
    ]
    if alpha is None:
        degrees = rng.poisson(avg_degree, num_nodes).clip(1, max_degree)
    else:
        degrees = powerlaw_degrees(
            num_nodes, num_nodes * avg_degree, alpha, rng,
            d_cap=max_degree,
        )
    neighbors = []
    for nid in range(num_nodes):
        d = degrees[nid]
        intra = rng.random(d) < intra_p
        own = by_comm[communities[nid]]
        nbrs = np.where(
            intra,
            own[rng.integers(0, len(own), d)],
            rng.integers(0, num_nodes, d),
        )
        neighbors.append(nbrs)
    info = dict(
        communities=communities,
        features=features,
        centroids=centroids,
        neighbors=neighbors,
    )

    params = json.dumps(
        dict(kind="planted", num_nodes=num_nodes,
             num_communities=num_communities, feature_dim=feature_dim,
             avg_degree=avg_degree, intra_p=intra_p, noise=noise,
             num_partitions=num_partitions, max_degree=max_degree,
             seed=seed, alpha=alpha),
        sort_keys=True,
    )
    if _cache_begin(out_dir, params):
        return out_dir, info
    from euler_tpu.graph.convert import pack_block

    meta = {
        "node_type_num": 1,
        "edge_type_num": 1,
        "node_uint64_feature_num": 0,
        "node_float_feature_num": 2,
        "node_binary_feature_num": 0,
        "edge_uint64_feature_num": 0,
        "edge_float_feature_num": 0,
        "edge_binary_feature_num": 0,
    }
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f)
    outs = [
        open(os.path.join(out_dir, "part_%d.dat" % p), "wb")
        for p in range(num_partitions)
    ]
    for nid in range(num_nodes):
        labels = np.zeros(num_communities)
        labels[communities[nid]] = 1.0
        node = {
            "node_id": nid,
            "node_type": 0,
            "node_weight": 1.0,
            "neighbor": {
                "0": {str(int(d)): 1.0 for d in neighbors[nid]}
            },
            "uint64_feature": {},
            "float_feature": {
                "0": labels.tolist(),
                "1": features[nid].tolist(),
            },
            "binary_feature": {},
            "edge": [],
        }
        outs[nid % num_partitions].write(pack_block(node, meta))
    for o in outs:
        o.close()
    _cache_finish(out_dir, params)
    return out_dir, info


def powerlaw_degrees(
    num_nodes: int, num_edges: int, alpha: float, rng,
    d_min: int = 1, d_cap: int | None = None,
):
    """[num_nodes] int64 out-degrees from a discrete power law
    P(d) ~ d^-alpha (inverse-transform Pareto, d >= d_min, capped at
    ``d_cap`` or num_nodes/4), then scaled so the total lands within
    ~1% of ``num_edges``. Real Reddit's degree distribution is
    heavy-tailed with mean ~490 over 233k nodes; alpha in [1.6, 2.2]
    reproduces that max/mean shape (see scripts/reddit_heavytail.py)."""
    if alpha <= 1.0:
        raise ValueError(
            f"powerlaw_degrees needs alpha > 1 (got {alpha}): the "
            "inverse-transform exponent -1/(alpha-1) is undefined at 1 "
            "and flips sign below it (degenerating to all-d_min rows)"
        )
    if d_cap is None:
        d_cap = max(d_min + 1, num_nodes // 4)
    u = rng.random(num_nodes)
    d = d_min * (1.0 - u) ** (-1.0 / (alpha - 1.0))
    d = np.minimum(d, d_cap)
    # multiplicative rescale to the target edge count; iterate because
    # the cap bites harder as the scale grows
    for _ in range(16):
        total = d.sum()
        if abs(total - num_edges) <= 0.01 * num_edges:
            break
        d = np.minimum(np.maximum(d * (num_edges / total), d_min), d_cap)
    return np.maximum(d.round(), d_min).astype(np.int64)


def _powerlaw_params(num_nodes, num_edges, feature_dim, label_dim,
                     alpha, multilabel, num_partitions, seed,
                     placement="hash") -> str:
    """The cache-identity string build_powerlaw's done marker records."""
    d = dict(kind="powerlaw", num_nodes=num_nodes, num_edges=num_edges,
             feature_dim=feature_dim, label_dim=label_dim, alpha=alpha,
             multilabel=multilabel, num_partitions=num_partitions,
             seed=seed, gen="unique-fill-v3-gumbel-hubs")
    if placement != "hash":
        # keyed only when non-default so every done marker written
        # before placement existed stays valid
        d["placement"] = placement
    return json.dumps(d, sort_keys=True)


def heavytail_cache_dir() -> str:
    """Default build_powerlaw cache dir for the Reddit-scale graph of
    scripts/reddit_heavytail.py --full.
    EULER_TPU_HEAVYTAIL_CACHE overrides; else <repo>/.data/reddit_ht."""
    return os.environ.get(
        "EULER_TPU_HEAVYTAIL_CACHE",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".data", "reddit_ht",
        ),
    )


def build_powerlaw(
    out_dir: str,
    num_nodes: int,
    num_edges: int,
    feature_dim: int,
    label_dim: int,
    alpha: float = 1.8,
    multilabel: bool = False,
    num_partitions: int = 4,
    seed: int = 17,
    progress_every: int = 0,
    placement: str = "hash",
) -> str:
    """Heavy-tailed synthetic graph at a REAL edge budget: power-law
    out-degrees (``powerlaw_degrees``) with targets drawn preferentially
    (p ~ degree), so in-degrees are heavy-tailed too — the degree shape
    build_synthetic's Poisson(avg_degree).clip(max_degree) deliberately
    avoids and real Reddit (~233k nodes x ~114M directed edges, mean
    ~490, hub degrees in the tens of thousands) actually has. Weights
    are 1.0 like real Reddit. This is the graph the max_degree
    truncation questions must be answered on: an untruncated device
    slab would be [N, max_observed_degree] and is not buildable, which
    is exactly the regime the exact (alias) device sampler exists for.

    Neighbors are drawn UNIQUE per source: naive with-replacement draws
    against a preferential target distribution collide so often that a
    120M-draw run landed only 74M distinct edges (measured 2026-07-31),
    35% under the real budget the graph exists to hit. Typical rows use
    draw/drop-duplicates/redraw rounds; HUB rows (where bounded redraws
    still fell 4.5% short in aggregate) switch to an exact weighted
    sample WITHOUT replacement via the Gumbel top-k race — so the
    achieved edge count tracks sum(degrees) ~ num_edges to <1%. Cached
    via the same done-marker protocol as build_synthetic. Returns
    out_dir.
    """
    os.makedirs(out_dir, exist_ok=True)
    params = _powerlaw_params(
        num_nodes, num_edges, feature_dim, label_dim, alpha, multilabel,
        num_partitions, seed, placement,
    )
    if _cache_begin(out_dir, params):
        return out_dir
    from euler_tpu.graph.convert import pack_block

    rng = np.random.default_rng(seed)
    degrees = powerlaw_degrees(num_nodes, num_edges, alpha, rng)
    # preferential targets: p ~ degree, drawn by inverse-CDF per node
    cum = np.cumsum(degrees.astype(np.float64))
    cum /= cum[-1]
    meta = {
        "node_type_num": 1,
        "edge_type_num": 1,
        "node_uint64_feature_num": 0,
        "node_float_feature_num": 2,
        "node_binary_feature_num": 0,
        "edge_uint64_feature_num": 0,
        "edge_float_feature_num": 0,
        "edge_binary_feature_num": 0,
    }
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f)
    # placement='degree' (eg_placement.h): buffer the node dicts and let
    # the converter's degree-aware placer route them + emit the
    # placement artifact — trades the streaming writer's O(1) memory for
    # the two-pass placement (fixture/bench scales; the hash default
    # keeps streaming for reddit-scale builds)
    if placement != "hash":
        from euler_tpu.graph.convert import _check_placement

        _check_placement(placement)
    buffered: list | None = [] if placement != "hash" else None
    outs = [] if buffered is not None else [
        open(os.path.join(out_dir, "part_%d.dat" % p), "wb")
        for p in range(num_partitions)
    ]
    # hub rows draw a large fraction of the skewed target mass; redraw
    # rounds stall once the heavy targets are all taken, so past this
    # degree use the exact O(N) Gumbel race instead (few thousand rows
    # at Reddit scale — ~2 ms each)
    hub_degree = max(2048, num_nodes // 64)
    log_w = np.log(degrees.astype(np.float64))
    for nid in range(num_nodes):
        d = int(degrees[nid])
        if d >= hub_degree:
            # exact weighted sample WITHOUT replacement (Gumbel top-k /
            # Efraimidis-Spirakis race): perturb log-weights with Gumbel
            # noise, keep the d largest — every row lands exactly d
            # unique neighbors with the preferential distribution.
            # Uniforms clipped away from 0: log(0) would emit a
            # divide-by-zero warning (the -inf key itself is harmless)
            u = np.maximum(rng.random(num_nodes), np.finfo(np.float64).tiny)
            g = log_w - np.log(-np.log(u))
            nbrs = np.argpartition(g, num_nodes - d)[num_nodes - d:]
        else:
            # unique-fill: redraw the duplicate shortfall (bounded
            # rounds; each round oversamples 25% for collisions)
            nbrs = np.unique(np.searchsorted(cum, rng.random(d)))
            for _ in range(8):
                short = d - nbrs.size
                if short <= 0:
                    break
                extra = np.searchsorted(
                    cum, rng.random(short + short // 4 + 4)
                )
                nbrs = np.union1d(nbrs, extra)
            if nbrs.size > d:
                # union1d sorts; a [:d] trim would keep only LOW ids —
                # drop the overshoot uniformly instead
                nbrs = rng.choice(nbrs, size=d, replace=False)
        if multilabel:
            labels = rng.integers(0, 2, label_dim).astype(float)
        else:
            labels = np.zeros(label_dim)
            labels[rng.integers(0, label_dim)] = 1.0
        node = {
            "node_id": nid,
            "node_type": 0,
            "node_weight": 1.0,
            "neighbor": {"0": {str(int(t)): 1.0 for t in nbrs}},
            "uint64_feature": {},
            "float_feature": {
                "0": labels.tolist(),
                "1": rng.standard_normal(feature_dim).round(3).tolist(),
            },
            "binary_feature": {},
            "edge": [],
        }
        if buffered is not None:
            buffered.append(node)
        else:
            outs[nid % num_partitions].write(pack_block(node, meta))
        if progress_every and nid and nid % progress_every == 0:
            print(
                "build_powerlaw: %d/%d nodes" % (nid, num_nodes),
                flush=True,
            )
    for o in outs:
        o.close()
    if buffered is not None:
        from euler_tpu.graph.convert import convert_dicts

        convert_dicts(
            buffered, meta, os.path.join(out_dir, "part"),
            num_partitions=num_partitions, placement=placement,
        )
    _cache_finish(out_dir, params)
    return out_dir


# real Reddit's published scale: 232,965 nodes, ~114.6M directed edges
# (mean degree ~492) — the shape scripts/reddit_heavytail.py measures
REDDIT_HEAVYTAIL = dict(
    num_nodes=232965, num_edges=114_600_000, feature_dim=602,
    label_dim=41, alpha=1.8, multilabel=False,
)


def nearest_centroid_accuracy(info: dict, use_neighbors: bool) -> float:
    """Fraction of nodes whose (optionally neighborhood-averaged) feature
    vector is nearest to its own community centroid — the numeric
    separability bound the convergence tests gate against."""
    feats = info["features"]
    if use_neighbors:
        agg = np.stack(
            [
                (feats[nid] + info["features"][nbrs].sum(0))
                / (1 + len(nbrs))
                for nid, nbrs in enumerate(info["neighbors"])
            ]
        )
    else:
        agg = feats
    pred = np.argmax(agg @ info["centroids"].T, axis=1)
    return float(np.mean(pred == info["communities"]))


def build_ppi(out_dir: str, **overrides) -> str:
    return build_synthetic(out_dir, **{**PPI, **overrides})


def build_reddit(out_dir: str, **overrides) -> str:
    return build_synthetic(out_dir, **{**REDDIT, **overrides})


# ---------------------------------------------------------------------------
# Real-dataset preparation (the transform halves of the reference's
# examples/ppi_data.py:40-150 and reddit_data.py:42-58, minus the network
# download — zero egress here; point these at data already on disk).
# Both write meta.json + part_<p>.dat partitions + {train,val,test}.id
# files ready for `python -m euler_tpu.ppi_main / reddit_main`.
# ---------------------------------------------------------------------------


def _write_graph(out_dir, meta, nodes_iter, id_lists, num_partitions):
    from euler_tpu.graph.convert import convert_dicts

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f)
    convert_dicts(
        nodes_iter, meta, os.path.join(out_dir, "part"), num_partitions
    )
    # id files hold GRAPH node ids (what evaluate/save_embedding query) —
    # deliberate deviation from the reference, which writes the source
    # dataset's id_map values (ppi_data.py:150) and so can't evaluate the
    # graph it just built unless id_map is the identity.
    names = ["train.id", "val.id", "test.id"]
    for name, ids in zip(names, id_lists):
        with open(os.path.join(out_dir, name), "w") as f:
            f.writelines("%d\n" % i for i in ids)
    return out_dir


def prepare_ppi(prefix: str, out_dir: str, num_partitions: int = 1,
                normalize: bool = True) -> str:
    """GraphSAGE-format PPI on disk -> .dat partitions.

    ``prefix`` as in the GraphSAGE release: reads ``{prefix}-G.json``
    (node-link), ``{prefix}-feats.npy``, ``{prefix}-id_map.json``,
    ``{prefix}-class_map.json``. Mirrors reference
    examples/ppi_data.py:40-175: nodes lacking val/test annotations are
    dropped; node types are train=0/val=1/test=2; edges touching a
    val/test endpoint get type 1 ("train_removed"), others type 0;
    features are standardized by train-split statistics; float_feature
    slot 0 = the multilabel class vector, slot 1 = features.
    """
    with open(prefix + "-G.json") as f:
        g = json.load(f)
    feats = np.load(prefix + "-feats.npy").astype(np.float64)
    with open(prefix + "-id_map.json") as f:
        id_map = {int(k): int(v) for k, v in json.load(f).items()}
    with open(prefix + "-class_map.json") as f:
        class_map = {int(k): v for k, v in json.load(f).items()}

    node_ids = [n["id"] for n in g["nodes"]]
    attrs = {n["id"]: n for n in g["nodes"]}
    # node-link "links" reference positions in the "nodes" array
    # (networkx 1.x node_link_data, what the GraphSAGE release used)
    adj: dict[int, set] = {i: set() for i in node_ids}
    for link in g["links"]:
        s = node_ids[link["source"]]
        t = node_ids[link["target"]]
        adj[s].add(t)
        adj[t].add(s)

    kept = [i for i in node_ids if "val" in attrs[i] and "test" in attrs[i]]
    kept_set = set(kept)

    def ntype(i):
        return 1 if attrs[i]["val"] else (2 if attrs[i]["test"] else 0)

    if normalize:
        train_rows = np.array(
            [id_map[i] for i in kept if ntype(i) == 0], dtype=np.int64
        )
        mean = feats[train_rows].mean(axis=0)
        std = feats[train_rows].std(axis=0)
        std[std == 0] = 1.0
        feats = (feats - mean) / std

    meta = {
        "node_type_num": 3,
        "edge_type_num": 2,
        "node_uint64_feature_num": 0,
        "node_float_feature_num": 2,  # 0 labels, 1 features
        "node_binary_feature_num": 0,
        "edge_uint64_feature_num": 0,
        "edge_float_feature_num": 0,
        "edge_binary_feature_num": 0,
    }

    def etype(a, b):
        # "train_removed": either endpoint is outside the train split
        return 1 if (ntype(a) or ntype(b)) else 0

    def nodes_iter():
        for i in kept:
            nbrs = [n for n in adj[i] if n in kept_set]
            labels = class_map[i]
            labels = (
                [float(x) for x in labels]
                if isinstance(labels, list)
                else [float(labels)]
            )
            yield {
                "node_id": i,
                "node_type": ntype(i),
                "node_weight": 1,
                "neighbor": {
                    str(t): {
                        str(n): 1 for n in nbrs if etype(i, n) == t
                    }
                    for t in range(2)
                },
                "uint64_feature": {},
                "float_feature": {
                    "0": labels,
                    "1": feats[id_map[i]].tolist(),
                },
                "binary_feature": {},
                "edge": [
                    {
                        "src_id": i,
                        "dst_id": n,
                        "edge_type": etype(i, n),
                        "weight": 1,
                        "uint64_feature": {},
                        "float_feature": {},
                        "binary_feature": {},
                    }
                    for n in nbrs
                ],
            }

    ids = [[i for i in kept if ntype(i) == t] for t in range(3)]
    return _write_graph(out_dir, meta, nodes_iter(), ids, num_partitions)


def prepare_reddit(data_dir: str, out_dir: str,
                   num_partitions: int = 1) -> str:
    """DGL reddit npz files on disk -> .dat partitions.

    Reads ``{data_dir}/reddit_self_loop_graph.npz`` (scipy CSR adjacency)
    and ``{data_dir}/reddit_data.npz`` (feature / node_ids / label /
    node_types). Mirrors reference examples/reddit_data.py:42-135: node
    type = node_types - 1 (train=0/val=1/test=2), all edges type 0,
    float_feature slot 0 = one-hot(label, 41), slot 1 = features.
    """
    import scipy.sparse as sp

    graph = sp.load_npz(
        os.path.join(data_dir, "reddit_self_loop_graph.npz")
    ).tocsr()
    data = np.load(os.path.join(data_dir, "reddit_data.npz"))
    feats = data["feature"]
    id_map = data["node_ids"].astype(np.int64)
    labels = data["label"].astype(np.int64)
    node_types = data["node_types"].astype(np.int64)
    num_nodes = graph.shape[0]
    num_classes = int(labels.max()) + 1

    meta = {
        "node_type_num": 3,
        "edge_type_num": 1,
        "node_uint64_feature_num": 0,
        "node_float_feature_num": 2,  # 0 labels, 1 features
        "node_binary_feature_num": 0,
        "edge_uint64_feature_num": 0,
        "edge_float_feature_num": 0,
        "edge_binary_feature_num": 0,
    }

    def nodes_iter():
        indptr, indices = graph.indptr, graph.indices
        for i in range(num_nodes):
            nbrs = indices[indptr[i]:indptr[i + 1]]
            onehot = [0.0] * num_classes
            onehot[int(labels[i])] = 1.0
            yield {
                "node_id": i,
                "node_type": int(node_types[i]) - 1,
                "node_weight": 1,
                "neighbor": {"0": {str(int(n)): 1 for n in nbrs}},
                "uint64_feature": {},
                "float_feature": {
                    "0": onehot,
                    "1": feats[i].tolist(),
                },
                "binary_feature": {},
                "edge": [
                    {
                        "src_id": i,
                        "dst_id": int(n),
                        "edge_type": 0,
                        "weight": 1,
                        "uint64_feature": {},
                        "float_feature": {},
                        "binary_feature": {},
                    }
                    for n in nbrs
                ],
            }

    ids = [
        [i for i in range(num_nodes) if node_types[i] - 1 == t]
        for t in range(3)
    ]
    return _write_graph(out_dir, meta, nodes_iter(), ids, num_partitions)


def main() -> None:
    """CLI: synthetic builders + real-data preparation.

    python -m euler_tpu.datasets ppi|reddit --out DIR          (synthetic)
    python -m euler_tpu.datasets prepare_ppi --prefix P --out DIR
    python -m euler_tpu.datasets prepare_reddit --src DIR --out DIR
    """
    import argparse

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("cmd", choices=[
        "ppi", "reddit", "prepare_ppi", "prepare_reddit"])
    ap.add_argument("--out", required=True)
    ap.add_argument("--prefix", help="GraphSAGE file prefix (prepare_ppi)")
    ap.add_argument("--src", help="DGL npz directory (prepare_reddit)")
    ap.add_argument("--partitions", type=int, default=1)
    args = ap.parse_args()
    if args.cmd == "ppi":
        print(build_ppi(args.out, num_partitions=args.partitions))
    elif args.cmd == "reddit":
        print(build_reddit(args.out, num_partitions=args.partitions))
    elif args.cmd == "prepare_ppi":
        if not args.prefix:
            ap.error("prepare_ppi needs --prefix")
        print(prepare_ppi(args.prefix, args.out, args.partitions))
    else:
        if not args.src:
            ap.error("prepare_reddit needs --src")
        print(prepare_reddit(args.src, args.out, args.partitions))


if __name__ == "__main__":
    main()
