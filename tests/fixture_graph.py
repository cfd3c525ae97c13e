"""Tiny deterministic heterogeneous fixture graph shared by all tests.

Same role as the reference's 6-node test graph
(reference tf_euler/python/euler_ops/testdata/graph.json + base_test.py:36-53):
every op test loads this via the converter + native engine.

7 nodes (ids 10..16), 2 node types, 2 edge types, dense/sparse/binary
features on nodes and edges.
"""

import os

import numpy as np

import euler_tpu

FIXTURE_META = {
    "node_type_num": 2,
    "edge_type_num": 2,
    "node_uint64_feature_num": 2,
    "node_float_feature_num": 3,
    "node_binary_feature_num": 1,
    "edge_uint64_feature_num": 1,
    "edge_float_feature_num": 1,
    "edge_binary_feature_num": 1,
}

# node id -> (type, weight, {edge_type: {dst: weight}})
TOPOLOGY = {
    10: (0, 1.0, {0: {11: 1.0, 12: 3.0}, 1: {13: 2.0}}),
    11: (1, 2.0, {0: {12: 2.0}}),
    12: (0, 3.0, {1: {13: 1.0, 14: 4.0}}),
    13: (1, 4.0, {0: {10: 1.0}}),
    14: (0, 5.0, {0: {15: 2.0}, 1: {11: 1.0}}),
    15: (1, 6.0, {}),
    16: (0, 1.0, {0: {10: 2.0, 11: 1.0, 12: 1.0}, 1: {13: 1.0, 15: 2.0}}),
}


def dense_f0(nid):
    return [nid * 0.5, nid * 0.25]


def fixture_nodes():
    nodes = []
    for nid, (ntype, w, nbrs) in TOPOLOGY.items():
        edges = []
        for t, group in nbrs.items():
            for dst, ew in group.items():
                edges.append(
                    {
                        "src_id": nid,
                        "dst_id": dst,
                        "edge_type": t,
                        "weight": ew,
                        "uint64_feature": {"0": [nid * 100 + dst]},
                        "float_feature": {"0": [ew * 0.1]},
                        "binary_feature": {"0": "e%d-%d" % (nid, dst)},
                    }
                )
        nodes.append(
            {
                "node_id": nid,
                "node_type": ntype,
                "node_weight": w,
                "neighbor": {
                    str(t): {str(d): w2 for d, w2 in g.items()}
                    for t, g in nbrs.items()
                },
                "uint64_feature": {"0": [nid, nid + 1], "1": [7]},
                "float_feature": {
                    "0": dense_f0(nid),
                    "1": [1.0, 2.0, 3.0],
                    # slot 2: a 3-class multi-hot label (nid mod 3 one-hot,
                    # plus class 2 for even ids) for supervised-model tests
                    "2": [
                        1.0 if nid % 3 == 0 else 0.0,
                        1.0 if nid % 3 == 1 else 0.0,
                        1.0 if nid % 2 == 0 else 0.0,
                    ],
                },
                "binary_feature": {"0": "n%d" % nid},
                "edge": edges,
            }
        )
    return nodes


def write_fixture(directory, num_partitions=2):
    return euler_tpu.convert_dicts(
        fixture_nodes(),
        FIXTURE_META,
        os.path.join(directory, "part"),
        num_partitions=num_partitions,
    )


# ---- the hub-heavy fixture of the remote smokes and the locality tests ----

PL_NUM_PARTITIONS = 4

PL_META = {
    "node_type_num": 2,
    "edge_type_num": 2,
    "node_uint64_feature_num": 1,
    "node_float_feature_num": 1,
    "node_binary_feature_num": 0,
    "edge_uint64_feature_num": 0,
    "edge_float_feature_num": 0,
    "edge_binary_feature_num": 0,
}


def powerlaw_fixture_nodes(num_nodes: int, avg_degree: int,
                           feature_dim: int, alpha: float = 1.1,
                           seed: int = 7) -> list:
    """Node dicts of the hub-heavy synthetic graph: zipf(alpha)-ranked
    destination draws, so the first few ids soak up most edge mass (the
    Reddit heavy tail at smoke size). Split from the .dat writer so the
    locality A/B (scripts/heat_dump.py --ab-smoke) can partition ONE
    node set two ways."""
    rng = np.random.default_rng(seed)
    # zipf-ish rank weights over destinations
    ranks = np.arange(1, num_nodes + 1, dtype=np.float64)
    probs = ranks ** (-alpha)
    probs /= probs.sum()
    nodes = []
    for nid in range(num_nodes):
        deg = max(1, int(rng.poisson(avg_degree)))
        dsts = rng.choice(num_nodes, size=deg, p=probs)
        groups: dict = {}
        for d in dsts:
            d = int(d)
            t = d % 2
            groups.setdefault(t, {})
            groups[t][d] = groups[t].get(d, 0.0) + 1.0
        nodes.append(
            {
                "node_id": nid,
                "node_type": nid % 2,
                "node_weight": 1.0,
                "neighbor": {
                    str(t): {str(d): w for d, w in g.items()}
                    for t, g in groups.items()
                },
                "uint64_feature": {"0": [nid]},
                "float_feature": {
                    "0": (np.arange(feature_dim) * 0.01 + nid * 0.001)
                    .astype(float).tolist()
                },
                "binary_feature": {},
                "edge": [
                    {
                        "src_id": nid, "dst_id": d, "edge_type": t,
                        "weight": w, "uint64_feature": {},
                        "float_feature": {}, "binary_feature": {},
                    }
                    for t, g in groups.items()
                    for d, w in g.items()
                ],
            }
        )
    return nodes


def build_powerlaw_fixture(directory: str, num_nodes: int, avg_degree: int,
                           feature_dim: int, alpha: float = 1.1,
                           seed: int = 7, placement: str = "hash") -> None:
    """Partition the hub-heavy fixture into PL_NUM_PARTITIONS .dat files
    (placement='degree' adds the converter's placement artifact)."""
    euler_tpu.convert_dicts(
        powerlaw_fixture_nodes(num_nodes, avg_degree, feature_dim, alpha,
                               seed),
        PL_META, os.path.join(directory, "part"),
        num_partitions=PL_NUM_PARTITIONS, placement=placement,
    )
