"""Device-sampling structural invariants on a RANDOM weighted graph.

tests/test_device_graph.py pins semantics on the 7-node hand-built
fixture; this module re-checks the slab build, the XLA draw path, and
the packed kernel layout at an irregular scale the fixture cannot
produce — poisson degrees, forced dead ends, zero-weight (unsampleable)
rows, a 150-degree hub that forces K=2 packing, and exponential edge
weights — against the host engine as ground truth. Everything here is
CPU-runnable (slab construction and packing are host numpy; the XLA
draw path runs on the virtual CPU mesh).
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

N = 300
AVG_DEG = 6
HUB = 5          # forced degree-150 node: slab wider than 1 register
DEAD_STRIDE = 17   # nid % 17 == 0 -> degree 0 (dead end)
ZEROW_STRIDE = 13  # nid % 13 == 0 (and not dead) -> all-zero weights

META = {
    "node_type_num": 2,
    "edge_type_num": 2,
    "node_uint64_feature_num": 0,
    "node_float_feature_num": 1,
    "node_binary_feature_num": 0,
    "edge_uint64_feature_num": 0,
    "edge_float_feature_num": 0,
    "edge_binary_feature_num": 0,
}


def _random_nodes(rng):
    nodes = []
    for nid in range(N):
        if nid % DEAD_STRIDE == 0:
            deg = 0
        elif nid == HUB:
            deg = 150
        else:
            deg = int(np.clip(rng.poisson(AVG_DEG), 1, 40))
        dsts = (
            rng.choice(N, size=deg, replace=False).astype(int)
            if deg else np.zeros(0, int)
        )
        if deg and nid % ZEROW_STRIDE == 0:
            ws = {int(d): 0.0 for d in dsts}
        else:
            ws = {
                int(d): float(rng.exponential() + 1e-3) for d in dsts
            }
        nodes.append({
            "node_id": nid,
            "node_type": nid % 2,
            "node_weight": float(rng.uniform(0.5, 2.0)),
            "neighbor": {
                "0": {str(d): w for d, w in ws.items()},
                "1": {},
            },
            "float_feature": {"0": [float(nid)]},
            "edge": [
                {
                    "src_id": nid, "dst_id": d, "edge_type": 0,
                    "weight": w,
                }
                for d, w in ws.items()
            ],
        })
    return nodes


@pytest.fixture(scope="module")
def graph(tmp_path_factory):
    import euler_tpu

    d = str(tmp_path_factory.mktemp("rand_graph"))
    euler_tpu.convert_dicts(
        _random_nodes(np.random.default_rng(11)), META,
        os.path.join(d, "part"), num_partitions=2,
    )
    return euler_tpu.Graph(directory=d)


@pytest.fixture(scope="module")
def adj(graph):
    from euler_tpu.graph import device

    return device.build_adjacency(graph, [0], N - 1)


def _host_rows(graph, ids):
    """{id: (nbr array, weight array)} over edge type 0 from the host
    engine (the ground truth the slabs must reproduce)."""
    nb, w, _, cnt = graph.get_full_neighbor(ids, [0])
    rows, off = {}, 0
    for i, c in zip(ids, cnt):
        c = int(c)
        rows[int(i)] = (nb[off:off + c], w[off:off + c])
        off += c
    return rows


def test_slab_rows_match_host_everywhere(graph, adj):
    ids = np.arange(N)
    rows = _host_rows(graph, ids)
    W = adj["nbr"].shape[1]
    assert W >= 150  # the hub widened the slab past one register
    default = adj["nbr"].shape[0] - 1  # max_id + 1, the padding node
    saw_unsampleable = 0
    for i in ids:
        nb, w = rows[int(i)]
        deg = int(adj["deg"][i])
        assert deg == min(len(nb), W)
        np.testing.assert_array_equal(adj["nbr"][i, :deg], nb[:deg])
        assert (adj["nbr"][i, deg:] == default).all()
        if len(nb) and w.sum() > 0:
            assert adj["sampleable"][i]
            exp = np.cumsum(w[:deg]) / w.sum()
            np.testing.assert_allclose(
                adj["cum"][i, :deg], np.minimum(exp, 1.0), atol=1e-5
            )
            assert adj["cum"][i, deg - 1] == 1.0
        elif len(nb):
            # zero-weight row: neighbors exist but sampling mass is zero
            assert not adj["sampleable"][i]
            saw_unsampleable += 1
    assert saw_unsampleable > 0  # the generator's ZEROW rows made it in


def test_dead_end_rows_draw_default(graph, adj):
    """Real degree-0 rows (nid % 17 == 0) and zero-weight rows must draw
    the default node through the XLA path."""
    from euler_tpu.graph import device

    deg = np.asarray(adj["deg"])[:N]
    ok = np.asarray(adj["sampleable"])[:N]
    targets = np.flatnonzero((deg == 0) | ~ok)
    assert len(targets) >= N // DEAD_STRIDE  # genuinely exercised
    default = adj["nbr"].shape[0] - 1
    out = np.asarray(
        device.sample_neighbor(
            {k: jax.numpy.asarray(v) for k, v in adj.items()},
            jax.numpy.asarray(targets[:64], jax.numpy.int32),
            jax.random.PRNGKey(0), 7,
        )
    )
    assert (out == default).all()


def test_draw_distribution_matches_weights(graph, adj):
    """Empirical XLA-path draw frequencies ≈ the host's NON-uniform
    normalized weights on random sampleable nodes + the hub (6-sigma
    bound, same discipline as the fixture tests)."""
    from euler_tpu.graph import device

    rng = np.random.default_rng(3)
    ok = np.flatnonzero(
        np.asarray(adj["sampleable"])[:N] & (np.asarray(adj["deg"])[:N] > 0)
    )
    picks = rng.choice(ok, size=min(10, len(ok)), replace=False)
    picks = np.unique(np.append(picks, HUB))
    rows = _host_rows(graph, picks)
    draws = 4000
    adj_j = {k: jax.numpy.asarray(v) for k, v in adj.items()}
    out = np.asarray(
        device.sample_neighbor(
            adj_j, jax.numpy.asarray(picks, jax.numpy.int32),
            jax.random.PRNGKey(5), draws,
        )
    )
    checked_nonuniform = False
    for r, i in enumerate(picks):
        nb, w = rows[int(i)]
        p = w / w.sum()
        if p.std() > 0.01:
            checked_nonuniform = True
        for n_, pi in zip(nb, p):
            freq = (out[r] == n_).mean()
            bound = 6 * np.sqrt(pi * (1 - pi) / draws) + 1e-3
            assert abs(freq - pi) < bound, (i, n_, freq, pi)
    assert checked_nonuniform  # exponential weights: not a uniform retest


def test_multi_hop_matches_host_on_random_graph(graph, adj):
    """The deterministic device full-neighbor expansion reproduces the
    host ops.get_multi_hop_neighbor exactly at irregular scale — same
    sorted unique node sets, same edge multisets — with dead ends and
    the 150-degree hub in play."""
    from euler_tpu import ops
    from euler_tpu.graph import device
    from tests.test_device_graph import _assert_hops_match_host

    roots = np.array([HUB, 1, 2, 35, 170], dtype=np.int64)
    # guard the tricky cases the roots claim to cover: a dead-end root
    # and the multi-register hub
    assert int(adj["deg"][170]) == 0 and 170 % DEAD_STRIDE == 0
    assert int(adj["deg"][HUB]) == 150
    caps = [256, 1024]
    h_roots, h_hops = ops.get_multi_hop_neighbor(
        graph, roots, [[0], [0]],
        max_nodes_per_hop=caps, max_edges_per_hop=[4096, 65536],
        default_node=N,
    )
    d_hops = device.multi_hop_neighbor([adj, adj], roots, caps)
    _assert_hops_match_host(h_hops, d_hops, roots)


def test_typed_negatives_distribution_at_scale(graph):
    """sample_node_with_src draws each source's negatives from ITS node
    type's weighted global sampler; at 300 nodes with non-uniform node
    weights the per-type marginals must match the host-side weights."""
    from euler_tpu.graph import device

    ts = device.build_typed_node_sampler(graph, 2, N - 1)
    src = np.asarray([4, 7], dtype=np.int64)  # one even-, one odd-type id
    types = np.asarray(ts["types"])[src]
    assert types[0] != types[1]
    draws = 30000
    out = np.asarray(
        device.sample_node_with_src(
            ts, jax.numpy.asarray(src, jax.numpy.int32),
            jax.random.PRNGKey(2), draws,
        )
    )
    ids_all = np.asarray(ts["ids"])
    cum_all = np.asarray(ts["cum"])
    off = np.asarray(ts["off"])
    for r in range(len(src)):
        t = int(types[r])
        seg = slice(int(off[t]), int(off[t + 1]))
        ids_t, cum_t = ids_all[seg], cum_all[seg]
        probs = np.diff(cum_t, prepend=0.0)
        # negatives stay within the source's type segment
        assert set(out[r].tolist()) <= set(ids_t.tolist())
        # spot-check the heaviest ten marginals
        top = np.argsort(probs)[::-1][:10]
        for j in top:
            freq = (out[r] == ids_t[j]).mean()
            bound = 6 * np.sqrt(probs[j] * (1 - probs[j]) / draws) + 1e-3
            assert abs(freq - probs[j]) < bound, (r, ids_t[j])


@pytest.mark.parametrize("pq", [(4.0, 0.25), (0.25, 4.0)])
def test_biased_walk_analytic_on_random_graph(graph, pq):
    """The node2vec-biased device walk reproduces the analytic
    d_tx-reweighted 2-step joint on the RANDOM graph — exercising the
    sorted-slab binary-search membership test at irregular degrees and
    non-uniform weights (the fixture version of this test covers only
    7 nodes)."""
    from euler_tpu.graph import device
    from tests.test_device_graph import _analytic_biased_joint

    p, q = pq
    adj = device.build_adjacency(graph, [0], N - 1, sorted=True)
    deg = np.asarray(adj["deg"])
    ok = np.asarray(adj["sampleable"])
    nbr = np.asarray(adj["nbr"])

    # the analytic model assumes every step-1 candidate has a live row:
    # pick a mid-degree root whose neighbors are all sampleable
    root = None
    for v in range(N):
        if not (ok[v] and 3 <= deg[v] <= 12):
            continue
        c1s = nbr[v][: deg[v]]
        if all(ok[c] and deg[c] > 0 for c in c1s):
            root = v
            break
    assert root is not None, "random graph lacks a clean root (reseed)"

    n = 40000
    walks = np.asarray(
        device.biased_random_walk(
            adj, np.full(n, root), jax.random.PRNGKey(9), 2, p, q
        )
    )
    assert (walks[:, 0] == root).all()
    expected = _analytic_biased_joint(adj, root, p, q)
    pairs, counts = np.unique(walks[:, 1:], axis=0, return_counts=True)
    seen = {
        (int(a), int(b)): c / n for (a, b), c in zip(pairs, counts)
    }
    assert set(seen) <= set(expected), set(seen) - set(expected)
    for pair, prob in expected.items():
        bound = 6 * np.sqrt(prob * (1 - prob) / n) + 1e-3
        assert abs(seen.get(pair, 0.0) - prob) < bound, (pair, prob)


def test_packed_layout_matches_slabs(adj):
    """pack_adjacency invariants at irregular degrees with K=2 (the hub
    forces a 2-register slab): real lanes mirror nbr/cum, unsampleable
    rows bake the default fill, pad lanes are (default id, cum 1.0)."""
    from euler_tpu.graph import pallas_sampling as ps

    packed = ps.pack_adjacency(adj)
    assert packed is not None
    n, w = adj["nbr"].shape
    k = packed.shape[0] // (2 * n)
    assert k == 2  # the hub pushed W past one 128-lane register
    blk = packed.reshape(n, 2 * k, ps.LANES)
    nbr_lanes = blk[:, :k].reshape(n, k * ps.LANES)
    cum_lanes = blk[:, k:].reshape(n, k * ps.LANES).view(np.float32)
    ok = np.asarray(adj["sampleable"]).astype(bool)
    assert not ok.all()  # unsampleable baking genuinely exercised
    exp_nbr = np.where(ok[:, None], adj["nbr"], n - 1)
    np.testing.assert_array_equal(nbr_lanes[:, :w], exp_nbr)
    np.testing.assert_array_equal(cum_lanes[:, :w], adj["cum"])
    assert (nbr_lanes[:, w:] == n - 1).all()
    assert (cum_lanes[:, w:] == 1.0).all()


def test_alias_root_sampler_distribution_at_scale(graph):
    """Random-graph analog of the fixture-level alias test: 300 nodes
    of non-uniform weight, so the alias table pairs most slots — the
    draw must reproduce every node's weight share."""
    from euler_tpu.graph import device

    s = device.build_node_sampler(graph, -1, N - 1)
    assert (s["prob"] < 1).sum() > 10
    assert (s["alias"] != np.arange(len(s["ids"]))).sum() > 10
    draws = np.asarray(
        device.sample_node(s, jax.random.PRNGKey(3), 60000)
    )
    ids = np.arange(N)
    w = graph.node_weights(ids)
    probs = w / w.sum()
    for i in ids[w > 0]:
        p = probs[i]
        assert (
            abs((draws == i).mean() - p)
            < 6 * np.sqrt(p * (1 - p) / 60000) + 1e-3
        ), i
