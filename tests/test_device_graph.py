"""Device-resident sampling must match the host engine's distributions.

The host engine's samplers are distribution-tested in
tests/test_graph_engine.py; these tests hold the HBM-side implementations
(euler_tpu/graph/device.py) to the same statistical standard on the same
fixture, plus structural checks (padding rows, truncation, fanout
chaining through dead ends).
"""

import numpy as np
import pytest

from euler_tpu.graph import device

MAX_ID = 16  # fixture ids go up to 16


@pytest.fixture(scope="module")
def adj01(graph):
    return device.build_adjacency(graph, [0, 1], MAX_ID)


def test_adjacency_shapes_and_padding(graph, adj01):
    n_rows = MAX_ID + 2
    assert adj01["nbr"].shape == adj01["cum"].shape
    assert adj01["nbr"].shape[0] == n_rows
    # default row (max_id + 1) must be a dead end pointing at itself
    assert (adj01["nbr"][MAX_ID + 1] == MAX_ID + 1).all()
    # cumulative rows end at exactly 1 so u<1 always lands in-row
    assert (adj01["cum"][:, -1] == 1.0).all()


def test_neighbor_sets_match_host(graph, adj01):
    """Every device-sampled neighbor is a true neighbor of its node."""
    import jax

    nodes = graph.sample_node(64, -1)
    out = np.asarray(
        device.sample_neighbor(
            adj01, nodes, jax.random.PRNGKey(0), 8
        )
    )
    for i, n in enumerate(nodes):
        nbr, _, _, _ = graph.get_full_neighbor([n], [0, 1])
        allowed = set(nbr.tolist()) or {MAX_ID + 1}
        assert set(out[i].tolist()) <= allowed, f"node {n}"


def test_neighbor_distribution_matches_weights(graph, adj01, nodes):
    """Empirical draw frequency tracks edge weights (CompactNode
    semantics) — same bar as the host engine's distribution test."""
    import jax

    node = 10  # fixture node with weighted neighbors
    nbr, w, _, _ = graph.get_full_neighbor([node], [0, 1])
    draws = np.asarray(
        device.sample_neighbor(
            adj01, np.full(200, node), jax.random.PRNGKey(1), 100
        )
    ).reshape(-1)
    freq = {int(i): float((draws == i).mean()) for i in nbr}
    probs = w / w.sum()
    for i, p in zip(nbr, probs):
        assert abs(freq[int(i)] - p) < 0.02, (i, freq[int(i)], p)


def test_node_sampler_distribution(graph):
    import jax

    sampler = device.build_node_sampler(graph, -1, MAX_ID)
    draws = np.asarray(
        device.sample_node(sampler, jax.random.PRNGKey(2), 20000)
    )
    ids = np.arange(MAX_ID + 1, dtype=np.int64)
    weights = graph.node_weights(ids)
    probs = weights / weights.sum()
    for i in ids[weights > 0]:
        assert abs((draws == i).mean() - probs[i]) < 0.02


def test_node_sampler_typed(graph):
    import jax

    sampler = device.build_node_sampler(graph, 1, MAX_ID)
    draws = np.asarray(
        device.sample_node(sampler, jax.random.PRNGKey(3), 2000)
    )
    types = graph.node_types(np.unique(draws))
    assert (types == 1).all()


def test_fanout_chains_through_dead_ends(graph, adj01):
    """A hop landing on the default node keeps yielding the default node,
    like the host sample_fanout's default_node fill."""
    import jax

    # build a sampler over type-0 edges only; fixture node 15's type-0
    # group may be empty -> default, and hop 2 from default stays default
    adj0 = device.build_adjacency(graph, [0], MAX_ID)
    hops = device.sample_fanout(
        [adj0, adj0], np.array([15]), jax.random.PRNGKey(4), [4, 2]
    )
    assert len(hops) == 3
    h1, h2 = np.asarray(hops[1]), np.asarray(hops[2]).reshape(4, 2)
    for i, n in enumerate(h1):
        if n == MAX_ID + 1:
            assert (h2[i] == MAX_ID + 1).all()


def test_metapath_walk_respects_step_types(graph):
    """A heterogeneous walk alternating type-0 and type-1 adjacencies must
    only traverse edges of the step's type (device analog of the host
    metapath random_walk)."""
    import jax

    adj0 = device.build_adjacency(graph, [0], MAX_ID)
    adj1 = device.build_adjacency(graph, [1], MAX_ID)
    roots = graph.sample_node(32, 0)
    paths = np.asarray(
        device.random_walk(
            [adj0, adj1], roots, jax.random.PRNGKey(0), 2
        )
    )
    default = MAX_ID + 1
    for row in paths:
        a, b, c = row
        if b != default:
            nbr, _, _, _ = graph.get_full_neighbor([a], [0])
            assert b in nbr
        if c != default:
            nbr, _, _, _ = graph.get_full_neighbor([b], [1])
            assert c in nbr


def test_typed_negatives_match_src_type(graph, meta):
    """Each source's negatives come from its OWN node type's weighted
    sampler (native sample_node_with_src semantics), with the right
    marginal distribution."""
    import jax

    ts = device.build_typed_node_sampler(
        graph, meta["node_type_num"], MAX_ID
    )
    src = graph.sample_node(64, -1)
    negs = np.asarray(
        device.sample_node_with_src(ts, src, jax.random.PRNGKey(0), 50)
    )
    src_types = graph.node_types(src)
    for i in range(len(src)):
        assert (graph.node_types(negs[i]) == src_types[i]).all()
    # distribution within one type follows node weights
    t0 = np.flatnonzero(src_types == 0)
    draws = negs[t0].reshape(-1)
    ids = np.arange(MAX_ID + 1)
    w = graph.node_weights(ids)
    w[graph.node_types(ids) != 0] = 0
    probs = w / w.sum()
    for i in ids[w > 0]:
        assert abs((draws == i).mean() - probs[i]) < 0.03


def test_alias_node_sampler_exact_on_fixture_weights(graph):
    """The fixture's node weights are not uniform, so the alias table
    pairs slots: some prob under 1, some alias other than itself — and
    the draw (integer slot, keep with prob else alias) must still
    reproduce the host sampling weights."""
    import jax

    sampler = device.build_node_sampler(graph, -1, MAX_ID)
    assert set(sampler) == {"ids", "prob", "alias"}
    slots = np.arange(len(sampler["ids"]))
    assert (sampler["prob"] < 1).any()
    assert (sampler["alias"] != slots).any()
    draws = np.asarray(
        device.sample_node(sampler, jax.random.PRNGKey(5), 20000)
    )
    ids = np.arange(MAX_ID + 1, dtype=np.int64)
    weights = graph.node_weights(ids)
    probs = weights / weights.sum()
    for i in ids[weights > 0]:
        assert abs((draws == i).mean() - probs[i]) < 0.02


def test_two_level_typed_negatives_multi_segment(graph, meta, monkeypatch):
    """Same segment-boundary coverage for the typed negative sampler:
    SEG=2 forces every type across multiple sub-segments, and each
    source must still draw its own type at the host weights."""
    import jax

    monkeypatch.setattr(device, "SEG", 2)
    ts = device.build_typed_node_sampler(graph, meta["node_type_num"], MAX_ID)
    assert ts["seg_cum"].shape[0] > ts["off"].shape[0] - 1
    # the 0.03 gate below is tight enough that the SRC draw must be
    # pinned: inheriting whatever thread-RNG state earlier tests left
    # behind made this pass or fail with suite composition
    from euler_tpu.graph.native import lib as native_lib

    native_lib().eg_seed(182)
    src = graph.sample_node(64, -1)
    negs = np.asarray(
        device.sample_node_with_src(ts, src, jax.random.PRNGKey(1), 64)
    )
    src_types = graph.node_types(src)
    for i in range(len(src)):
        assert (graph.node_types(negs[i]) == src_types[i]).all()
    for t in range(meta["node_type_num"]):
        rows = np.flatnonzero(src_types == t)
        if not len(rows):
            continue
        draws = negs[rows].reshape(-1)
        ids = np.arange(MAX_ID + 1)
        w = graph.node_weights(ids)
        w[graph.node_types(ids) != t] = 0
        probs = w / w.sum()
        for i in ids[w > 0]:
            assert abs((draws == i).mean() - probs[i]) < 0.03


class _ArrayGraph:
    """node_weights / node_types from two arrays: all build_node_sampler
    asks of a graph."""

    def __init__(self, weights, types=None):
        self.weights = np.asarray(weights, np.float32)
        self.types = (
            np.zeros(len(self.weights), np.int32)
            if types is None else np.asarray(types, np.int32)
        )

    def node_weights(self, ids):
        return self.weights[ids]

    def node_types(self, ids):
        return self.types[ids]


def test_alias_node_sampler_beyond_float32_cliff():
    """>2^24 comparably-weighted nodes — the regime where a FLAT float32
    cumulative provably collides (adjacent values equal, tail nodes
    silently unsampleable) and where floor(u * M) of a float32 uniform
    skips slots. The alias table has no cumulative (every prob is its
    own threshold) and the slot is an integer draw, so the tail region
    draws at its exact probability and every slot is reachable."""
    import jax

    m = (1 << 24) + (1 << 20)  # 17.8M equal-weight nodes
    tail = 1 << 20

    # the flat cumulative DOES collide at this size
    flat_tail = (
        (np.arange(m - tail, m, dtype=np.float64) + 1) / m
    ).astype(np.float32)
    assert (np.diff(flat_tail) == 0).any()

    sampler = device.build_node_sampler(
        _ArrayGraph(np.ones(m, np.float32)), -1, m - 1
    )
    # equal weights: nothing to pair, every slot keeps itself
    assert (sampler["prob"] == 1).all()
    assert sampler["alias"][-1] == m - 1 and sampler["ids"][-1] == m - 1
    draws = np.asarray(
        device.sample_node(sampler, jax.random.PRNGKey(7), 4096)
    )
    p_tail = tail / m
    got = (draws >= m - tail).mean()
    assert abs(got - p_tail) < 6 * np.sqrt(p_tail * (1 - p_tail) / 4096)
    # the very tail is reachable, not probability-0
    assert draws.max() >= m - tail
    # above 2^24 a float32 holds even integers only: a slot made from a
    # float32 uniform would never be odd there
    assert (draws[draws > 1 << 24] % 2 == 1).any()


def _skewed_weights(m=5000, seed=0):
    w = np.random.default_rng(seed).pareto(1.2, m).astype(np.float32)
    return w + np.float32(1e-3)


@pytest.mark.parametrize("case", [
    "table_is_exact", "zero_weight_and_other_types_left_out",
    "one_node", "deterministic_in_its_key",
])
def test_alias_node_sampler(case):
    import jax

    if case == "table_is_exact":
        # per node: its own slot's prob plus what the slots aliased to
        # it give away, over M, is w / total
        w = _skewed_weights()
        s = device.build_node_sampler(_ArrayGraph(w), -1, len(w) - 1)
        m = len(s["ids"])
        assert m == len(w) and (s["prob"] < 1).any()
        mass = s["prob"].astype(np.float64)
        np.add.at(mass, s["alias"], 1.0 - s["prob"].astype(np.float64))
        w64 = w.astype(np.float64)
        np.testing.assert_allclose(
            mass / m, w64 / w64.sum(), rtol=0, atol=1e-6
        )
        assert ((s["prob"] >= 0) & (s["prob"] <= 1)).all()
        assert ((s["alias"] >= 0) & (s["alias"] < m)).all()
    elif case == "zero_weight_and_other_types_left_out":
        w = _skewed_weights(400, seed=1)
        w[::7] = 0
        types = np.arange(400) % 3
        s = device.build_node_sampler(_ArrayGraph(w, types), 1, 399)
        want = np.flatnonzero((types == 1) & (w > 0))
        np.testing.assert_array_equal(s["ids"], want)
        assert ((s["alias"] >= 0) & (s["alias"] < len(want))).all()
        draws = np.asarray(
            device.sample_node(s, jax.random.PRNGKey(0), 20000)
        )
        assert np.isin(draws, want).all()
        with pytest.raises(ValueError, match="no nodes of type 5"):
            device.build_node_sampler(_ArrayGraph(w, types), 5, 399)
    elif case == "one_node":
        w = np.zeros(9, np.float32)
        w[6] = 2.5
        s = device.build_node_sampler(_ArrayGraph(w), -1, 8)
        assert s["ids"].tolist() == [6] and s["alias"].tolist() == [0]
        draws = np.asarray(
            device.sample_node(s, jax.random.PRNGKey(4), 64)
        )
        assert (draws == 6).all()
    else:
        w = _skewed_weights()
        s = device.build_node_sampler(_ArrayGraph(w), -1, len(w) - 1)
        a, b, c = (
            np.asarray(device.sample_node(s, jax.random.PRNGKey(k), 512))
            for k in (11, 11, 12)
        )
        np.testing.assert_array_equal(a, b)
        assert (a != c).any()
        assert a.dtype == np.int32 and a.shape == (512,)


def test_sample_node_is_three_gathers_and_no_loop():
    """A count, not a speed: the draw of one step's negatives at the
    walk cell's size lowers, for the chip, to at most three gathers
    (prob[i], alias[i], ids[pick]) and no loop — a search over a
    cumulative table (seventeen gathers and a `while` before the alias
    table) cannot come back unseen. Lowered for the TPU platform, which
    needs no chip: on a CPU jax rolls the threefry rounds of the random
    bits themselves into a `while`."""
    import re

    import jax
    import jax.numpy as jnp

    m = 1 << 20
    sampler = {
        "ids": jax.ShapeDtypeStruct((m,), jnp.int32),
        "prob": jax.ShapeDtypeStruct((m,), jnp.float32),
        "alias": jax.ShapeDtypeStruct((m,), jnp.int32),
    }
    text = (
        jax.jit(lambda s, k: device.sample_node(s, k, 76800))
        .trace(sampler, jax.random.PRNGKey(0))
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    assert 1 <= len(re.findall(r'"?stablehlo\.gather"?\(', text)) <= 3
    assert "stablehlo.while" not in text


def test_typed_negatives_clamp_out_of_range_types(graph):
    """Sources whose node type is outside the sampler's configured range
    clamp into it (like the TypedDense towers) — never the degenerate
    all-default-negatives path."""
    import jax

    ts = device.build_typed_node_sampler(graph, 1, MAX_ID)  # only type 0
    src = graph.sample_node(16, 1)  # type-1 sources
    negs = np.asarray(
        device.sample_node_with_src(ts, src, jax.random.PRNGKey(0), 8)
    )
    assert (negs != MAX_ID + 1).all()  # real nodes, not the default
    assert (graph.node_types(negs.reshape(-1)) == 0).all()


def test_device_sparse_tables_match_host_gather(graph):
    """consts['sparse'] rows gathered at gids must equal the host-side
    padded sparse gather for the same nodes."""
    from euler_tpu import ops
    from euler_tpu.models import SupervisedGraphSage
    from euler_tpu.models.base import gather_consts

    m = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1]], fanouts=[3],
        dim=16, feature_idx=0, feature_dim=2, max_id=MAX_ID,
        sparse_feature_idx=[0], sparse_feature_max_ids=[40],
        sparse_max_len=4, device_features=True,
    )
    consts = m.build_consts(graph)
    ids = np.arange(MAX_ID + 1, dtype=np.int64)
    host = ops.get_sparse_feature(graph, ids, [0], 4, default_values=[41])
    feats = gather_consts({"gids": ids.astype(np.int32)}, consts, 2)
    dev_ids, dev_mask = feats["sparse"][0]
    np.testing.assert_array_equal(np.asarray(dev_ids), host[0][0])
    np.testing.assert_array_equal(np.asarray(dev_mask), host[0][1])


def test_zero_weight_neighbors_exist_but_never_sample(tmp_path):
    """A node whose edges all weigh 0: the host engine returns the
    neighbors from GetFullNeighbor (they EXIST — the full-neighborhood
    GCN aggregates them) but can never sample them. The slab must encode
    both: nbr/deg keep the neighbors, sample_neighbor yields default."""
    import jax

    import euler_tpu
    from euler_tpu.graph.convert import convert_dicts

    meta = {
        "node_type_num": 1, "edge_type_num": 1,
        "node_uint64_feature_num": 0, "node_float_feature_num": 0,
        "node_binary_feature_num": 0, "edge_uint64_feature_num": 0,
        "edge_float_feature_num": 0, "edge_binary_feature_num": 0,
    }
    nodes = [
        {"node_id": 0, "node_type": 0, "node_weight": 1.0,
         "neighbor": {"0": {"1": 0.0, "2": 0.0}},  # all-zero weights
         "uint64_feature": {}, "float_feature": {}, "binary_feature": {},
         "edge": []},
        {"node_id": 1, "node_type": 0, "node_weight": 1.0,
         "neighbor": {"0": {"2": 1.0}}, "uint64_feature": {},
         "float_feature": {}, "binary_feature": {}, "edge": []},
        {"node_id": 2, "node_type": 0, "node_weight": 1.0,
         "neighbor": {"0": {}}, "uint64_feature": {},
         "float_feature": {}, "binary_feature": {}, "edge": []},
    ]
    convert_dicts(nodes, meta, str(tmp_path / "part"), 1)
    g = euler_tpu.Graph(directory=str(tmp_path))
    adj = device.build_adjacency(g, [0], 2)
    # existence: both zero-weight neighbors are in the slab
    assert adj["deg"][0] == 2
    assert set(adj["nbr"][0, :2].tolist()) == {1, 2}
    # sampling: node 0 yields only the default node (host semantics)
    out = np.asarray(
        device.sample_neighbor(
            adj, np.array([0, 1]), jax.random.PRNGKey(0), 16
        )
    )
    assert (out[0] == 3).all()   # default = max_id + 1
    assert (out[1] == 2).all()
    g.close()


def test_truncation_keeps_heaviest(graph):
    with pytest.warns(UserWarning, match="truncated"):
        adj = device.build_adjacency(graph, [0, 1], MAX_ID, max_degree=1)
    node = 10
    nbr, w, _, _ = graph.get_full_neighbor([node], [0, 1])
    heaviest = int(nbr[np.argmax(w)])
    assert adj["nbr"][node, 0] == heaviest


def test_supervised_sage_device_sampling_trains(graph):
    """device_sampling=True: batch is roots+seed only; fanout, feature
    gather, labels, loss all happen inside the jitted step (8-dev mesh
    via conftest)."""
    import jax

    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage

    m = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=16, feature_idx=0, feature_dim=2,
        max_id=MAX_ID, device_features=True, device_sampling=True,
    )
    batch = m.sample(graph, graph.sample_node(8, -1))
    assert set(batch) == {"roots", "seed"}
    state, hist = train_lib.train(
        m, graph, lambda s: graph.sample_node(8, -1),
        num_steps=8, learning_rate=0.01, optimizer="adam", log_every=4,
    )
    res = train_lib.evaluate(m, graph, [np.arange(16)], state)
    assert np.isfinite(res["loss"])


def test_chunked_train_runs_fully_on_device(graph):
    """train() on a device-sampled model: up to CHUNK_STEPS steps a
    dispatch, the fan-out drawn on the device; losses must be finite,
    the params must move, and there must be fewer dispatches than steps
    (a hook that takes the keywords is called once a dispatch)."""
    import jax

    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage

    m = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=16, feature_idx=0, feature_dim=2,
        max_id=MAX_ID, device_features=True, device_sampling=True,
    )
    opt = train_lib.get_optimizer("adam", 0.01)
    state = m.init_state(
        jax.random.PRNGKey(0), graph, graph.sample_node(8, -1), opt
    )
    p0 = np.asarray(
        jax.tree_util.tree_leaves(state["params"])[0]
    ).copy()
    losses = []
    state, _ = train_lib.train(
        m, graph, lambda s: graph.sample_node(8, -1), 10,
        learning_rate=0.01, optimizer="adam", log_every=10, state=state,
        step_hook=lambda step, state=None, batch=None, loss=None:
            losses.append(float(loss)),
    )
    # the hook's three single first steps, then one chunk of seven
    assert len(losses) == 4, losses
    assert np.isfinite(losses).all()
    p1 = np.asarray(jax.tree_util.tree_leaves(state["params"])[0])
    assert not np.allclose(p0, p1)  # training actually moved the params


def test_device_sampling_model_parallel_mesh(graph):
    """The sampler consts must survive a (data x model) mesh: adjacency
    arrays replicate (never padded/row-sharded), tables shard —
    regression for the searchsorted-corruption hazard."""
    import jax

    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage
    from euler_tpu.parallel import (
        make_mesh, pad_tables_for_mesh, state_sharding,
    )

    m = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=16, feature_idx=0, feature_dim=2,
        max_id=MAX_ID, device_features=True, device_sampling=True,
    )
    mesh = make_mesh(8, model_parallel=2)
    opt = train_lib.get_optimizer("adam", 0.01)
    state = m.init_state(
        jax.random.PRNGKey(0), graph, graph.sample_node(8, -1), opt
    )
    adj_rows = {k: a["cum"].shape[0]
                for k, a in state["consts"]["adj"].items()}
    state = pad_tables_for_mesh(state, mesh)
    # sampler arrays unpadded, feature table padded to the model axis
    assert {k: a["cum"].shape[0]
            for k, a in state["consts"]["adj"].items()} == adj_rows
    assert state["consts"]["features"].shape[0] % 2 == 0
    shardings = state_sharding(mesh, state)
    state = jax.device_put(state, shardings)
    step = jax.jit(
        m.make_train_step(opt),
        in_shardings=(shardings, None),
        out_shardings=(shardings, None, None),
    )
    batch = m.sample(graph, graph.sample_node(8, -1))
    state, loss, metric = step(state, batch)
    assert np.isfinite(float(loss))


def test_unsup_negs_sampler_survives_model_parallel(graph):
    """consts['negs'] (the unsupervised negative sampler) must replicate
    unpadded under model parallelism: a padded alias table would draw
    its padding slots, whose prob is 0 and whose alias is slot 0."""
    import jax

    from euler_tpu import train as train_lib
    from euler_tpu import models
    from euler_tpu.parallel import (
        make_mesh, pad_tables_for_mesh, state_sharding,
    )

    m = models.GraphSage(
        node_type=-1, edge_type=[0, 1], max_id=MAX_ID,
        metapath=[[0, 1]], fanouts=[3], dim=16, num_negs=3,
        feature_idx=0, feature_dim=2,
        device_features=True, device_sampling=True,
    )
    mesh = make_mesh(8, model_parallel=2)
    opt = train_lib.get_optimizer("adam", 0.01)
    state = m.init_state(
        jax.random.PRNGKey(0), graph, graph.sample_node(8, -1), opt
    )
    before = {k: np.asarray(v) for k, v in state["consts"]["negs"].items()}
    state = pad_tables_for_mesh(state, mesh)
    for k in ("ids", "prob", "alias"):
        np.testing.assert_array_equal(
            np.asarray(state["consts"]["negs"][k]), before[k]
        )
    shardings = state_sharding(mesh, state)
    state = jax.device_put(state, shardings)
    step = jax.jit(
        m.make_train_step(opt),
        in_shardings=(shardings, None),
        out_shardings=(shardings, None, None),
    )
    state, loss, _ = step(state, m.sample(graph, graph.sample_node(8, -1)))
    assert np.isfinite(float(loss))


def test_device_sampling_with_use_id(graph):
    """use_id composes with device_sampling (the gids double as embedding
    ids); sparse features are rejected up front."""
    import jax

    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage

    m = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1]], fanouts=[3],
        dim=16, feature_idx=0, feature_dim=2, max_id=MAX_ID, use_id=True,
        device_features=True, device_sampling=True,
    )
    opt = train_lib.get_optimizer("adam", 0.01)
    state = m.init_state(
        jax.random.PRNGKey(0), graph, graph.sample_node(8, -1), opt
    )
    step = jax.jit(m.make_train_step(opt), donate_argnums=(0,))
    state, loss, _ = step(state, m.sample(graph, graph.sample_node(8, -1)))
    assert np.isfinite(float(loss))

    # sparse features ride device-resident padded tables (consts["sparse"])
    m2 = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1]], fanouts=[3],
        dim=16, feature_idx=0, feature_dim=2, max_id=MAX_ID,
        sparse_feature_idx=[0], sparse_feature_max_ids=[40],
        device_features=True, device_sampling=True,
    )
    state = m2.init_state(
        jax.random.PRNGKey(0), graph, graph.sample_node(8, -1), opt
    )
    assert "sparse" in state["consts"]
    step = jax.jit(m2.make_train_step(opt), donate_argnums=(0,))
    state, loss, _ = step(
        state, m2.sample(graph, graph.sample_node(8, -1))
    )
    assert np.isfinite(float(loss))


@pytest.mark.parametrize(
    "family",
    ["unsup_sage", "gat", "scalable_sage", "scalable_gcn", "line",
     "node2vec", "lshne"],
)
def test_device_sampling_model_families(graph, family):
    """device_sampling generalizes across families: unsupervised GraphSAGE
    (device positives + typed negatives), GAT (device attention
    neighborhood), ScalableSage (device 1-hop + store scatter), LINE
    (device positives), Node2Vec (device walks -> skip-gram pairs). Each
    trains through train(), up to CHUNK_STEPS steps a dispatch."""
    from euler_tpu import train as train_lib
    from euler_tpu import models

    if family == "unsup_sage":
        m = models.GraphSage(
            node_type=-1, edge_type=[0, 1], max_id=MAX_ID,
            metapath=[[0, 1], [0, 1]], fanouts=[3, 2], dim=16,
            num_negs=3, feature_idx=0, feature_dim=2,
            device_features=True, device_sampling=True,
        )
    elif family == "gat":
        m = models.GAT(
            label_idx=2, label_dim=3, feature_idx=0, feature_dim=2,
            max_id=MAX_ID, head_num=2, hidden_dim=16, nb_num=4,
            edge_type=[0, 1],
            device_features=True, device_sampling=True,
        )
    elif family == "line":
        m = models.LINE(
            node_type=-1, edge_type=[0, 1], max_id=MAX_ID, dim=16,
            order=2, num_negs=3, device_sampling=True,
        )
    elif family == "node2vec":
        m = models.Node2Vec(
            node_type=-1, edge_type=[0, 1], max_id=MAX_ID, dim=16,
            walk_len=3, left_win_size=1, right_win_size=1, num_negs=3,
            device_sampling=True,
        )
    elif family == "lshne":
        m = models.LsHNE(
            node_type=-1,
            path_patterns=[
                [[[0], [1], [0]]],
                [[[0, 1], [0, 1], [0, 1]]],
            ],
            max_id=MAX_ID, dim=8, sparse_feature_dims=[32, 32],
            feature_ids=[0, 1], num_negs=4, src_type_num=2,
            device_sampling=True,
        )
    elif family == "scalable_gcn":
        m = models.ScalableGCN(
            label_idx=2, label_dim=3, edge_type=[0, 1], num_layers=2,
            dim=16, max_id=MAX_ID, max_neighbors=6, feature_idx=0,
            feature_dim=2, device_features=True, device_sampling=True,
        )
    else:
        m = models.ScalableSage(
            label_idx=2, label_dim=3, edge_type=[0, 1], fanout=3,
            num_layers=2, dim=16, max_id=MAX_ID, feature_idx=0,
            feature_dim=2, device_features=True, device_sampling=True,
        )
    batch = m.sample(graph, graph.sample_node(8, -1))
    assert set(batch) == {"roots", "seed"}
    state, _ = train_lib.train(
        m, graph, lambda s: graph.sample_node(8, -1),
        num_steps=6, learning_rate=0.01, optimizer="adam", log_every=3,
    )
    res = train_lib.evaluate(m, graph, [np.arange(16)], state)
    assert np.isfinite(res["loss"])


def _analytic_biased_joint(adj, root, p, q):
    """Exact P(c1, c2) for a 2-step node2vec walk from `root`, computed
    with numpy from the slab arrays: step 1 plain weighted, step 2
    reweighted by d_tx w.r.t. parent=root (1 shared neighbor — winning
    over 1/p on a root self-loop, the reference merge's branch order;
    1/p return; 1/q otherwise) — reference graph.cc:120-151 semantics."""
    nbr, cum, deg = (
        np.asarray(adj["nbr"]), np.asarray(adj["cum"]),
        np.asarray(adj["deg"]),
    )

    def row_probs(v):
        d = deg[v]
        w = np.diff(cum[v][:d], prepend=0.0)
        return nbr[v][:d], w / w.sum()

    joint = {}
    c1s, p1s = row_probs(root)
    root_nbrs = set(nbr[root][: deg[root]].tolist())
    for c1, p1 in zip(c1s, p1s):
        cands, w2 = row_probs(int(c1))
        scale = np.array(
            [
                1.0 if c in root_nbrs
                else (1.0 / p if c == root else 1.0 / q)
                for c in cands
            ]
        )
        w2 = w2 * scale
        w2 = w2 / w2.sum()
        for c2, pr in zip(cands, w2):
            joint[(int(c1), int(c2))] = (
                joint.get((int(c1), int(c2)), 0.0) + p1 * pr
            )
    return joint


@pytest.mark.parametrize("pq", [(4.0, 0.25), (0.25, 4.0)])
def test_biased_walk_matches_analytic_distribution(graph, pq):
    """The device node2vec-biased walk must reproduce the d_tx-reweighted
    distribution exactly (same bar as the host engine's biased-walk
    distribution test): empirical 2-step joint vs the analytic joint
    computed from the same slab."""
    import jax

    p, q = pq
    adj = device.build_adjacency(graph, [0, 1], MAX_ID, sorted=True)
    root = 10
    n = 40000
    walks = np.asarray(
        device.biased_random_walk(
            adj, np.full(n, root), jax.random.PRNGKey(5), 2, p, q
        )
    )
    assert (walks[:, 0] == root).all()
    expected = _analytic_biased_joint(adj, root, p, q)
    pairs, counts = np.unique(walks[:, 1:], axis=0, return_counts=True)
    seen = {
        (int(a), int(b)): c / n for (a, b), c in zip(pairs, counts)
    }
    # every observed pair is a legal transition, and frequencies match
    assert set(seen) <= set(expected), set(seen) - set(expected)
    for pair, prob in expected.items():
        assert abs(seen.get(pair, 0.0) - prob) < 0.02, (pair, prob, seen)


def test_biased_walk_rows_must_be_sorted(graph):
    """Unsorted slabs give wrong membership tests; the sorted builder is
    what makes them searchable. Sanity: the sorted variant's real slots
    are ascending per row."""
    adj = device.build_adjacency(graph, [0, 1], MAX_ID, sorted=True)
    nbr, deg = np.asarray(adj["nbr"]), np.asarray(adj["deg"])
    for v in range(nbr.shape[0]):
        row = nbr[v][: deg[v]]
        assert (np.diff(row) >= 0).all(), (v, row)


def test_node2vec_biased_device_sampling_trains(graph):
    """Node2Vec with p/q != 1 runs the biased walk on device end-to-end
    (this configuration raised before)."""
    from euler_tpu import models
    from euler_tpu import train as train_lib

    m = models.Node2Vec(
        node_type=-1, edge_type=[0, 1], max_id=MAX_ID, dim=16,
        walk_len=3, walk_p=4.0, walk_q=0.25, left_win_size=1,
        right_win_size=1, num_negs=3, device_sampling=True,
    )
    batch = m.sample(graph, graph.sample_node(8, -1))
    assert set(batch) == {"roots", "seed"}
    state, hist = train_lib.train(
        m, graph, lambda s: graph.sample_node(8, -1),
        num_steps=6, learning_rate=0.01, log_every=3,
    )
    assert np.isfinite(hist[-1]["loss"])


def _assert_hops_match_host(h_hops, d_hops, roots):
    """Hop-by-hop equality of the device multi_hop_neighbor COO against
    the host expansion: same sorted unique node sets, same (src node id,
    dst node id) edge MULTIsets (multiplicity included). Shared with the
    random-graph suite (tests/test_device_graph_random.py)."""
    cur_ids = roots
    for h, (hh, dh) in enumerate(zip(h_hops, d_hops)):
        assert np.array_equal(
            np.asarray(dh["nodes"]), hh.nodes.astype(np.int32)
        ), f"hop {h} node sets differ"
        h_mask = hh.adj["mask"] > 0
        h_edges = sorted(
            zip(
                cur_ids[hh.adj_src[h_mask]].tolist(),
                hh.nodes[hh.adj_dst[h_mask]].tolist(),
            )
        )
        d_mask = np.asarray(dh["mask"]) > 0
        d_src = np.asarray(cur_ids)[np.asarray(dh["src"])[d_mask]]
        d_dst = np.asarray(dh["nodes"])[np.asarray(dh["dst"])[d_mask]]
        assert sorted(zip(d_src.tolist(), d_dst.tolist())) == h_edges, (
            f"hop {h} edge multisets differ"
        )
        cur_ids = hh.nodes


def test_multi_hop_neighbor_matches_host_exactly(graph, adj01):
    """The device full-neighbor expansion is deterministic, so it must
    reproduce the host ops.get_multi_hop_neighbor exactly: same sorted
    unique node sets, same (src_id, dst_id) edge sets."""
    from euler_tpu import ops

    roots = np.array([10, 11, 16], dtype=np.int64)
    caps = [8, 12]
    h_roots, h_hops = ops.get_multi_hop_neighbor(
        graph, roots, [[0, 1], [0, 1]],
        max_nodes_per_hop=caps, max_edges_per_hop=[64, 256],
        default_node=MAX_ID + 1,
    )
    d_hops = device.multi_hop_neighbor([adj01, adj01], roots, caps)
    _assert_hops_match_host(h_hops, d_hops, roots)
    # dedup overflow: cap smaller than the unique count drops the
    # largest-id nodes instead of raising
    tight = device.multi_hop_neighbor([adj01], roots, [2])
    kept = np.asarray(tight[0]["nodes"])
    full = np.unique(
        np.asarray(h_hops[0].nodes[: h_hops[0].num_nodes])
    )
    assert np.array_equal(kept, np.sort(full)[:2].astype(np.int32))


@pytest.mark.parametrize("caps", [[15, 75], [15, 40], [8, 12], [2, 3]],
                         ids=["caps_hold", "hop2_binds", "both_bind",
                              "tight"])
def test_multi_hop_neighbor_slot_ids_mask_and_overflow(graph, adj01, caps):
    """Each hop's ``ids`` are the slots' own neighbour ids, ``nodes[dst]``
    on every slot where the cap holds (3 roots x 5 slots: hop 1 holds at
    15, hop 2 at 75) and on every unmasked slot where it binds; the mask
    is the ranked form ``valid & (rank < cap) & (id != default)`` whether
    or not the step computes a rank for it, and ``overflow`` the unique
    real ids past the cap. Computed anew here with ``np.unique``."""
    roots = np.array([10, 11, 16], dtype=np.int64)
    nbr_all, deg_all = np.asarray(adj01["nbr"]), np.asarray(adj01["deg"])
    default = nbr_all.shape[0] - 1
    hops = device.multi_hop_neighbor([adj01, adj01], roots, caps)
    cur = roots
    for h, cap in zip(hops, caps):
        nbr = nbr_all[cur]
        valid = np.arange(nbr.shape[1])[None, :] < deg_all[cur][:, None]
        flat = np.where(valid, nbr, default).reshape(-1)
        uniq, rank = np.unique(flat, return_inverse=True)
        holds = cap >= flat.shape[0]
        ids = np.asarray(h["ids"])
        nodes, dst = np.asarray(h["nodes"]), np.asarray(h["dst"])
        mask = np.asarray(h["mask"]) > 0
        assert ids.dtype == np.int32 and np.array_equal(ids, flat)
        assert np.array_equal(
            mask, valid.reshape(-1) & (rank < cap) & (flat != default))
        on = slice(None) if holds else mask
        assert np.array_equal(ids[on], nodes[dst][on])
        assert int(h["overflow"]) == max(int((uniq != default).sum()) - cap,
                                         0)
        assert float(h["edges"]) == mask.sum()
        cur = nodes


def test_supervised_gcn_device_matches_host_loss(graph):
    """Same params, same roots: the device-expanded SupervisedGCN step
    must produce the host path's loss (full-neighbor GCN has no sampling
    randomness)."""
    import jax

    from euler_tpu import models

    kw = dict(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]], dim=8,
        max_nodes_per_hop=[8, 12], max_edges_per_hop=[64, 256],
        feature_idx=0, feature_dim=2, max_id=MAX_ID,
    )
    mh = models.SupervisedGCN(**kw)
    md = models.SupervisedGCN(
        **kw, device_features=True, device_sampling=True
    )
    roots = np.array([10, 11, 16], dtype=np.int64)

    state_h = mh.init_state(
        jax.random.PRNGKey(0), graph, roots,
        __import__("optax").adam(0.01),
    )
    state_d = md.init_state(
        jax.random.PRNGKey(0), graph, roots,
        __import__("optax").adam(0.01),
    )
    # same module structure -> transplant host params into the device run
    out_h = mh.module.apply(
        {"params": state_h["params"]}, mh.sample(graph, roots)
    )
    out_d = md.module.apply(
        {"params": state_h["params"]},
        md.sample(graph, roots),
        state_d["consts"],
    )
    np.testing.assert_allclose(
        float(out_h.loss), float(out_d.loss), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(out_h.embedding), np.asarray(out_d.embedding),
        rtol=1e-4, atol=1e-5,
    )


def test_lasgnn_device_sampling_trains(graph):
    """LasGNN's structured batch (label + node-id groups) also runs the
    device path: host ships only labels/ids/seed, the per-group
    heterogeneous metapath fanouts and sparse-feature gathers happen
    inside the jitted step."""
    from euler_tpu import models
    from euler_tpu import train as train_lib

    m = models.LasGNN(
        metapaths_of_groups=[
            [[[0], [0, 1]]],
            [[[0], [0, 1]], [[1], [0, 1]]],
        ],
        fanouts=[2, 2],
        dim=8,
        feature_ixs=[0, 1],
        feature_dims=[32, 32],
        group_sizes=[1, 2],
        max_id=MAX_ID,
        device_sampling=True,
    )
    rng = np.random.default_rng(0)

    def source_fn(step):
        ids = graph.sample_node(8, -1)
        ctx = graph.sample_node(16, -1).reshape(8, 2)
        return {
            "label": rng.integers(0, 2, (8, 1)).astype(np.float32),
            "groups": [ids.reshape(8, 1), ctx],
        }

    batch = m.sample(graph, source_fn(0))
    assert set(batch) == {"label", "group0", "group1", "seed"}
    assert batch["group1"].dtype == np.int32

    state, hist = train_lib.train(
        m, graph, source_fn, num_steps=6, learning_rate=0.01,
        log_every=3,
    )
    assert np.isfinite(hist[-1]["loss"])
    assert 0.0 <= hist[-1]["auc"] <= 1.0
    emb = train_lib.save_embedding(m, graph, MAX_ID, state, batch_size=8)
    assert emb.shape == (MAX_ID + 1, 8)
    assert np.isfinite(emb).all()


def test_remote_graph_export_matches_local(graph, tmp_path):
    """Device-graph export composes with remote mode (round 3): the
    samplers ride the kNodeWeight/kNodeType RPCs and the adjacency rides
    get_full_neighbor, so a sharded service exports byte-identical slabs
    to the embedded engine's."""
    from euler_tpu.graph.service import GraphService
    import euler_tpu

    from tests.fixture_graph import write_fixture

    d = str(tmp_path / "g")
    import os

    os.makedirs(d)
    write_fixture(d, num_partitions=2)
    with GraphService(d, 0, 2) as s0, GraphService(d, 1, 2) as s1:
        remote = euler_tpu.Graph(
            mode="remote", shards=[s0.address, s1.address]
        )
        for nt in (-1, 0, 1):
            rs = device.build_node_sampler(remote, nt, MAX_ID)
            ls = device.build_node_sampler(graph, nt, MAX_ID)
            np.testing.assert_array_equal(rs["ids"], ls["ids"])
            np.testing.assert_allclose(rs["prob"], ls["prob"], rtol=1e-6)
            np.testing.assert_array_equal(rs["alias"], ls["alias"])
        rt = device.build_typed_node_sampler(remote, 2, MAX_ID)
        lt = device.build_typed_node_sampler(graph, 2, MAX_ID)
        for k in ("ids", "off", "types"):
            np.testing.assert_array_equal(rt[k], lt[k])
        np.testing.assert_allclose(rt["cum"], lt["cum"], rtol=1e-6)
        ra = device.build_adjacency(remote, [0, 1], MAX_ID)
        la = device.build_adjacency(graph, [0, 1], MAX_ID)
        for k in ("nbr", "deg", "sampleable"):
            np.testing.assert_array_equal(ra[k], la[k])
        np.testing.assert_allclose(ra["cum"], la["cum"], rtol=1e-6)
        # the exact alias form (incl. the id-sorted rows the rejection
        # walk bisects) exports identically through the sharded client
        raa = device.build_alias_adjacency(remote, [0, 1], MAX_ID,
                                           sorted=True)
        laa = device.build_alias_adjacency(graph, [0, 1], MAX_ID,
                                           sorted=True)
        for k in ("off", "deg", "nbr", "alias", "sampleable"):
            np.testing.assert_array_equal(raa[k], laa[k])
        np.testing.assert_allclose(raa["prob"], laa["prob"], rtol=1e-6)
        remote.close()
