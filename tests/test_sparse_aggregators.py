"""Sparse (COO segment-op) aggregator + GCNEncoder path tests — exercises
the full-neighbor pipeline end to end: get_multi_hop_neighbor -> MultiHop.adj
-> GCNEncoder."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from euler_tpu import ops
from euler_tpu.nn import sparse_aggregators
from euler_tpu.nn.encoders import GCNEncoder


def _toy_adj():
    # 2 self nodes, 3 neighbor nodes; node 0 -> {0, 1}, node 1 -> {2};
    # one padding edge pointing at slot 0.
    return {
        "src": jnp.array([0, 0, 1, 0], dtype=jnp.int32),
        "dst": jnp.array([0, 1, 2, 0], dtype=jnp.int32),
        "w": jnp.array([1.0, 1.0, 1.0, 0.0]),
        "mask": jnp.array([1.0, 1.0, 1.0, 0.0]),
    }


def test_gcn_aggregator_mean_semantics():
    self_emb = jnp.array([[1.0, 0.0], [0.0, 1.0]])
    neigh_emb = jnp.array([[2.0, 0.0], [4.0, 0.0], [0.0, 6.0]])
    adj = _toy_adj()
    agg = sparse_aggregators.GCNAggregator(dim=2, activation=None)
    params = agg.init(jax.random.PRNGKey(0), (self_emb, neigh_emb, adj))
    # Pre-dense aggregation: node0 = self + mean(n0,n1) = [1,0]+[3,0];
    # node1 = [0,1]+[0,6]. Verify via identity-kernel application.
    params = jax.tree.map(
        lambda p: jnp.eye(2) if p.shape == (2, 2) else p, params
    )
    out = agg.apply(params, (self_emb, neigh_emb, adj))
    np.testing.assert_allclose(out, [[4.0, 0.0], [0.0, 7.0]], atol=1e-5)


def test_padding_edges_do_not_contribute():
    self_emb = jnp.ones((2, 4))
    neigh_emb = jnp.ones((3, 4)) * 100.0
    adj = _toy_adj()
    # zero out ALL real edges; only the padding edge remains
    adj = dict(adj, mask=jnp.array([0.0, 0.0, 0.0, 0.0]))
    agg = sparse_aggregators.MeanAggregator(dim=4, activation=None)
    params = agg.init(jax.random.PRNGKey(0), (self_emb, neigh_emb, adj))
    out = agg.apply(params, (self_emb, neigh_emb, adj))
    # with no real edges the neighbor term must be exactly zero, so the
    # output equals the self projection alone
    self_only = agg.apply(
        params, (self_emb, jnp.zeros_like(neigh_emb), adj)
    )
    np.testing.assert_allclose(out, self_only, atol=1e-6)


def test_segment_softmax_masks_padding():
    logits = jnp.array([1.0, 2.0, 3.0, 100.0])
    seg = jnp.array([0, 0, 1, 1])
    mask = jnp.array([1.0, 1.0, 1.0, 0.0])
    p = sparse_aggregators.segment_softmax(logits, seg, 2, mask)
    np.testing.assert_allclose(p[3], 0.0)
    np.testing.assert_allclose(p[0] + p[1], 1.0, atol=1e-6)
    np.testing.assert_allclose(p[2], 1.0, atol=1e-6)


@pytest.mark.parametrize("aggregator", ["gcn", "mean", "attention"])
def test_gcn_encoder_full_pipeline(graph, aggregator):
    """ops.get_multi_hop_neighbor -> MultiHop.adj -> GCNEncoder, jitted."""
    roots = np.array([10, 16], dtype=np.int64)
    roots, hops = ops.get_multi_hop_neighbor(
        graph,
        roots,
        [[0, 1], [0, 1]],
        max_nodes_per_hop=[8, 8],
        max_edges_per_hop=[16, 32],
    )
    feats = [graph.get_dense_feature(roots, [0], [2])] + [
        graph.get_dense_feature(h.nodes, [0], [2]) for h in hops
    ]
    adjs = [h.adj for h in hops]
    enc = GCNEncoder(num_layers=2, dim=8, aggregator=aggregator)
    params = enc.init(jax.random.PRNGKey(0), feats, adjs)
    out = jax.jit(enc.apply)(params, feats, adjs)
    assert out.shape == (2, 8)
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# Layer 0's messages: one pass from the stored table, or the hop's rows
# (models/gcn.py _SupervisedGCNModule._forward, OBSERVABILITY.md "message
# path")
# ---------------------------------------------------------------------------

MAX_ID = 16  # fixture ids go up to 16


def _gcn(aggregator="mean", **kw):
    from euler_tpu.models import SupervisedGCN

    kw.setdefault("device_features", True)
    kw.setdefault("device_sampling", kw["device_features"])
    return SupervisedGCN(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]], dim=16,
        max_nodes_per_hop=[24, 40], max_edges_per_hop=[64, 256],
        aggregator=aggregator, feature_idx=0, feature_dim=2, max_id=MAX_ID,
        **kw,
    )


def _state_batch(m, graph):
    from euler_tpu import train as train_lib

    opt = train_lib.get_optimizer("adam", 0.01)
    roots = graph.sample_node(8, -1)
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    return opt, state, jax.tree.map(jnp.asarray, m.sample(graph, roots))


def _plain_loss(m, params, batch, consts):
    """The step's loss with every hop's rows gathered by the hop's own
    set, cut to ``feature_dim`` and then read through ``dst``: the plain
    form ``table[nodes][..., :F][dst]``, written out here with the
    encoder handed nothing but ``(hidden, adjs)``."""
    import flax.linen as nn

    from euler_tpu.models import base
    from euler_tpu.nn.encoders import ShallowEncoder

    mod = m.module
    hops, adjs = mod.apply(
        {"params": params}, batch, consts, method=mod._hops_adjs)
    node_encoder = ShallowEncoder(
        dim=mod.dim if mod.use_residual else None,
        feature_dim=mod.feature_dim, max_id=mod.max_id,
        embedding_dim=mod.embedding_dim,
        combiner="add" if mod.use_residual else "concat",
    )
    hidden = []
    for f in hops:
        f = dict(f)
        if "dense" not in f:
            f["dense"] = consts["features"][f["gids"]][
                ..., :mod.feature_dim].astype(jnp.float32)
        hidden.append(node_encoder.apply(
            {"params": params.get("node_encoder", {})}, f))
    emb = GCNEncoder(
        num_layers=mod.num_layers, dim=mod.dim, aggregator=mod.aggregator,
        use_residual=mod.use_residual,
    ).apply({"params": params["encoder"]}, hidden, adjs)
    logits = nn.Dense(mod.num_classes).apply(
        {"params": params["predict"]}, emb)
    labels = base.lookup_labels(batch, consts, hops[0].get("gids"))
    return base.supervised_decoder(logits, labels, mod.sigmoid_loss)[0]


def _step_and_plain_form(m, state, batch):
    """(loss, gradients) of the model's step and of ``_plain_loss``."""
    args = state["params"], batch, state.get("consts")
    got = jax.jit(jax.value_and_grad(
        lambda p, b, c: m._apply(p, b, c).loss))(*args)
    want = jax.jit(jax.value_and_grad(
        lambda p, b, c: _plain_loss(m, p, b, c)))(*args)
    return got, want


def _assert_same_to_the_bit(m, state, batch):
    got, want = _step_and_plain_form(m, state, batch)
    assert np.asarray(got[0]) == np.asarray(want[0])
    leaves = jax.tree_util.tree_leaves_with_path(got[1])
    assert len(leaves) >= 4
    for (path, g), w in zip(leaves, jax.tree.leaves(want[1])):
        assert np.abs(np.asarray(g)).sum() > 0, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), str(path))


@pytest.mark.parametrize("aggregator", ["mean", "gcn"])
def test_one_pass_messages_equal_the_plain_form_to_the_bit(
        graph, aggregator):
    """Device expansion, device features, a node encoder that is the
    identity: the messages of both hops come from the stored table in one
    pass by ``nodes[dst]``, and the loss and every parameter's gradient
    are those of ``table[nodes][..., :F][dst]``, bit for bit."""
    m = _gcn(aggregator)
    _, state, batch = _state_batch(m, graph)
    assert m.module._hop_rows_why(batch, state["consts"]) is None
    _assert_same_to_the_bit(m, state, batch)


def test_attention_takes_the_one_pass_and_equals_the_plain_form(
        graph, caplog):
    """The attention aggregator reads layer 0's messages a slot, from the
    stored table, as the mean does: a slot's projection is ``W`` applied
    to that slot's row, the same dot product as projecting the hop's set
    and gathering by ``dst`` after, so loss and gradients are the plain
    form's to float32 rounding; both the sum and the softmax of the
    device expansion's lists go along their rows, and say so."""
    import logging

    m = _gcn("attention")
    opt, state, batch = _state_batch(m, graph)
    assert m.module._hop_rows_why(batch, state["consts"]) is None
    got, want = _step_and_plain_form(m, state, batch)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    leaves = jax.tree_util.tree_leaves_with_path(got[1])
    # two layers of four heads of three kernels, the classifier's two
    assert len(leaves) == 2 * 4 * 3 + 2
    for (path, g), w in zip(leaves, jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7,
                                   err_msg=str(path))
    from euler_tpu.models import gcn as gcn_models

    gcn_models._log_message_route.cache_clear()
    sparse_aggregators._log_aggregate_route.cache_clear()
    sparse_aggregators._log_attention_route.cache_clear()
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        new, loss, _ = jax.jit(m.make_train_step(opt))(state, batch)
    said = [r.getMessage() for r in caplog.records]
    messages = [s for s in said if s.startswith("message path:")]
    assert len(messages) == 2 and all(
        "one pass from the stored table" in s for s in messages)
    assert _routes(caplog) and all(
        "row sum over" in s for s in _routes(caplog))
    softmax = [s for s in said if s.startswith("attention path:")]
    assert softmax and all(
        "x 4 heads -> row softmax over" in s for s in softmax)
    assert np.isfinite(float(loss))
    # a step trains; a gate's gradient may be nought to rounding
    moved = jax.tree.map(
        lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))),
        state["params"], new["params"])
    assert any(jax.tree.leaves(moved))


@pytest.mark.parametrize("kw, why", [
    (dict(device_features=False), "host-expanded batch"),
    (dict(device_sampling=False), "host-expanded batch"),
    (dict(use_id=True), "use_id"),
    (dict(use_residual=True), "use_residual"),
], ids=["host_rows", "host_expanded", "use_id", "use_residual"])
def test_other_configurations_keep_the_hops_own_rows(graph, kw, why, caplog):
    """What must keep today's path does: it says why, its loss and
    gradients are the plain form's to the bit, and a step trains. A
    host-expanded batch's edge lists arrive as arguments of the jitted
    step, so they keep the segment sum and say so."""
    import logging

    m = _gcn(**kw)
    opt, state, batch = _state_batch(m, graph)
    assert why in m.module._hop_rows_why(batch, state.get("consts"))
    _assert_same_to_the_bit(m, state, batch)
    sparse_aggregators._log_aggregate_route.cache_clear()
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        new, loss, _ = jax.jit(m.make_train_step(opt))(state, batch)
    said = _routes(caplog)
    if "hops" in batch:
        assert said and all(
            s.endswith("segment sum (traced src)") for s in said)
    else:
        assert said and all("row sum over" in s for s in said)
    assert np.isfinite(float(loss))
    moved = jax.tree.map(
        lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))),
        state["params"], new["params"])
    assert all(jax.tree.leaves(moved))


def test_scalable_gcn_trains_a_step_on_its_own_path(graph, caplog):
    """ScalableGCN's module never sees a hop's set: its ``dst`` is
    ``arange`` and its neighbour rows are gathered by the slot's id
    already, so it takes no message route and says none."""
    import logging

    from euler_tpu import train as train_lib
    from euler_tpu.models import ScalableGCN

    m = ScalableGCN(
        label_idx=2, label_dim=3, edge_type=[0, 1], num_layers=2, dim=16,
        max_id=MAX_ID, max_neighbors=4, aggregator="mean", feature_idx=0,
        feature_dim=2, device_features=True, device_sampling=True,
    )
    opt = train_lib.get_optimizer("adam", 0.01)
    roots = graph.sample_node(8, -1)
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    sparse_aggregators._log_aggregate_route.cache_clear()
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        out = jax.jit(m.make_train_step(opt))(state, m.sample(graph, roots))
    new, loss = out[0], out[1]
    # its device expansion is a regular list too: 8 roots x 4 slab slots
    assert _routes(caplog) == ["aggregate path: 32 slots -> row sum over 4"]
    assert np.isfinite(float(loss))
    assert any(
        np.any(np.asarray(a) != np.asarray(b)) for a, b in zip(
            jax.tree.leaves(state["params"]), jax.tree.leaves(new["params"])))
    assert not [r for r in caplog.records if "message path" in r.getMessage()]


@pytest.mark.parametrize("slot_rows", [False, True], ids=["rows", "slots"])
@pytest.mark.parametrize("aggregator", ["mean", "gcn"])
def test_aggregator_reads_rows_by_dst_or_as_they_lie(aggregator, slot_rows):
    """``(self, neigh, adj)`` with the hop's rows, or with ``SlotRows``
    that already lie one a slot: the same masked mean, to the bit."""
    rng = np.random.default_rng(3)
    self_emb = jnp.asarray(rng.normal(size=(2, 4)), jnp.float32)
    neigh_emb = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    adj = _toy_adj()
    agg = sparse_aggregators.get(aggregator)(dim=4, activation=None)
    params = agg.init(jax.random.PRNGKey(0), (self_emb, neigh_emb, adj))
    neigh = neigh_emb
    if slot_rows:
        neigh = sparse_aggregators.SlotRows(neigh_emb[adj["dst"]])
    out = agg.apply(params, (self_emb, neigh, adj))
    msgs = neigh_emb[adj["dst"]] * adj["mask"][:, None]
    deg = jax.ops.segment_sum(adj["mask"], adj["src"], num_segments=2)
    mean = jax.ops.segment_sum(msgs, adj["src"], num_segments=2) / (
        jnp.maximum(deg, 1e-7)[:, None])
    kernels = jax.tree.leaves(params)
    if aggregator == "gcn":
        want = (self_emb + mean) @ kernels[0]
    else:
        want = self_emb @ kernels[0] + mean @ kernels[1]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_message_path_is_said_once_a_shape_in_both_forms(graph, caplog):
    import logging

    from euler_tpu.models import gcn as gcn_models

    def said(m):
        _, state, batch = _state_batch(m, graph)
        gcn_models._log_message_route.cache_clear()
        caplog.clear()
        with caplog.at_level(logging.INFO, logger="euler_tpu"):
            step = jax.jit(lambda s, b: m._apply(
                s["params"], b, s.get("consts")).loss)
            step(state, batch)
            step(state, batch)
            # a second trace of the same shapes says nothing new
            jax.jit(lambda s, b: m._apply(
                s["params"], b, s.get("consts")).embedding)(state, batch)
        return [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("message path:")]

    m = _gcn("mean")
    W = m.build_consts(graph)["adj"][m.adj_key([0, 1])]["nbr"].shape[1]
    assert said(m) == [
        f"message path: hop 1 {8 * W} slots -> one pass from the stored "
        "table (128 lanes)",
        f"message path: hop 2 {24 * W} slots -> one pass from the stored "
        "table (128 lanes)",
    ]
    assert said(_gcn("mean", use_residual=True)) == [
        f"message path: hop {h} {n * W} slots -> from the hop's rows "
        "(use_residual: the rows are projected)"
        for h, n in ((1, 8), (2, 24))
    ]


# ---------------------------------------------------------------------------
# A regular edge list (a constant src = repeat(arange(n), W)) is summed
# along its rows; every other list keeps the segment sum
# (sparse_aggregators._row_width, OBSERVABILITY.md "aggregate path")
# ---------------------------------------------------------------------------


def _regular_list(n, W, m, F=6, seed=5):
    """(self rows, the hop's rows, adjacency) of a regular list: n rows
    of W slots into a hop of m nodes, some slots masked, row 1 masked
    whole."""
    rng = np.random.default_rng(seed)
    mask = (rng.random((n, W)) < 0.7).astype(np.float32)
    mask[1] = 0.0
    mask = mask.reshape(-1)
    adj = {
        "src": np.repeat(np.arange(n, dtype=np.int32), W),
        "dst": rng.integers(0, m, n * W).astype(np.int32),
        "mask": mask, "w": mask,
    }
    self_emb = jnp.asarray(rng.normal(size=(n, F)), jnp.float32)
    neigh_emb = jnp.asarray(rng.normal(size=(m, F)), jnp.float32)
    return self_emb, neigh_emb, adj


def _routes(caplog):
    return [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("aggregate path:")]


def _said_while(caplog, fn, *args):
    """fn(*args), and the aggregate-path lines it said."""
    import logging

    sparse_aggregators._log_aggregate_route.cache_clear()
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        out = fn(*args)
    return out, _routes(caplog)


@pytest.mark.parametrize("jitted", [True, False], ids=["jit", "eager"])
@pytest.mark.parametrize("W", [5, 56])
@pytest.mark.parametrize("slot_rows", [True, False],
                         ids=["SlotRows", "hop_rows"])
@pytest.mark.parametrize("aggregator", ["mean", "gcn"])
def test_row_sum_of_a_regular_list_equals_the_segment_sum(
        aggregator, slot_rows, W, jitted, caplog):
    """The same masked mean by both forms: the output, the weights'
    gradients and (for the hop's rows) the neighbour input's are the
    segment sum's within float32 rounding, masked slots and an all-masked
    row among them; and each form says it was taken."""
    n, m = 7, 11
    self_emb, neigh_emb, adj = _regular_list(n, W, m)
    agg = sparse_aggregators.get(aggregator)(dim=4)
    params = agg.init(jax.random.PRNGKey(0), (self_emb, neigh_emb, adj))

    def value(params, neigh_emb, src):
        a = dict(adj, src=src)
        neigh = neigh_emb
        if slot_rows:
            neigh = sparse_aggregators.SlotRows(neigh_emb[a["dst"]])
        out = agg.apply(params, (self_emb, neigh, a))
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(
            out.shape))), out

    grad = jax.value_and_grad(value, argnums=(0, 1), has_aux=True)
    # the regular form: src stays the constant it is, closed over
    row = lambda p, x: grad(p, x, adj["src"])
    ((_, out), (g_w, g_x)), said = _said_while(
        caplog, jax.jit(row) if jitted else row, params, neigh_emb)
    assert said == [f"aggregate path: {n * W} slots -> row sum over {W}"]
    # the segment form: src arrives as an argument of the jitted program
    ((_, want), (w_w, w_x)), said = _said_while(
        caplog, jax.jit(grad), params, neigh_emb, adj["src"])
    assert said == [
        f"aggregate path: {n * W} slots -> segment sum (traced src)"]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(out)).sum() > 0
    for g, w in zip(jax.tree.leaves(g_w), jax.tree.leaves(w_w)):
        assert np.abs(np.asarray(w)).sum() > 0
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g_x, w_x, rtol=1e-5, atol=1e-5)


def _permuted(src, n, W):
    return np.random.default_rng(1).permutation(src)


def _rows_interleaved(src, n, W):
    # the right length and every row W times, slot i in row i % n
    return np.tile(np.arange(n, dtype=np.int32), W)


def _as_it_is(src, n, W):
    return src


def _permuted_on_device(src, n, W):
    return jnp.asarray(_permuted(src, n, W))


@pytest.mark.parametrize("src_of, traced, jitted", [
    (_as_it_is, True, True),
    (_permuted, False, True), (_permuted, False, False),
    (_rows_interleaved, False, True), (_rows_interleaved, False, False),
    (_permuted_on_device, False, True), (_permuted_on_device, False, False),
], ids=["traced-jit", "permutation-jit", "permutation-eager",
        "wrong_order-jit", "wrong_order-eager", "device_array-jit",
        "device_array-eager"])
def test_any_other_list_keeps_the_segment_sum(
        src_of, traced, jitted, caplog):
    """A src the trace cannot see (an argument of the jitted program; an
    eager call has none), or sees to be irregular, is not summed by rows:
    the route line says segment sum and why, and the mean is the list's
    own (a row sum would have given another)."""
    why = "traced src" if traced else "src not repeat(arange)"
    n, W, m = 7, 5, 11
    self_emb, neigh_emb, adj = _regular_list(n, W, m)
    src = src_of(adj["src"], n, W)
    agg = sparse_aggregators.MeanAggregator(dim=4, activation=None)
    params = agg.init(jax.random.PRNGKey(0), (self_emb, neigh_emb, adj))
    if traced:
        fn = lambda p, s: agg.apply(
            p, (self_emb, neigh_emb, dict(adj, src=s)))
        args = (params, src)
    else:
        fn = lambda p: agg.apply(
            p, (self_emb, neigh_emb, dict(adj, src=src)))
        args = (params,)
    out, said = _said_while(caplog, jax.jit(fn) if jitted else fn, *args)
    assert said == [f"aggregate path: {n * W} slots -> segment sum ({why})"]
    msgs = neigh_emb[adj["dst"]] * adj["mask"][:, None]
    deg = jax.ops.segment_sum(adj["mask"], src, num_segments=n)
    mean = jax.ops.segment_sum(msgs, src, num_segments=n) / (
        jnp.maximum(deg, 1e-7)[:, None])
    kernels = jax.tree.leaves(params)
    want = self_emb @ kernels[0] + mean @ kernels[1]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The attention aggregator's edge softmax takes the same two forms: along
# the rows of a regular list, by segment over any other
# (sparse_aggregators._gather_max, OBSERVABILITY.md "attention path")
# ---------------------------------------------------------------------------


def _attention_list(n, W, m):
    """``_regular_list`` with a row that lists one neighbour twice, both
    slots live (two edges, two shares of the softmax)."""
    self_emb, neigh_emb, adj = _regular_list(n, W, m)
    adj["dst"][2 * W + 1] = adj["dst"][2 * W]
    adj["mask"][2 * W:2 * W + 2] = 1.0
    return self_emb, neigh_emb, adj


def _attention_routes(caplog):
    return [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("attention path:")]


def _attention_said_while(caplog, fn, *args):
    import logging

    sparse_aggregators._log_attention_route.cache_clear()
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        out = fn(*args)
    return out, _attention_routes(caplog)


@pytest.mark.parametrize("heads", [None, 3], ids=["flat", "3_heads"])
@pytest.mark.parametrize("W", [5, 56])
def test_row_softmax_of_a_regular_list_equals_the_segment_softmax(
        W, heads, caplog):
    """``segment_softmax`` by both forms, value and gradient: masked
    slots get nought, a row masked whole is nought throughout, every
    other row sums to one, a neighbour listed twice has two shares."""
    n = 7
    _, _, adj = _attention_list(n, W, 11)
    shape = (n * W,) if heads is None else (n * W, heads)
    logits = jnp.asarray(
        np.random.default_rng(0).normal(size=shape) * 3, jnp.float32)
    mask = jnp.asarray(adj["mask"])

    def value(logits, src):
        p = sparse_aggregators.segment_softmax(logits, src, n, mask)
        return jnp.sum(p * jnp.cos(jnp.arange(p.size).reshape(p.shape))), p

    grad = jax.value_and_grad(value, has_aux=True)
    ((_, p), g), said = _attention_said_while(
        caplog, jax.jit(lambda x: grad(x, adj["src"])), logits)
    k = heads or 1
    assert said == [
        f"attention path: {n * W} slots x {k} heads -> row softmax over {W}"]
    ((_, want), w), said = _attention_said_while(
        caplog, jax.jit(grad), logits, adj["src"])
    assert said == [
        f"attention path: {n * W} slots x {k} heads -> segment softmax"]
    np.testing.assert_allclose(p, want, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    rows = np.asarray(p).reshape((n, W) + shape[1:])
    live = adj["mask"].reshape(n, W) > 0
    assert (rows[~live] == 0).all() and (rows[1] == 0).all()
    np.testing.assert_allclose(
        rows.sum(1)[live.any(1)], 1.0, rtol=1e-6)
    assert np.abs(np.asarray(g)).sum() > 0


@pytest.mark.parametrize("jitted", [True, False], ids=["jit", "eager"])
@pytest.mark.parametrize("renorm", [False, True], ids=["plain", "renorm"])
@pytest.mark.parametrize("W", [5, 56])
@pytest.mark.parametrize("slot_rows", [True, False],
                         ids=["SlotRows", "hop_rows"])
def test_row_form_of_the_attention_equals_the_segment_form(
        slot_rows, W, renorm, jitted, caplog):
    """The four heads by both forms on the same seeded weights: output,
    the weights' gradients and the neighbour input's are the segment
    form's within float32 rounding, with masked slots, an all-masked row
    and a neighbour listed twice; and each form says it was taken."""
    n, m = 7, 11
    self_emb, neigh_emb, adj = _attention_list(n, W, m)
    agg = sparse_aggregators.AttentionAggregator(dim=8, renorm=renorm)
    params = agg.init(jax.random.PRNGKey(0), (self_emb, neigh_emb, adj))
    assert sorted(params["params"]) == [
        "SingleAttentionAggregator_%d" % k for k in range(4)]

    def value(params, neigh_emb, src):
        a = dict(adj, src=src)
        neigh = neigh_emb
        if slot_rows:
            neigh = sparse_aggregators.SlotRows(neigh_emb[a["dst"]])
        out = agg.apply(params, (self_emb, neigh, a))
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(
            out.shape))), out

    grad = jax.value_and_grad(value, argnums=(0, 1), has_aux=True)
    row = lambda p, x: grad(p, x, adj["src"])
    ((_, out), (g_w, g_x)), said = _attention_said_while(
        caplog, jax.jit(row) if jitted else row, params, neigh_emb)
    assert said == [
        f"attention path: {n * W} slots x 4 heads -> row softmax over {W}"]
    ((_, want), (w_w, w_x)), said = _attention_said_while(
        caplog, jax.jit(grad), params, neigh_emb, adj["src"])
    assert said == [
        f"attention path: {n * W} slots x 4 heads -> segment softmax"]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert out.shape == (n, 8) and np.abs(np.asarray(out)).sum() > 0
    for g, w in zip(jax.tree.leaves(g_w), jax.tree.leaves(w_w)):
        assert np.abs(np.asarray(w)).sum() > 0
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(g_x, w_x, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("renorm", [False, True], ids=["plain", "renorm"])
def test_attention_reads_rows_by_dst_or_as_they_lie(renorm):
    """``SlotRows`` into the attention aggregator against the hop's set
    rows through ``dst``, and both against the softmax written out dense
    [n, m] here, head by head: to float32 rounding."""
    n, W, m = 7, 5, 11
    self_emb, neigh_emb, adj = _attention_list(n, W, m)
    agg = sparse_aggregators.AttentionAggregator(dim=8, renorm=renorm)
    params = agg.init(jax.random.PRNGKey(0), (self_emb, neigh_emb, adj))
    by_dst = agg.apply(params, (self_emb, neigh_emb, adj))
    as_they_lie = agg.apply(params, (
        self_emb, sparse_aggregators.SlotRows(neigh_emb[adj["dst"]]), adj))
    np.testing.assert_allclose(as_they_lie, by_dst, rtol=1e-5, atol=1e-6)
    # dense: count[i, j] edges from i to j, each with its own share
    count = np.zeros((n, m))
    np.add.at(count, (adj["src"], adj["dst"]), adj["mask"])
    heads = []
    for k in range(4):
        leaves = params["params"]["SingleAttentionAggregator_%d" % k]
        w, u, v = (np.asarray(leaves["Dense_%d" % i]["Dense_0"]["kernel"],
                              np.float64) for i in range(3))
        p_s = np.asarray(self_emb, np.float64) @ w
        p_a = np.asarray(neigh_emb, np.float64) @ w
        logit = p_s @ u + (p_a @ v).T                     # [n, m]
        e = count * np.exp(np.where(logit > 0, logit, 0.01 * logit))
        if renorm:
            own = (p_s @ u + p_s @ v)[:, 0]
            e_own = np.exp(np.where(own > 0, own, 0.01 * own))
            denom = e.sum(1) + e_own
            h = (e @ p_a + e_own[:, None] * p_s) / denom[:, None]
        else:
            h = p_s + e @ p_a / np.maximum(e.sum(1), 1e-30)[:, None]
        heads.append(np.maximum(h, 0))
    np.testing.assert_allclose(
        by_dst, np.concatenate(heads, 1), rtol=1e-4, atol=1e-5)


def test_kernel_is_a_bias_free_denses_kernel_at_its_path():
    """``_Kernel`` declares by hand what ``layers.Dense(dim,
    use_bias=False)`` keeps (the attention heads' tree, which checkpoints
    and the benchmark's seeded weights name): the same tree, the same
    initial values from the same key. A change to ``Dense``'s tree or
    initialiser has to be made in both, and this says so."""
    from flax import linen as nn

    from euler_tpu.nn.layers import Dense

    class ByDense(nn.Module):
        @nn.compact
        def __call__(self, x):
            return (Dense(8, use_bias=False)(x),
                    Dense(1, use_bias=False)(x))

    class ByKernel(nn.Module):
        @nn.compact
        def __call__(self, x):
            return (sparse_aggregators._Kernel(8, name="Dense_0")(x.shape[-1]),
                    sparse_aggregators._Kernel(1, name="Dense_1")(x.shape[-1]))

    x = jnp.ones((3, 5))
    want = ByDense().init(jax.random.PRNGKey(7), x)
    got = ByKernel().init(jax.random.PRNGKey(7), x)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert path[-1].key == "kernel"
        np.testing.assert_array_equal(g, w)
