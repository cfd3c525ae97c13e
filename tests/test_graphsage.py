"""End-to-end GraphSAGE training tests on the fixture graph + an 8-device
CPU mesh (the conftest forces JAX_PLATFORMS=cpu with 8 virtual devices)."""

import numpy as np
import pytest

import jax


@pytest.fixture(scope="module")
def sage_model():
    from euler_tpu.models import SupervisedGraphSage

    # Fixture nodes: dense feature slot 0 (dim 2) as input features, slot 2
    # (dim 3, multi-hot) as labels for a 3-class toy problem.
    return SupervisedGraphSage(
        label_idx=2,
        label_dim=3,
        metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2],
        dim=8,
        feature_idx=0,
        feature_dim=2,
        max_id=16,
    )


def test_sample_shapes(graph, sage_model):
    batch = sage_model.sample(graph, np.array([10, 12, 14, 16]))
    assert batch["labels"].shape == (4, 3)
    hops = batch["hops"]
    assert hops[0]["dense"].shape == (4, 2)
    assert hops[1]["dense"].shape == (12, 2)
    assert hops[2]["dense"].shape == (24, 2)


def test_train_loop_runs_and_learns(graph, sage_model):
    from euler_tpu import train as train_lib

    def source_fn(step):
        return graph.sample_node(16, -1)

    state, history = train_lib.train(
        sage_model,
        graph,
        source_fn,
        num_steps=60,
        learning_rate=0.05,
        log_every=10,
    )
    assert len(history) == 6
    # loss trends down on this trivially learnable toy target (individual
    # windows are noisy: 16-node batches, unseeded sampling)
    assert min(h["loss"] for h in history[1:]) < history[0]["loss"]


def test_train_multidevice_equals_semantics(graph, sage_model):
    """The 8-device data-parallel step must produce finite loss and valid f1
    counts with a batch sharded over all devices."""
    from euler_tpu import train as train_lib
    from euler_tpu.parallel import make_mesh

    assert len(jax.devices()) == 8
    mesh = make_mesh(8)

    def source_fn(step):
        return graph.sample_node(16, -1)  # 2 rows per device

    state, history = train_lib.train(
        sage_model, graph, source_fn, num_steps=10, mesh=mesh, log_every=5
    )
    assert np.isfinite(history[-1]["loss"])
    assert 0.0 <= history[-1]["f1"] <= 1.0


def test_evaluate_and_save_embedding(graph, sage_model):
    from euler_tpu import train as train_lib

    def source_fn(step):
        return graph.sample_node(16, -1)

    state, _ = train_lib.train(
        sage_model, graph, source_fn, num_steps=5, log_every=5
    )
    result = train_lib.evaluate(
        sage_model, graph, [graph.sample_node(16, -1) for _ in range(3)], state
    )
    assert "f1" in result and np.isfinite(result["loss"])
    emb = train_lib.save_embedding(
        sage_model, graph, max_id=16, state=state, batch_size=8
    )
    assert emb.shape == (17, 8)
    assert np.isfinite(emb).all()


def test_unsupervised_graphsage(graph):
    from euler_tpu import train as train_lib
    from euler_tpu.models import GraphSage

    model = GraphSage(
        node_type=-1,
        edge_type=[0, 1],
        max_id=16,
        metapath=[[0, 1]],
        fanouts=[3],
        dim=8,
        num_negs=4,
        feature_idx=0,
        feature_dim=2,
    )

    def source_fn(step):
        return graph.sample_node(16, -1)

    state, history = train_lib.train(
        model, graph, source_fn, num_steps=10, log_every=5
    )
    assert np.isfinite(history[-1]["loss"])
    assert 0.0 < history[-1]["mrr"] <= 1.0


def test_device_features_match_host_gather(graph):
    """device_features=True (HBM-resident tables + on-device gather) must be
    numerically identical to the host-gather path on the same sampled ids."""
    import jax
    import numpy as np
    import optax
    from euler_tpu.models import SupervisedGraphSage

    kw = dict(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=8, feature_idx=0, feature_dim=2, max_id=16,
    )
    m_host = SupervisedGraphSage(**kw)
    m_dev = SupervisedGraphSage(**kw, device_features=True)
    roots = np.array([10, 12, 14, 16], dtype=np.int64)
    ids_per_hop, _, _ = graph.sample_fanout(
        roots, m_host.metapath, m_host.fanouts, m_host.default_node
    )
    host_batch = {
        "hops": [
            {"dense": graph.get_dense_feature(ids, [0], [2])}
            for ids in ids_per_hop
        ],
        "labels": graph.get_dense_feature(roots, [2], [3]),
    }
    dev_batch = {
        "hops": [
            {"gids": np.clip(ids, 0, 17).astype(np.int32)}
            for ids in ids_per_hop
        ]
    }
    opt = optax.adam(0.01)
    state = m_dev.init_state(jax.random.PRNGKey(7), graph, roots, opt)
    assert set(state["consts"]) == {"features", "labels"}
    out_dev = m_dev.module.apply(
        {"params": state["params"]}, dev_batch, state["consts"]
    )
    out_host = m_host.module.apply({"params": state["params"]}, host_batch)
    np.testing.assert_allclose(
        np.asarray(out_dev.loss), np.asarray(out_host.loss), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(out_dev.embedding),
        np.asarray(out_host.embedding),
        rtol=1e-5,
    )
    # and a full train step through the generic machinery runs
    step = jax.jit(m_dev.make_train_step(opt), donate_argnums=(0,))
    batch = m_dev.sample(graph, roots)
    state2, loss, metric = step(state, batch)
    assert np.isfinite(float(loss))
    assert "consts" in state2


def test_feature_dtype_bfloat16(graph, monkeypatch):
    """feature_dtype='bfloat16' stores the feature table half-size in HBM;
    rows are cast back to float32 at the gather (base.gather_consts), so
    model math sees only the storage rounding. On the fixture (feature
    values exactly representable in bfloat16) the result is identical to
    the float32 path; labels must stay float32 regardless."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from euler_tpu.models import SupervisedGraphSage

    kw = dict(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=8, feature_idx=0, feature_dim=2, max_id=16,
        device_features=True,
    )
    roots = np.array([10, 12, 14, 16], dtype=np.int64)
    opt = optax.adam(0.01)

    m32 = SupervisedGraphSage(**kw)
    s32 = m32.init_state(jax.random.PRNGKey(7), graph, roots, opt)

    m16 = SupervisedGraphSage(**kw, feature_dtype="bfloat16")
    s16 = m16.init_state(jax.random.PRNGKey(7), graph, roots, opt)
    assert s16["consts"]["features"].dtype == jnp.bfloat16
    assert s16["consts"]["labels"].dtype == jnp.float32

    batch = m16.sample(graph, roots)
    out32 = m32.module.apply(
        {"params": s32["params"]}, batch, s32["consts"]
    )
    out16 = m16.module.apply(
        {"params": s32["params"]}, batch, s16["consts"]
    )
    assert out16.embedding.dtype == jnp.float32  # cast back at the gather
    np.testing.assert_allclose(
        np.asarray(out16.loss), np.asarray(out32.loss), rtol=1e-6
    )

    # env-var spelling reaches build_consts too
    monkeypatch.setenv("EULER_TPU_FEATURE_DTYPE", "bfloat16")
    m_env = SupervisedGraphSage(**kw)
    s_env = m_env.init_state(jax.random.PRNGKey(7), graph, roots, opt)
    assert s_env["consts"]["features"].dtype == jnp.bfloat16
    monkeypatch.delenv("EULER_TPU_FEATURE_DTYPE")

    # a bogus dtype fails loudly, naming the knob
    with pytest.raises(ValueError, match="feature_dtype"):
        SupervisedGraphSage(**kw, feature_dtype="bf16").build_consts(graph)


# ---- the stored feature table (PERF.md section 6, PR 28) ----


def _sage_kw(feature_dim=2):
    return dict(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=8, feature_idx=0, feature_dim=feature_dim,
        max_id=16, device_features=True,
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "feature_dim,stored", [(50, 128), (602, 640), (128, 128)]
)
def test_build_consts_stores_lane_multiple_width(
    graph, feature_dim, stored, dtype
):
    """The feature table's rows are stored at the next multiple of 128
    lanes (a lane multiple as it is), the pad lanes hold zeros, and the
    lanes before them are the engine's rows, in either storage dtype."""
    from euler_tpu.models import SupervisedGraphSage, base

    assert base.stored_width(feature_dim) == stored
    model = SupervisedGraphSage(
        **_sage_kw(feature_dim), feature_dtype=dtype
    )
    table = model.build_consts(graph)["features"]
    assert table.shape == (18, stored) and table.dtype == dtype
    table = np.asarray(table, np.float32)
    want = graph.get_dense_feature(
        np.arange(18, dtype=np.int64), [0], [feature_dim]
    )
    np.testing.assert_array_equal(table[:, :feature_dim], want)
    assert want[10:17, :2].any()  # the fixture's rows, not all zeros
    assert not table[:, feature_dim:].any()


def _stored_and_sliced(model, graph, roots, opt):
    """One state with the table as build_consts stores it, and one whose
    table is the same rows cut to feature_dim by hand (the pre-PR 28
    form): same params, same batch."""
    state = model.init_state(jax.random.PRNGKey(7), graph, roots, opt)
    assert state["consts"]["features"].shape[1] == 128
    sliced = dict(state)
    sliced["consts"] = dict(state["consts"])
    sliced["consts"]["features"] = state["consts"]["features"][
        :, : model.feature_dim
    ]
    return state, sliced


@pytest.mark.parametrize("family", ["graphsage_supervised", "gat"])
def test_stored_table_is_bit_identical_to_sliced(graph, family):
    """The pad lanes are cut off at the gather, so loss, gradients and
    embeddings are the same bits with the stored [N, 128] table and with
    the [N, feature_dim] table it was before."""
    import optax

    from euler_tpu.models import GAT, SupervisedGraphSage

    if family == "gat":
        model = GAT(
            label_idx=2, label_dim=3, feature_idx=0, feature_dim=2,
            max_id=16, head_num=2, hidden_dim=16, nb_num=4, edge_type=0,
            device_features=True,
        )
    else:
        model = SupervisedGraphSage(**_sage_kw())
    roots = np.array([10, 12, 14, 16], dtype=np.int64)
    opt = optax.adam(0.01)
    state, sliced = _stored_and_sliced(model, graph, roots, opt)
    batch = model.sample(graph, roots)

    embed = model.make_embed_step()
    step = jax.jit(model.make_train_step(opt))

    def outputs(s):
        loss, grads = jax.value_and_grad(
            lambda p: model._apply(p, batch, s["consts"]).loss
        )(s["params"])
        return grads, loss, embed(s, batch), step(s, batch)[0]["params"]

    got, want = outputs(state), outputs(sliced)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert any(np.asarray(g).any() for g in jax.tree.leaves(got[0]))


def test_stored_table_row_shards_on_data2_model2(graph):
    """data=2 x model=2: the stored table still shards by rows over
    'model' (the lane padding is on dim 1, the mesh padding on dim 0)
    and train() runs on it."""
    from jax.sharding import PartitionSpec as P

    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage
    from euler_tpu.parallel import make_mesh

    model = SupervisedGraphSage(**_sage_kw(50))
    state, hist = train_lib.train(
        model, graph, lambda s: graph.sample_node(8, -1), num_steps=12,
        mesh=make_mesh(4, model_parallel=2), learning_rate=0.05,
        log_every=6,
    )
    table = state["consts"]["features"]
    assert table.shape == (18, 128)
    assert table.sharding.spec == P("model")
    assert {s.data.shape for s in table.addressable_shards} == {(9, 128)}
    assert len(hist) == 2 and np.isfinite(hist[-1]["loss"])
