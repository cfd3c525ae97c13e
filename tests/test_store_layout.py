"""The Scalable* stores' pinned device layout (parallel/mesh.py
state_sharding / put_global, train.py): which leaves carry a layout,
that placement re-lays and frees what it must and nothing else, and that
a state handed to ``train()`` as ``[max_id + 2, dim]`` tables comes out
as such, bit for bit what the unpinned step gives on the same batches.
Runs on the conftest's 8-device CPU mesh, where rows-major is the
device's own layout: the re-lay branch is driven with a column-major
source array, which the CPU backend can hold."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding

from euler_tpu import train as train_lib
from euler_tpu.parallel import (
    batch_sharding,
    make_mesh,
    pad_tables_for_mesh,
    put_global,
    replicated_sharding,
    shard_batch,
    state_sharding,
)
from euler_tpu.parallel.mesh import _is_table, table_sharding

STORE_KEYS = ("stores", "grad_stores")


def _scalable(name, **over):
    from euler_tpu.models import ScalableGCN, ScalableSage

    kw = dict(
        label_idx=2, label_dim=3, edge_type=[0, 1], num_layers=2, dim=8,
        max_id=16, feature_idx=0, feature_dim=2,
    )
    if name == "scalable_sage":
        kw["fanout"] = 3
        cls = ScalableSage
    else:
        kw["max_neighbors"] = 16
        cls = ScalableGCN
    kw.update(over)
    return cls(**kw)


def _graphsage():
    from euler_tpu.models import SupervisedGraphSage

    return SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]], fanouts=[3, 2],
        dim=8, feature_idx=0, feature_dim=2, max_id=16, device_features=True,
    )


def _state(model, graph, mesh, seed=0):
    opt = train_lib.get_optimizer("adam", 0.02)
    state = model.init_state(
        jax.random.PRNGKey(seed), graph, np.arange(8), opt
    )
    return pad_tables_for_mesh(state, mesh), opt


def _bare_shardings(mesh, state):
    """The pytree ``state_sharding`` gave before any layout was pinned:
    a bare sharding a leaf, tables row-sharded over a model axis."""
    rep, tab = replicated_sharding(mesh), table_sharding(mesh)
    if mesh.shape["model"] <= 1:
        return jax.tree.map(lambda _: rep, state)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: tab if _is_table(path, x) else rep, state
    )


def _leaves_by_path(tree):
    return {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


@pytest.mark.parametrize("mp", [1, 2], ids=["data_only", "model_parallel"])
@pytest.mark.parametrize("name", ["scalable_sage", "scalable_gcn"])
def test_state_sharding_pins_exactly_the_store_leaves(graph, name, mp):
    mesh = make_mesh(8, model_parallel=mp)
    state, _ = _state(_scalable(name, device_features=True), graph, mesh)
    got = _leaves_by_path(state_sharding(mesh, state))
    bare = _leaves_by_path(_bare_shardings(mesh, state))
    assert got.keys() == bare.keys() == _leaves_by_path(state).keys()
    pinned = {k for k, v in got.items() if isinstance(v, Format)}
    assert pinned == {
        k for k in got if k.startswith(tuple("['%s']" % s for s in STORE_KEYS))
    }
    assert len(pinned) == 2
    for k, v in got.items():
        if k in pinned:
            assert v.layout.major_to_minor == (0, 1)
            assert v.layout.tiling is None   # the device's compiler tiles
            assert v.sharding == bare[k]
        else:
            assert isinstance(v, NamedSharding) and v == bare[k], k
    if mp > 1:
        assert got["['stores'][0]"].sharding == table_sharding(mesh)
        assert got["['consts']['features']"] == table_sharding(mesh)


@pytest.mark.parametrize("mp", [1, 2], ids=["data_only", "model_parallel"])
def test_graphsage_state_yields_the_pytree_it_yielded_before(graph, mp):
    mesh = make_mesh(8, model_parallel=mp)
    state, _ = _state(_graphsage(), graph, mesh)
    got = state_sharding(mesh, state)
    assert jax.tree.structure(got) == jax.tree.structure(state)
    assert all(isinstance(s, NamedSharding) for s in jax.tree.leaves(got))
    assert _leaves_by_path(got) == _leaves_by_path(
        _bare_shardings(mesh, state))


def _column_major(x, sharding):
    return jax.device_put(
        jax.device_put(x, sharding), Format(Layout((1, 0)), sharding))


@pytest.mark.parametrize("mp", [1, 2], ids=["data_only", "model_parallel"])
def test_put_global_places_and_relays_a_pinned_pytree(graph, mp):
    mesh = make_mesh(8, model_parallel=mp)
    state, _ = _state(_scalable("scalable_sage"), graph, mesh)
    shardings = state_sharding(mesh, state)
    want = jax.tree.map(np.asarray, state)
    placed = put_global(state, shardings)
    for k, s in _leaves_by_path(shardings).items():
        x = _leaves_by_path(placed)[k]
        if isinstance(s, Format):
            assert x.sharding == s.sharding
            assert tuple(x.format.layout.major_to_minor) == (0, 1)
        else:
            assert x.sharding == s
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, placed), want)
    # placed arrays are handed back as they are, consumed or not
    again = put_global(placed, shardings, consume=True)
    assert all(a is b for a, b in zip(
        jax.tree.leaves(again), jax.tree.leaves(placed)))
    assert not placed["stores"][0].is_deleted()

    # a table the device holds column-major (a TPU's own choice for a
    # [rows, 64] float32 table) is re-laid once; the source is freed only
    # where the caller gives the state up
    s = shardings["stores"][0]
    for consume in (False, True):
        col = _column_major(want["stores"][0], s.sharding)
        gcol = _column_major(want["grad_stores"][0], s.sharding)
        assert tuple(col.format.layout.major_to_minor) == (1, 0)
        out = put_global(
            dict(placed, stores=[col], grad_stores=[gcol]), shardings,
            consume=consume)
        for got, src, key in ((out["stores"][0], col, "stores"),
                              (out["grad_stores"][0], gcol, "grad_stores")):
            assert got is not src
            assert tuple(got.format.layout.major_to_minor) == (0, 1)
            assert got.shape == want[key][0].shape
            np.testing.assert_array_equal(np.asarray(got), want[key][0])
            assert src.is_deleted() == consume
        # nothing but the re-laid leaves was touched
        assert out["params"] is not None and all(
            a is b for a, b in zip(jax.tree.leaves(out["params"]),
                                   jax.tree.leaves(placed["params"])))
        assert not any(x.is_deleted() for x in jax.tree.leaves(placed))


def test_jitted_step_refuses_a_store_that_was_not_placed(graph):
    """The pin is a contract on the step's input: a column-major store
    that skipped ``put_global`` is refused, not silently copied a step."""
    mesh = make_mesh(8, model_parallel=1)
    model = _scalable("scalable_sage")
    state, opt = _state(model, graph, mesh)
    shardings = state_sharding(mesh, state)
    rep = replicated_sharding(mesh)
    step = jax.jit(
        model.make_train_step(opt),
        in_shardings=(shardings, batch_sharding(mesh)),
        out_shardings=(shardings, rep, rep),
    )
    batch = shard_batch(model.sample(graph, np.arange(8)), mesh)
    bad = dict(put_global(state, shardings))
    bad["stores"] = [_column_major(np.asarray(state["stores"][0]), rep)]
    with pytest.raises(ValueError, match="[Ll]ayout"):
        step(bad, batch)
    out, loss, _ = step(put_global(bad, shardings), batch)
    assert np.isfinite(float(loss))
    assert tuple(out["stores"][0].format.layout.major_to_minor) == (0, 1)


def test_put_global_multi_process_branch_accepts_a_format(graph, monkeypatch):
    """Under jax.distributed every leaf is assembled from a callback; a
    ``Format`` goes where the sharding went, and a leaf that already sits
    as asked is not pulled to the host."""
    mesh = make_mesh(8, model_parallel=2)
    state, _ = _state(_scalable("scalable_sage"), graph, mesh)
    shardings = state_sharding(mesh, state)
    want = jax.tree.map(np.asarray, state)
    calls = []
    real = jax.make_array_from_callback

    def recording(shape, sharding, cb, *a, **kw):
        calls.append(sharding)
        return real(shape, sharding, cb, *a, **kw)

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "make_array_from_callback", recording)
    placed = put_global(state, shardings)
    n = len(jax.tree.leaves(state))
    assert len(calls) == n
    assert sum(isinstance(s, Format) for s in calls) == 2
    assert tuple(
        placed["stores"][0].format.layout.major_to_minor) == (0, 1)
    assert placed["grad_stores"][0].sharding == table_sharding(mesh)
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, placed), want)
    again = put_global(placed, shardings)
    assert len(calls) == n
    assert all(a is b for a, b in zip(
        jax.tree.leaves(again), jax.tree.leaves(placed)))


def _recording_sample(model, record):
    sample = model.sample

    def recorded(graph, roots):
        batch = sample(graph, roots)
        record.append(jax.tree.map(np.asarray, batch))
        return batch

    return recorded


@pytest.mark.parametrize("mp", [1, 2], ids=["data_only", "model_parallel"])
@pytest.mark.parametrize("name", ["scalable_sage", "scalable_gcn"])
def test_train_from_a_handed_in_state_matches_the_unpinned_step(
        graph, tmp_path, monkeypatch, name, mp):
    """``train(state=)`` from [max_id + 2, dim] tables, through a
    checkpoint save and a resume, then ``evaluate`` and ``embed``: the
    stores come out with the logical shape they went in with and the bits
    the step gives under bare shardings (the parent's placement) on the
    same batches."""
    mesh = make_mesh(8, model_parallel=mp)
    model = _scalable(name, device_features=True)
    state0, opt = _state(model, graph, mesh)
    rows = state0["stores"][0].shape[0]
    assert rows == (18 if mp == 1 else 18 + (-18) % mp)
    host0 = jax.tree.map(lambda x: np.array(x, copy=True), state0)

    def fresh():
        # device arrays of their own: the step donates what it is handed,
        # and a CPU array made from a numpy one may share its memory
        return jax.tree.map(lambda x: jnp.array(x, copy=True), host0)

    batches = []
    monkeypatch.setattr(model, "sample", _recording_sample(model, batches))

    def source(step):
        return np.asarray(graph.sample_node(8, -1))

    kw = dict(
        mesh=mesh, optimizer="adam", learning_rate=0.02, log_every=2,
        prefetch_threads=1, prefetch_depth=1, checkpoint_every=2,
        checkpoint_dir=str(tmp_path / "ck"), seed=3,
    )
    state, _ = train_lib.train(
        model, graph, source, num_steps=4, state=fresh(), **kw)
    # resumes at step 4 from the checkpoint, whatever state it is handed
    state, _ = train_lib.train(
        model, graph, source, num_steps=6, state=fresh(), **kw)
    # one batch a step, in step order (one prefetch worker): four of the
    # first call, two after the resume
    assert len(batches) == 6
    trained_on = list(batches)
    for key in STORE_KEYS:
        assert state[key][0].shape == (rows, 8)
        assert state[key][0].dtype == jnp.float32
        assert tuple(state[key][0].format.layout.major_to_minor) == (0, 1)

    res = train_lib.evaluate(
        model, graph, [np.arange(8)], state, mesh=mesh)
    assert np.isfinite(res["loss"])
    emb = train_lib.save_embedding(
        model, graph, 16, state, batch_size=8, mesh=mesh)
    assert emb.shape == (17, 8) and np.isfinite(emb).all()
    assert not state["stores"][0].is_deleted()

    # the same batches through the step under bare shardings
    bare = _bare_shardings(mesh, state0)
    rep = replicated_sharding(mesh)
    step = jax.jit(
        model.make_train_step(opt),
        in_shardings=(bare, batch_sharding(mesh)),
        out_shardings=(bare, rep, rep),
        donate_argnums=(0,),
    )
    ref = jax.device_put(fresh(), bare)
    for batch in trained_on:
        ref, _, _ = step(ref, shard_batch(batch, mesh))
    for key in STORE_KEYS + ("params",):
        jax.tree.map(
            np.testing.assert_array_equal,
            jax.tree.map(np.asarray, state[key]),
            jax.tree.map(np.asarray, ref[key]))
    assert not np.array_equal(np.asarray(state["stores"][0]),
                              host0["stores"][0])


def test_pinned_programs_compile_outside_the_persistent_cache(tmp_path):
    """A program whose placement pins a layout is neither read from nor
    written to the persistent compile cache (a TPU executable that comes
    back from it has lost its pinned result layouts); a placement
    without a pin keeps the cache, and the cache is back on afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    from euler_tpu.parallel import compiles_keep_layouts

    rep = replicated_sharding(make_mesh(1))
    pinned = {"stores": [Format(Layout((0, 1)), rep)], "params": rep}
    flags = {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }
    before = {k: getattr(jax.config, k) for k in flags}
    # the cache's directory is parallel/mesh.py's to name (a test of
    # tests/test_bench_gate.py holds every other file to that): here it
    # is set and put back through jax's own call, to what the environment
    # gave the process
    before_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        for k, v in flags.items():
            jax.config.update(k, v)
        compilation_cache.set_cache_dir(str(tmp_path))
        compilation_cache.reset_cache()

        def entries():
            return {f for f in (p.name for p in tmp_path.iterdir())
                    if f.endswith("-cache")}

        with compiles_keep_layouts(pinned):
            assert not jax.config.jax_enable_compilation_cache
            jax.jit(lambda a: a * 2 + 1)(jnp.ones(4)).block_until_ready()
        assert jax.config.jax_enable_compilation_cache
        assert entries() == set()
        with compiles_keep_layouts({"params": rep}):
            assert jax.config.jax_enable_compilation_cache
            jax.jit(lambda a: a * 3 + 1)(jnp.ones(4)).block_until_ready()
        assert len(entries()) == 1
        # and an exception inside does not leave the cache off
        with pytest.raises(RuntimeError):
            with compiles_keep_layouts(pinned["stores"][0]):
                raise RuntimeError("compile failed")
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.set_cache_dir(before_dir)
        compilation_cache.reset_cache()
