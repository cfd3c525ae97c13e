"""The toy store family's plain reference, held to the description of
the step it follows (tests/benchmark/toy/toy_store_reference.py): the
bookkeeping of the two stores by hand in numpy, the two Adams against
optax's, and the reference against the program's own step on the CPU."""

import json
import os

import numpy as np
import pytest

from benchmark import check, graphgen, harness

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = {"fanouts": [2, 2], "dim": 4, "feature_dim": 3, "num_classes": 2,
       "concat": True, "aggregator": "mean", "sigmoid_loss": False,
       "learning_rate": 0.03, "store_learning_rate": 0.001,
       "store_init_maxval": 0.05, "graph": {"num_nodes": 9}}


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(
        os.path.join(HERE, "toy", "toy_store_reference.py"),
        "test_toy_store_reference")


def _start(ref, seed=0):
    import jax
    import optax

    start, state = ref.init_state(CFG, jax.random.PRNGKey(seed),
                                  optax.adam(CFG["learning_rate"]))
    return {k: np.asarray(v) for k, v in start.items()}, state


def _batch(rng, roots, neighbours):
    roots, neighbours = np.asarray(roots), np.asarray(neighbours)
    y = np.zeros((len(roots), 2), np.float32)
    y[np.arange(len(roots)), rng.integers(0, 2, len(roots))] = 1
    return {"roots": roots, "neighbours": neighbours, "y": y,
            "x0": rng.normal(size=(len(roots), 3)).astype(np.float32),
            "x1": rng.normal(size=(len(neighbours), 3)).astype(np.float32)}


def test_state_is_what_the_store_step_takes(ref):
    start, state = _start(ref)
    assert set(state) == {"params", "opt_state", "stores", "grad_stores",
                          "store_opt_state"}
    assert state["stores"][0].shape == (10, 4)        # max_id + 2 rows
    assert 0 <= start["store0"].min() and start["store0"].max() <= 0.05
    assert not start["grad_store0"].any()
    assert ref.drawn_fanouts(CFG) == [2]
    # compared after step 3: parameters, both stores, the second Adam
    assert set(ref.compared_state(state)) == set(start)


def test_store_bookkeeping_by_hand(ref):
    """Steps 1, 4 and 5 of the description on two batches: the second
    one's roots were the first one's neighbours, one root comes twice."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    start, _ = _start(ref)
    b1 = _batch(rng, [0, 1], [2, 3, 3, 4])
    b2 = _batch(rng, [3, 3], [5, 6, 7, 3])
    _, _, mid = ref.train_steps(CFG, start, [b1])
    _, _, end = ref.train_steps(CFG, start, [b1, b2])
    params0 = {k: jnp.asarray(v) for k, v in start.items()
               if k[0] in "wb"}
    # step 5: the roots' rows hold their fresh layer-0 embeddings
    _, h0 = ref.forward(params0, b1["x0"], b1["x1"],
                        start["store0"][b1["neighbours"]], b1["y"], CFG)
    np.testing.assert_allclose(mid["store0"][[0, 1]], h0, rtol=1e-6)
    np.testing.assert_array_equal(mid["store0"][2:], start["store0"][2:])
    # step 4: the reads' gradients add up where a neighbour comes twice
    g = mid["grad_store0"]
    assert g[3].any() and g[2].any() and g[4].any()
    assert not g[[0, 1, 5, 6, 7, 8, 9]].any()
    # step 1 of the second batch clears row 3 before step 4 adds to it
    # again (3 is its own neighbour there), and leaves 2 and 4 alone
    np.testing.assert_array_equal(end["grad_store0"][[2, 4]], g[[2, 4]])
    assert not np.allclose(end["grad_store0"][3], g[3])
    assert end["grad_store0"][[5, 6, 7]].any(axis=1).all()
    # a root drawn twice: the later row is kept
    params1 = {k: jnp.asarray(mid[k]) for k in params0}
    _, h0 = ref.forward(params1, b2["x0"], b2["x1"],
                        mid["store0"][b2["neighbours"]], b2["y"], CFG)
    np.testing.assert_allclose(end["store0"][3], h0[1], rtol=1e-5)


def test_second_adam_sees_the_store_loss_alone(ref):
    """Step 3: nought while the stale gradients are nought (the first
    step), then the store loss's gradient, which reaches layer 0 only."""
    rng = np.random.default_rng(1)
    start, _ = _start(ref)
    b1 = _batch(rng, [0, 1], [2, 3, 3, 4])
    b2 = _batch(rng, [3, 2], [5, 6, 7, 8])
    _, _, mid = ref.train_steps(CFG, start, [b1])
    for k in mid:
        if k.startswith(ref.STORE_MU):
            assert not np.asarray(mid[k]).any(), k
    _, _, end = ref.train_steps(CFG, start, [b1, b2])
    moved = {k[len(ref.STORE_MU):] for k in end
             if k.startswith(ref.STORE_MU) and np.asarray(end[k]).any()}
    assert moved == {"w_self0", "w_neigh0"}


def test_both_adams_match_optax(ref):
    """Step 2 and step 3 on one batch with stale gradients in place:
    parameters moved by optax.adam(lr) on the loss's gradient, then by
    optax.adam(store_lr) on the store loss's, both taken at the old
    parameters."""
    import jax
    import jax.numpy as jnp
    import optax

    rng = np.random.default_rng(2)
    start, _ = _start(ref)
    start["grad_store0"] = rng.normal(size=(10, 4)).astype(np.float32)
    b = _batch(rng, [0, 1, 2], [3, 4, 5, 6, 7, 8])
    _, first, end = ref.train_steps(CFG, start, [b])
    p = {k: jnp.asarray(v) for k, v in start.items() if k[0] in "wb"}
    reads, stale = start["store0"][b["neighbours"]], start["grad_store0"][:3]
    g = jax.grad(lambda q: ref.forward(
        q, b["x0"], b["x1"], reads, b["y"], CFG)[0])(p)
    gs = jax.grad(lambda q: jnp.sum(ref.forward(
        q, b["x0"], b["x1"], reads, b["y"], CFG)[1] * stale))(p)
    for k in p:
        np.testing.assert_allclose(first[k], g[k], rtol=1e-5, atol=1e-7)
    main, second = optax.adam(0.03), optax.adam(0.001)
    up, _ = main.update(g, main.init(p), p)
    q = optax.apply_updates(p, up)
    up, s_state = second.update(gs, second.init(p), q)
    q = optax.apply_updates(q, up)
    for k in p:
        np.testing.assert_allclose(end[k], q[k], rtol=1e-4, atol=2e-6)
        np.testing.assert_allclose(end[ref.STORE_MU + k], s_state[0].mu[k],
                                   rtol=1e-5, atol=1e-8)


def test_reference_matches_the_programs_store_step(tmp_path, ref):
    """Three steps of the program's own jitted step (host-sampled
    batches, so no device draw is in the way) against the reference on
    the same ids, at the toy configuration's sizes."""
    import jax

    import euler_tpu
    from euler_tpu import run_loop
    from euler_tpu import train as train_lib

    with open(os.path.join(HERE, "toy", "toy_store.json")) as f:
        cfg = json.load(f)
    spec = graphgen.spec_from_config(cfg)
    data = spec.write(str(tmp_path / "g"))
    mod, attr = cfg["preset"]
    argv = list(getattr(__import__(mod, fromlist=[attr]), attr)) + [
        "--data_dir", data, "--device_features", "true",
        "--batch_size", str(cfg["batch_size"])]
    for k, v in cfg["flags"].items():
        argv += ["--" + k, str(v)]
    args = run_loop.define_flags().parse_args(argv)
    graph = euler_tpu.Graph(directory=data)
    model = run_loop.build_model(args, graph)
    opt = train_lib.get_optimizer("adam", cfg["learning_rate"])
    start, state = ref.init_state(cfg, jax.random.PRNGKey(3), opt)
    state["consts"] = model.build_consts(graph)
    step = jax.jit(model.make_train_step(opt))
    rng = np.random.default_rng(4)
    batches, losses = [], []
    for _ in range(3):
        roots = rng.choice(spec.num_nodes, cfg["batch_size"], replace=False)
        batch = model.sample(graph, roots)
        hops = ref.drawn_hops(model, state, batch)
        batches.append(ref.reference_batch(spec, hops))
        state, loss, _ = step(state, batch)
        losses.append(float(loss))
    start = {k: np.asarray(v) for k, v in start.items()}
    ref_losses, _, ref_end = ref.train_steps(cfg, start, batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    got = ref.compared_state(state)
    change = {k: got[k] - start[k] for k in got}
    ref_change = {k: np.asarray(ref_end[k]) - start[k] for k in got}
    assert np.linalg.norm(ref_change[ref.STORE_MU + "w_self0"]) > 0
    assert check.worst_leaf_gap(change, ref_change) < 1e-3
    np.testing.assert_allclose(got["store0"], ref_end["store0"],
                               rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(got["grad_store0"], ref_end["grad_store0"],
                               rtol=1e-3, atol=1e-6)
