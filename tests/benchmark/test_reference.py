"""The plain reference against the program's own train step (the step
``chip_smoke.py`` trains through ``train()``) at toy size on the CPU, and
its Adam against optax's."""

import json
import os

import numpy as np
import pytest

from benchmark import check, graphgen, sage_reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))


def _toy(name):
    with open(os.path.join(HERE, "toy", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["toy_reddit", "toy_ppi"])
def test_reference_step_matches_the_programs_step(tmp_path, name):
    import jax
    import jax.numpy as jnp

    import euler_tpu
    from euler_tpu import run_loop
    from euler_tpu import train as train_lib

    cfg = _toy(name)
    spec = graphgen.spec_from_config(cfg)
    data = spec.write(str(tmp_path / "g"))
    mod, attr = cfg["preset"]
    preset = list(getattr(__import__(mod, fromlist=[attr]), attr))
    argv = preset + ["--data_dir", data, "--device_features", "true",
                     "--batch_size", str(cfg["batch_size"])]
    for k, v in cfg["flags"].items():
        argv += ["--" + k, str(v)]
    args = run_loop.define_flags().parse_args(argv)
    graph = euler_tpu.Graph(directory=data)
    model = run_loop.build_model(args, graph)
    opt = train_lib.get_optimizer("adam", cfg["learning_rate"])
    params0 = ref.init_params(cfg, jax.random.PRNGKey(3))
    tree = ref.to_program(params0)
    state = {"params": tree, "opt_state": opt.init(tree),
             "consts": model.build_consts(graph)}
    step = jax.jit(model.make_train_step(opt))
    roots = np.arange(cfg["batch_size"], dtype=np.int64) * 7 % spec.num_nodes
    batches, losses = [], []
    for i in range(3):
        batch = model.sample(graph, roots + i)
        hops = [np.asarray(h["gids"]) for h in batch["hops"]]
        state, loss, _ = step(state, batch)
        losses.append(float(loss))
        batches.append(ref.reference_batch(spec, hops))
    ref_losses, ref_grad, ref_params = ref.train_steps(
        cfg, {k: jnp.asarray(v) for k, v in params0.items()}, batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    # Adam's first steps move an element by about lr whatever its
    # gradient's size, so an element whose gradient is nought to rounding
    # may differ; the leaves' changes agree by the benchmark's own measure
    got = ref.from_program(state["params"])
    change = {k: np.asarray(got[k]) - np.asarray(params0[k]) for k in got}
    ref_change = {k: np.asarray(ref_params[k]) - np.asarray(params0[k])
                  for k in got}
    assert check.worst_leaf_gap(change, ref_change) < 1e-3
    for k in got:
        assert np.mean(np.abs(change[k] - ref_change[k])) < 1e-4


def test_adam_matches_optax():
    import jax
    import jax.numpy as jnp
    import optax

    key = jax.random.PRNGKey(0)
    p = {"a": jax.random.normal(key, (5, 3)), "b": jnp.ones((3,))}
    opt = optax.adam(0.03)
    o_state = opt.init(p)
    mine, m_state = dict(p), ref.adam_init(p)
    for i in range(4):
        g = {k: jax.random.normal(jax.random.fold_in(key, i + 1), v.shape)
             for k, v in p.items()}
        up, o_state = opt.update(g, o_state, p)
        p = optax.apply_updates(p, up)
        mine, m_state = ref.adam_update(mine, g, m_state, 0.03)
    for k in p:
        np.testing.assert_allclose(np.asarray(mine[k]), np.asarray(p[k]),
                                   rtol=1e-4, atol=2e-6)


def test_graph_function_matches_what_the_engine_loads(tmp_path):
    import euler_tpu

    spec = graphgen.spec_from_config(_toy("toy_ppi"))
    g = euler_tpu.Graph(directory=spec.write(str(tmp_path / "g")))
    ids = np.array([0, 1, 17, spec.num_nodes - 1], dtype=np.int64)
    np.testing.assert_array_equal(
        g.get_dense_feature(ids, [1], [spec.feature_dim]), spec.features(ids))
    np.testing.assert_array_equal(
        g.get_dense_feature(ids, [0], [spec.label_dim]), spec.labels(ids))
    deg, slab = spec.degrees(ids), spec.neighbor_slab(ids)
    flat = g.get_full_neighbor(ids, [0])[0]
    want = np.concatenate([np.sort(r[:d]) for r, d in zip(slab, deg)])
    np.testing.assert_array_equal(np.sort(flat.reshape(-1)), np.sort(want))


def test_draw_numbers_by_hand():
    spec = graphgen.spec_from_config(_toy("toy_ppi"))
    parents = np.array([3, 4], dtype=np.int64)
    deg, slab = spec.degrees(parents), spec.neighbor_slab(parents)
    first = np.stack([slab[0, :2], slab[1, :2]])          # slots 0 and 1
    foreign, skew = check.draw_numbers(spec, [parents, first.reshape(-1)], [2])
    assert foreign == 0
    want = np.mean([(0.5) / deg[0], 1.5 / deg[0], 0.5 / deg[1], 1.5 / deg[1]])
    assert skew == pytest.approx(abs(want - 0.5))
    bad = first.copy()
    outsider = next(i for i in range(spec.num_nodes)
                    if i not in set(slab[0, :deg[0]].tolist()))
    bad[0, 0] = outsider
    foreign, _ = check.draw_numbers(spec, [parents, bad.reshape(-1)], [2])
    assert foreign == 1


def test_worst_leaf_gap_by_hand():
    refd = {"a": np.array([3.0, 4.0]), "b": np.array([0.0, 1e-6]),
            "c": np.array([1.0])}
    prog = {"a": np.array([3.0, 4.0]) * 1.01, "b": np.array([0.0, 2e-6]),
            "c": np.array([1.0])}
    # norms 5, 1e-6, 1; median 1: leaf a reads 0.05/5, leaf b 1e-6/1
    assert check.worst_leaf_gap(prog, refd) == pytest.approx(0.01)
    assert check.still_leaves(refd) == {"b"}
    # with leaf a dropped the median is of b and c: 1e-6 / 0.5000005
    assert check.worst_leaf_gap(prog, refd, drop={"a"}) == pytest.approx(
        2e-6, rel=1e-5)


def test_a_sampler_stuck_on_one_slot_reads_half():
    """draw_skew's upper reading: every pick the parent's first slot."""
    spec = graphgen.spec_from_config(_toy("toy_ppi"))
    parents = np.arange(2000, dtype=np.int64)
    first = spec.neighbor_slab(parents)[:, :1].repeat(3, axis=1)
    foreign, skew = check.draw_numbers(spec, [parents, first.reshape(-1)], [3])
    assert foreign == 0
    assert skew > 0.35       # mean quantile 0.5/degree, far from 0.5
    # and a uniform sampler over the same parents reads nought to noise
    rng = np.random.default_rng(0)
    deg, slab = spec.degrees(parents), spec.neighbor_slab(parents)
    cols = (rng.random((len(parents), 3)) * deg[:, None]).astype(np.int64)
    picks = np.take_along_axis(slab, cols, axis=1)
    _, skew = check.draw_numbers(spec, [parents, picks.reshape(-1)], [3])
    assert skew < 0.03
