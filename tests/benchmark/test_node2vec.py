"""The ``node2vec_ppi`` configuration's own files (its reference, its cost
function, its readers, its entries in BENCHMARK.json), on the CPU: the
reference by hand and against the program's bare step at a few hundred
nodes, device- and host-sampled; a walk that revisits a node; the toy
walk cell (``toy/toy_walk.json`` under ``BENCHMARK_toy_walk.json``: the
configuration's reference and cost function at 2,000 nodes and 16 roots,
every width and count the recipe's) through the harness and ``train()``;
the control and every planted fault coming out as not correct, the
negatives' faults (made from the pairs; a stuck sampler) by the leaf that
holds them against the node sampler alone; and a finished and a failed
run of ``benchmark/run.py`` leaving no process behind in their session.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import check, costs, graphgen, harness, manifest, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "node2vec_ppi.json")
TOY = os.path.join(HERE, "BENCHMARK_toy_walk.json")
TOY_CONFIG = os.path.join(HERE, "toy", "toy_walk.json")
CELL, TOY_CELL, TOY_HOST = (
    "node2vec_device_train", "toy_walk_device", "toy_walk_host")
NEW_METRICS = ("walk.scope_ms", "embed.pair_rows_ms",
               "embed.traffic_roofline", "optimizer.traffic_roofline")
NOT_THIS_CELLS = {"mesh.collective_ms", "step.store_read_ms",
                  "step.store_write_ms", "store.traffic_roofline",
                  "store.layout_copy_ms"}
NODES, BATCH = 2000, 16


def _cfg(path=CONFIG):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(
        os.path.join(ROOT, _cfg()["reference"]), "test_node2vec_reference")


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("toy_walk_data"))


def _run(data_root, cell, seed, **kw):
    return harness.run_cell(TOY, cell, seed, 0.2, False, time.time(),
                            require_chip=False, data_root=data_root, **kw)


# ---------------------------------------------------------------------------
# the files and the entries
# ---------------------------------------------------------------------------


def test_manifest_takes_the_configuration_as_files_and_entries():
    assert manifest.problems(MANIFEST) == []
    m = harness.load_json(MANIFEST)
    entry, cell = m["configs"][-1], m["workloads"][-1]
    assert entry["name"] == "node2vec_ppi" and entry["reduced"] == []
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "node2vec_ppi", "train_device_sampled", 1)
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "edges_per_s_chip"
        assert by_name[name]["source"] == "device_trace"
    assert [x["name"] for x in m["per_layer"][-4:]] == list(NEW_METRICS)
    assert by_name["walk.scope_ms"]["layer"] == "device_sampling"
    # every reader that serves the cell unchanged lists it, last
    silent = {n for n, x in by_name.items() if CELL not in x["workloads"]}
    assert silent == NOT_THIS_CELLS
    for name in set(by_name) - silent - set(NEW_METRICS):
        assert by_name[name]["workloads"][-1] == CELL


def test_the_store_family_keeps_its_entries():
    """What ``test_scalable_sage.py`` held of the manifest when its cell
    was the last one, of the manifest as it is now."""
    m = harness.load_json(MANIFEST)
    (entry,) = [c for c in m["configs"] if c["name"] == "scalable_sage_reddit"]
    (cell,) = [w for w in m["workloads"] if w["config"] == entry["name"]]
    assert m["configs"][-2] == entry and m["workloads"][-2] == cell
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in NOT_THIS_CELLS - {"mesh.collective_ms"}:
        assert by_name[name]["workloads"] == [cell["name"]]
    silent = {n for n, x in by_name.items()
              if cell["name"] not in x["workloads"]}
    assert silent == {"mesh.collective_ms"} | set(NEW_METRICS)


def test_reference_and_costs_bind_the_protocol(ref):
    cfg = _cfg()
    for key, functions in manifest.CONFIG_FILES.items():
        bound = manifest.bound_names(os.path.join(ROOT, cfg[key]))
        assert set(functions) <= bound, key
    for name in manifest.CONFIG_FILES["reference"]:
        assert callable(getattr(ref, name))
    # the benchmark's copy stands alone: nothing of the program
    for key in ("reference", "costs"):
        with open(os.path.join(ROOT, cfg[key])) as f:
            text = f.read()
        assert "import euler_tpu" not in text
        assert "from euler_tpu" not in text and "from benchmark" not in text


def test_configuration_states_the_recipe_in_flags_the_program_has():
    from euler_tpu import run_loop

    cfg = _cfg()
    assert cfg["reduced"] == [] and len(cfg["guarantees"]) >= 4
    assert (cfg["batch_size"], cfg["dim"], cfg["num_negs"], cfg["walk_len"],
            cfg["walk_p"], cfg["walk_q"], cfg["left_win_size"],
            cfg["right_win_size"], cfg["xent_loss"], cfg["optimizer"],
            cfg["learning_rate"]) == (
        512, 256, 5, 5, 1, 1, 5, 5, True, "adam", 0.01)
    flags = cfg["flags"]
    assert flags["model"] == "node2vec" and flags["all_edge_type"] == "0,1"
    for k in ("dim", "num_negs", "walk_len", "walk_p", "walk_q",
              "left_win_size", "right_win_size", "optimizer",
              "learning_rate"):
        assert flags[k] == cfg[k], k
    assert flags["max_id"] == cfg["graph"]["num_nodes"] - 1 == 2**20 - 1
    # every flag is one ``define_flags()`` knows, and the values are its
    # own defaults: the upstream recipe
    defaults = run_loop.define_flags().parse_args([])
    for k, v in flags.items():
        assert hasattr(defaults, k), k
        if k not in ("model", "max_id", "all_edge_type"):
            assert getattr(defaults, k) == (v == "true" if k == "xent_loss"
                                            else v), k
    # the graph function of the PPI cells under a seed of its own
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "graphsage_ppi.json")) as f:
        ppi = json.load(f)
    assert {k: cfg["graph"][k] for k in ("avg_degree", "max_degree")} == \
        {k: ppi["graph"][k] for k in ("avg_degree", "max_degree")}
    assert cfg["graph"]["graph_seed"] != ppi["graph"]["graph_seed"]
    assert (cfg["feature_dim"], cfg["label_dim"]) == (50, 121)
    assert "8.03 GB" in cfg["assumed"][0]
    # the toy copy cuts the node count, the batch and one limit alone
    toy = _cfg(TOY_CONFIG)
    for k in cfg:
        if k not in ("name", "flags", "batch_size", "graph", "assumed",
                     "why", "reference", "costs", "limits",
                     "limits_set_between"):
            assert toy[k] == cfg[k], k
    assert {k: v for k, v in toy["flags"].items() if k != "max_id"} == \
        {k: v for k, v in flags.items() if k != "max_id"}
    assert {k: v for k, v in toy["limits"].items() if k != "draw_skew"} == \
        {k: v for k, v in cfg["limits"].items() if k != "draw_skew"}


def test_costs_by_hand():
    """2,560 edges a step and chip; the numbers as literals."""
    c = costs.step_costs(_cfg(), 512, True)
    assert c["edges"] == 512 * 5 == 2_560
    # 30 pairs a root: 6 positions, 5 contexts each
    assert c["pair_rows"] == 512 * 30 * 7 == 107_520
    assert c["gather_bytes"] == 107_520 * 1024 == 110_100_480
    assert c["pair_row_bytes"] == 3 * 110_100_480 == 330_301_440
    assert c["params"] == 2 * 1_048_577 * 256 == 536_871_424
    assert c["opt_bytes"] == 7 * 536_871_424 * 4 == 15_032_399_872
    assert c["draw_bytes"] == 2_560 * 121 * 4 == 1_239_040
    assert c["neg_bytes"] == 76_800 * 23 * 4 == 7_065_600
    assert c["bytes"] == 15_032_399_872 + 330_301_440 + 1_239_040 \
        + 7_065_600 == 15_371_005_952
    assert c["flops"] == 6 * 256 * 15_360 * 6 + 12 * 536_871_424 \
        == 6_584_014_848
    host = costs.step_costs(_cfg(), 512, False)
    assert host["draw_bytes"] == host["neg_bytes"] == 0
    assert host["bytes"] == 15_032_399_872 + 330_301_440 + 107_520 * 4
    assert set(costs.REQUIRED) <= set(c)
    # bandwidth bounds the step: 18.8 ms at 819 GB/s against 33 us of FLOPs
    assert c["bytes"] / 819e9 > 500 * c["flops"] / 197e12


def test_every_program_scope_keeps_exactly_one_reader():
    from euler_tpu import trace as TR

    claimed = scopes.declared_scopes()
    assert {"walk", "negatives", "pair_rows"} <= set(TR.STEP_SCOPES)
    assert set(TR.STEP_SCOPES) == set(claimed)
    assert claimed["walk"] == claimed["negatives"] == "walk.scope_ms"
    assert claimed["pair_rows"] == "embed.pair_rows_ms"
    path = "jit(train_step)/transpose(jvp(_ShallowUnsupModule))/pair_rows/" \
        "target/Embedding_0/scatter-add"
    assert scopes.scope_of_op_name(path) == "pair_rows"
    # the draws inside the walk are the walk's
    path = "jit(train_step)/jvp(_ShallowUnsupModule)/walk/pallas_call"
    assert scopes.scope_of_op_name(path) == "walk"


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmark", "layers", name + ".py"),
        "test_layer_" + name.replace(".", "_"))


def test_new_readers_on_scope_times_and_on_a_program_without_them():
    c = costs.step_costs(_cfg(), 512, True)
    ctx = harness.Context(costs=c, peaks={"hbm_bytes_per_s": 819e9},
                          xplane_path=None)
    ctx._scope_ms = {"walk": 0.25, "negatives": 0.15, "pair_rows": 2.0,
                     "optimizer": 20.0, "loss": 0.1}
    assert _reader("walk.scope_ms").read(ctx) == pytest.approx(0.40)
    assert _reader("embed.pair_rows_ms").read(ctx) == pytest.approx(2.0)
    # 330.3 MB at 819 GB/s is 0.4033 ms of 2; 15.03 GB is 18.35 ms of 20
    assert _reader("embed.traffic_roofline").read(ctx) == pytest.approx(
        100 * 330_301_440 / 819e9 / 2.0e-3)
    assert _reader("optimizer.traffic_roofline").read(ctx) == pytest.approx(
        100 * 15_032_399_872 / 819e9 / 20.0e-3)
    assert _reader("optimizer.traffic_roofline").read(ctx) < 100
    # the identity: the scope metrics and the unscoped rest add up
    ctx._scope_ms["unscoped"] = 0.05
    parts = [_reader(n).read(ctx) for n in (
        "draw.scope_ms", "walk.scope_ms", "embed.pair_rows_ms",
        "step.gather_ms", "step.dense_ms", "step.optimizer_ms",
        "step.unscoped_ms")]
    assert sum(parts) == pytest.approx(sum(ctx._scope_ms.values()))
    # the parent names none of the three scopes (its optimizer scope is
    # there); a CPU run has no capture; a family without pair rows
    # counts none: silent, and no error
    old = harness.Context(costs=c, peaks={"hbm_bytes_per_s": 819e9},
                          xplane_path=None)
    old._scope_ms = {"loss": 0.1, "optimizer": 20.0, "unscoped": 3.0}
    cpu = harness.Context(costs=c, peaks=None, xplane_path=None)
    sage = harness.Context(costs={"opt_bytes": 0.0},
                           peaks={"hbm_bytes_per_s": 819e9},
                           xplane_path=None)
    sage._scope_ms = dict(ctx._scope_ms)
    for name in NEW_METRICS:
        assert _reader(name).read(cpu) is None, name
        if name != "optimizer.traffic_roofline":
            assert _reader(name).read(old) is None, name
    assert _reader("optimizer.traffic_roofline").read(old) > 0
    assert _reader("embed.traffic_roofline").read(sage) is None
    assert _reader("optimizer.traffic_roofline").read(sage) is None


# ---------------------------------------------------------------------------
# the reference by hand
# ---------------------------------------------------------------------------


def test_window_rule_by_hand_and_as_the_program_enumerates(ref):
    from euler_tpu import ops

    tgt, ctx = ref.pair_positions(4, 2, 1)
    # position 0: right 1; 1: left 0, right 2; 2: left 1, 0, right 3;
    # 3: left 2, 1
    assert list(zip(tgt, ctx)) == [
        (0, 1), (1, 0), (1, 2), (2, 1), (2, 0), (2, 3), (3, 2), (3, 1)]
    for shape in ((6, 5, 5), (6, 1, 1), (4, 2, 1), (3, 0, 2)):
        ti, ci = ops.walk.pair_indices(*shape)
        tgt, ctx = ref.pair_positions(*shape)
        assert list(ti) == list(tgt) and list(ci) == list(ctx)
    cfg = _cfg()
    cost = harness.load_module(os.path.join(ROOT, cfg["costs"]), "n2v_costs")
    assert len(ref.pair_positions(6, 5, 5)[0]) == cost.pair_count(6, 5, 5) \
        == ops.walk.pair_count(6, 5, 5) == 30
    assert cost.pair_count(4, 2, 1) == 8
    paths = np.array([[7, 8, 9, 7, 5, 6]])
    src, pos = ref.pairs_of(cfg, paths)
    assert list(src[:5]) == [7] * 5 and list(pos[:5]) == [8, 9, 7, 5, 6]
    assert list(src[5:10]) == [8] * 5 and list(pos[5:10]) == [7, 9, 7, 5, 6]


def test_pair_loss_and_adam_by_hand(ref):
    import jax.numpy as jnp
    import optax

    src = jnp.array([[1.0, 0.0], [0.0, 2.0]])
    pos = jnp.array([[2.0, 5.0], [1.0, -1.0]])
    neg = jnp.array([[[0.0, 3.0], [-1.0, 0.0]], [[0.0, 0.0], [4.0, 0.5]]])
    # logits: positives 2, -2; negatives 0, -1 and 0, 1
    sp = lambda x: np.log1p(np.exp(x))           # noqa: E731
    want = sp(-2.0) + sp(2.0) + sp(0.0) + sp(-1.0) + sp(0.0) + sp(1.0)
    assert float(ref.pair_loss(src, pos, neg)) == pytest.approx(want, 1e-6)
    # the written-out Adam against optax's, three steps, rows with a
    # gradient of nought standing still
    rng = np.random.default_rng(0)
    p = rng.normal(size=(5, 4)).astype(np.float32)
    p0 = p.copy()
    m, v = np.zeros_like(p), np.zeros_like(p)
    opt = optax.adam(0.01)
    q, state = jnp.asarray(p), opt.init(jnp.asarray(p))
    for t in (1, 2, 3):
        g = rng.normal(size=(5, 4)).astype(np.float32)
        g[3:] = 0.0
        if t > 1:
            g[1] = 0.0      # named at step 1 only: it keeps moving
        p, m, v = ref.adam_rows(p, g, m, v, t, 0.01)
        updates, state = opt.update(jnp.asarray(g), state)
        q = optax.apply_updates(q, updates)
    p, m = np.asarray(p), np.asarray(m)
    np.testing.assert_allclose(p, np.asarray(q), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(m, np.asarray(state[0].mu), rtol=1e-5)
    np.testing.assert_array_equal(p[3:], p0[3:])
    assert abs(p[1] - p0[1]).min() > 0.015      # three moves, not one
    # the lazy variant moves the named rows alone
    zero = np.zeros_like(p0)
    lazy, lm, lv = (np.asarray(a) for a in ref.adam_rows(
        p0, np.ones_like(p0), zero, zero, 1, 0.01,
        np.array([1, 0, 0, 0, 0], bool)))
    assert (lazy[1:] == p0[1:]).all() and not lm[1:].any()
    np.testing.assert_allclose(lazy[0], p0[0] - 0.01, rtol=1e-5)
    assert lv[0].all() and not lv[1:].any()


def test_rows_start_as_a_function_of_key_and_row(ref):
    import jax

    key = jax.random.PRNGKey(3)
    whole = np.asarray(ref.init_rows(256, key, np.arange(50)))
    some = np.asarray(ref.init_rows(256, key, np.array([7, 49, 7])))
    np.testing.assert_array_equal(some, whole[[7, 49, 7]])
    assert abs(whole).max() <= 0.2 and 0.08 < whole.std() < 0.095
    other = np.asarray(ref.init_rows(256, ref.table_key(key, "context"),
                                     np.arange(50)))
    assert not np.allclose(whole, other)


# ---------------------------------------------------------------------------
# the reference against the program's bare step
# ---------------------------------------------------------------------------


def _model(tmp_path, device_sampling):
    import euler_tpu
    from euler_tpu import run_loop

    cfg = _cfg(TOY_CONFIG)
    spec = graphgen.spec_from_config(cfg)
    data = spec.write(str(tmp_path / "g"))
    mod, attr = cfg["preset"]
    argv = list(getattr(__import__(mod, fromlist=[attr]), attr)) + [
        "--data_dir", data,
        "--device_sampling", str(device_sampling).lower(),
        "--batch_size", str(cfg["batch_size"])]
    for k, v in cfg["flags"].items():
        argv += ["--" + k, str(v)]
    args = run_loop.define_flags().parse_args(argv)
    graph = euler_tpu.Graph(directory=data)
    return cfg, spec, graph, run_loop.build_model(args, graph)


def _tables(state, ref):
    import jax

    got = jax.device_get({"p": state["params"],
                          "m": state["opt_state"][0].mu,
                          "v": state["opt_state"][0].nu})
    return {k: {t: np.asarray(a) for t, a in ref.from_program(v).items()}
            for k, v in got.items()}


def _assert_steps_close(got, want, name):
    """Parameters after Adam steps, element by element. An Adam step is
    lr * m / sqrt(v), of lr's size whatever the gradient's: where a
    gradient element is nought to rounding, its step's direction is
    rounding too. So: all but one element in ten thousand to 1e-4, and
    none further off than the three steps it can have gone astray."""
    off = np.abs(got - want) > 1e-4 + 1e-3 * np.abs(want)
    assert off.mean() < 1e-4, (name, int(off.sum()), off.size)
    assert np.abs(got - want).max() <= 3 * 2 * 0.01, name


def _assert_close_after(ref, cfg, key, state, loss, result):
    """Every row the program's step left against the reference's: the
    rows the steps named element by element, every other row against its
    start, bit by bit."""
    import jax

    r_loss, _, p, m, v, named = result
    assert float(loss) == pytest.approx(r_loss, rel=2e-6)
    got = _tables(state, ref)
    rows = cfg["graph"]["num_nodes"] + 1
    outside = np.setdiff1d(np.arange(rows), named)
    assert len(outside) > 0
    for t in ref.TABLES:
        _assert_steps_close(got["p"][t][named], p[t], t)
        np.testing.assert_allclose(got["m"][t][named], m[t], rtol=1e-3,
                                   atol=1e-7, err_msg=t)
        np.testing.assert_allclose(got["v"][t][named], v[t], rtol=2e-3,
                                   atol=1e-12, err_msg=t)
        was = np.asarray(ref.init_rows(
            cfg["dim"], ref.table_key(key, t), outside))
        np.testing.assert_array_equal(got["p"][t][outside], was)
        assert not got["m"][t][outside].any()
        assert not got["v"][t][outside].any()


@pytest.mark.parametrize("device_sampling", [False, True],
                         ids=["host_sampled", "device_sampled"])
def test_bare_step_against_the_reference_after_one_and_three_steps(
        tmp_path, ref, device_sampling):
    """``make_train_step`` jitted alone, three steps of random roots:
    loss, first gradient, both tables and both moments of both, after
    step 1 and after step 3; the row leaves and the count of rows
    outside as the cell compares them. At 2,000 nodes roots
    share nodes and negatives repeat, so the scatter has shares to add;
    rows named at step 1 alone keep moving at steps 2 and 3."""
    import jax

    from euler_tpu import train as train_lib

    cfg, spec, graph, model = _model(tmp_path, device_sampling)
    assert model.device_sampling == device_sampling
    opt = train_lib.get_optimizer("adam", cfg["learning_rate"])
    key = jax.random.PRNGKey(3)
    start, state = ref.init_state(cfg, key, opt)
    assert set(start) == {"key", ref.TWICE, ref.OUTSIDE, ref.SAMPLER} | {
        pre + t for pre in ("", "mu_") for t in (
            "target/walk", "context/walk", "context/negs")}
    state["consts"] = model.build_consts(graph)
    step = jax.jit(model.make_train_step(opt))
    rng = np.random.default_rng(4)
    batches, after = [], []
    for i in range(3):
        batch = model.sample(graph, rng.integers(0, NODES, BATCH))
        assert ("roots" in batch) == device_sampling
        hops = ref.drawn_hops(model, state, batch)
        assert len(hops) == 7 and len(hops[0]) == BATCH
        assert len(hops[-1]) == BATCH * 30 * 5
        foreign, _ = check.draw_numbers(spec, hops, ref.drawn_fanouts(cfg))
        assert foreign == 0
        batches.append(ref.reference_batch(spec, hops))
        state, loss, _ = step(state, batch)
        if i == 0:
            first = ref.first_gradient(state)
        after.append((jax.tree_util.tree_map(np.asarray, {
            k: state[k] for k in ("params", "opt_state")}), loss))
    results = [(r[0],) + tuple({t: np.asarray(a) for t, a in d.items()}
                               for d in r[1:5]) + (r[5],)
               for r in ref.follow(cfg, key, batches)]
    # the scatter had shares to add, and a row sat out a later step
    b0 = batches[0]
    assert len(np.unique(b0["negs"])) < len(b0["negs"])
    assert np.setdiff1d(b0["paths"], np.r_[
        batches[1]["paths"].ravel(), batches[1]["negs"]]).size
    walk1, negs1 = ref.named_ids(batches[:1])
    g1, named = results[0][1], results[0][5]
    for name, t, ids in (("target/walk", "target", walk1),
                         ("context/walk", "context", walk1),
                         ("context/negs", "context", negs1)):
        np.testing.assert_allclose(
            first[name], g1[t][np.searchsorted(named, ids)],
            rtol=1e-4, atol=1e-6, err_msg=name)
    for i in (0, 2):
        _assert_close_after(ref, cfg, key, after[i][0], after[i][1],
                            results[i])
    # the leaves as the cell compares them
    got = ref.compared_state(state)
    losses, grad, end = ref.train_steps(
        cfg, {"key": np.asarray(key)}, batches)
    assert set(got) == set(end) == set(start) - {"key"}
    assert got[ref.OUTSIDE][0] == 0 and end[ref.OUTSIDE][0] == 0
    assert got[ref.SAMPLER][0] == 0 and end[ref.SAMPLER][0] == 0
    assert len(got[ref.TWICE]) > 0
    for k in end:
        assert got[k].shape == end[k].shape, k
        if k.startswith("mu_"):
            np.testing.assert_allclose(got[k], end[k], rtol=1e-3, atol=1e-7,
                                       err_msg=k)
        else:
            _assert_steps_close(got[k], end[k], k)
    assert set(grad) == set(first)
    assert losses == [r[0] for r in results]


def _hand_batch(model, graph, ref, cfg, paths, negs):
    """A batch as the host sampler hands it over, on chosen walks."""
    src, pos = ref.pairs_of(cfg, np.asarray(paths))
    return model._pack(graph, src, pos, np.asarray(negs))


def test_a_walk_that_revisits_a_node_collects_every_share(tmp_path, ref):
    """Root 5 walks 5 9 5 9 5 7: node 5 is the source of 15 of the 30
    pairs and the context of 15; negatives name 5 and 9 too, and one id
    twice. Every share is added, by hand and in the program."""
    import jax

    from euler_tpu import train as train_lib

    cfg, spec, graph, model = _model(tmp_path, False)
    opt = train_lib.get_optimizer("adam", cfg["learning_rate"])
    key = jax.random.PRNGKey(6)
    _, state = ref.init_state(cfg, key, opt)
    state["consts"] = model.build_consts(graph)
    paths = np.array([[5, 9, 5, 9, 5, 7], [20, 21, 22, 23, 24, 25]])
    rng = np.random.default_rng(7)
    negs = rng.permutation(np.arange(100, 500))[:2 * 30 * 5]
    negs[:4] = [5, 9, 77, 77]
    batch = _hand_batch(model, graph, ref, cfg, paths, negs)
    hops = ref.drawn_hops(model, state, batch)
    np.testing.assert_array_equal(np.stack(hops[:-1], 1), paths)
    np.testing.assert_array_equal(hops[-1], negs)
    state, loss, _ = jax.jit(model.make_train_step(opt))(state, batch)
    (result,) = list(ref.follow(cfg, key, [ref.reference_batch(spec, hops)]))
    _assert_close_after(ref, cfg, key, state, loss, result)
    # by hand: the gradient rows of node 5
    src, pos = ref.pairs_of(cfg, paths)
    assert (src == 5).sum() == 15 and (pos == 5).sum() == 15
    start = {t: np.asarray(ref.init_rows(256, ref.table_key(key, t),
                                         np.arange(NODES + 1)))
             for t in ref.TABLES}
    sig = lambda x: 1.0 / (1.0 + np.exp(-x))     # noqa: E731
    want_t = np.zeros(256)
    want_c = np.zeros(256)
    neg = negs.reshape(-1, 5)
    for j in range(len(src)):
        e = start["target"][src[j]].astype(np.float64)
        if src[j] == 5:
            c = start["context"][pos[j]]
            want_t += (sig(e @ c) - 1.0) * c
            for n in neg[j]:
                want_t += sig(e @ start["context"][n]) * start["context"][n]
        if pos[j] == 5:
            want_c += (sig(e @ start["context"][5]) - 1.0) * e
        for n in neg[j]:
            if n == 5:
                want_c += sig(e @ start["context"][5]) * e
    first = ref.first_gradient(state)
    walk, _ = ref.named_ids(ref._run["hops"][-1:])
    at5 = int(np.searchsorted(walk, 5))
    np.testing.assert_allclose(first["target/walk"][at5], want_t,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(first["context/walk"][at5], want_c,
                               rtol=1e-4, atol=1e-6)
    # 77 was a negative twice in one step: the leaf holds its moment
    got = ref.compared_state(state)
    assert got[ref.TWICE].shape == (1, 256) and got[ref.OUTSIDE][0] == 0
    # an overwriting scatter reads another gradient there
    (wrong,) = list(ref.follow(cfg, key, [ref.reference_batch(spec, hops)],
                               fault="duplicates_overwritten"))
    named = result[5]
    assert not np.allclose(wrong[1]["target"][np.searchsorted(named, 5)],
                           want_t, rtol=1e-2)


def test_one_row_outside_the_steps_fails_the_cell(tmp_path, ref):
    """The exact statement, on the device: one bit of one row that no
    step named, in a table or in a first moment."""
    import jax
    import jax.numpy as jnp

    from euler_tpu import train as train_lib

    cfg, spec, graph, model = _model(tmp_path, False)
    opt = train_lib.get_optimizer("adam", cfg["learning_rate"])
    _, state = ref.init_state(cfg, jax.random.PRNGKey(8), opt)
    paths = np.array([[1, 2, 3, 4, 5, 6]])
    negs = np.arange(10, 160)
    ref.drawn_hops(model, state, _hand_batch(model, graph, ref, cfg, paths,
                                             negs))
    assert ref.compared_state(state)[ref.OUTSIDE][0] == 0

    def touched(state, where, table, row):
        tree = state["opt_state"][0].mu if where == "mu" else state["params"]
        leaf = tree[table]["Embedding_0"]["embeddings"]
        # (a moment starts at nought, and the step next to nought is
        # flushed to it)
        bumped = jnp.float32(1e-6) if where == "mu" else jnp.nextafter(
            leaf[row, 3], jnp.float32(1.0))
        new = {t: {"Embedding_0": {"embeddings": (
            leaf.at[row, 3].set(bumped) if t == table
            else tree[t]["Embedding_0"]["embeddings"])}} for t in ref.TABLES}
        if where == "mu":
            opt0 = state["opt_state"][0]._replace(mu=new)
            return dict(state, opt_state=(opt0,) + state["opt_state"][1:])
        return dict(state, params=new)

    # row 300 no step named; row 50 is a negative: the target table does
    # not name it, the context table does
    for where, table, row, want in (
            ("p", "target", 300, 1), ("p", "context", 300, 1),
            ("mu", "context", 300, 1), ("p", "target", 50, 1),
            ("p", "context", 50, 0), ("p", "target", 3, 0)):
        got = ref.compared_state(touched(state, where, table, row))
        assert got[ref.OUTSIDE][0] == want, (where, table, row)
    # ...and one such row fails the cell whatever else agrees
    ok = {"w": np.ones(4), ref.OUTSIDE: np.zeros(1)}
    bad = dict(ok, **{ref.OUTSIDE: np.ones(1)})
    assert check.worst_leaf_gap(bad, ok) >= 0.5


def test_negatives_are_held_against_the_node_sampler_by_hand(ref):
    """``sampler_z`` / ``off_sampler``: independent uniform draws read
    within a few standard deviations; ids out of range, a sampler that
    favours low ids, one stuck on a few nodes, a permutation and
    negatives made from the step's own pairs each read far outside."""
    cfg = dict(_cfg(TOY_CONFIG))
    n_nodes, rng = cfg["graph"]["num_nodes"], np.random.default_rng(5)
    paths = rng.integers(0, n_nodes, (BATCH, 6))
    n = BATCH * 30 * cfg["num_negs"]
    worst = {}
    for _ in range(200):
        z = ref.sampler_z(n_nodes, paths, rng.integers(0, n_nodes, n))
        assert z.pop("foreign") == 0
        worst = {k: max(abs(v), worst.get(k, 0)) for k, v in z.items()}
    # 600 readings of unit variance: the largest of them, and no bias in
    # the means the counts are held against
    assert all(2.0 < v < 4.5 for v in worst.values()), worst
    # by hand: 3 draws over 4 nodes; the walk named nodes 0 and 1
    z = ref.sampler_z(4, np.array([[0, 1, 1]]), np.array([0, 0, 3]))
    assert z["foreign"] == 0
    assert z["skew"] == pytest.approx(((0.125 + 0.125 + 0.875) / 3 - 0.5) * 6)
    # distinct: mean 4 (1 - (3/4)^3) = 2.3125, variance
    # 4 (27/64) + 12 (1/8) - (27/16)^2 = 0.33984375
    assert z["distinct"] == pytest.approx((2 - 2.3125) / 0.33984375 ** 0.5)
    # two of three are walk ids; binomial(3, 1/2)
    assert z["walk"] == pytest.approx((2 - 1.5) / 0.75 ** 0.5)

    def off(negs):
        return float(ref.off_sampler(cfg, [{"paths": paths, "negs": negs}])[0])

    sound = rng.integers(0, n_nodes, n)
    assert off(sound) == 0
    assert off(np.concatenate([sound[:-2], [n_nodes, -1]])) == 2  # the range
    assert off(rng.integers(0, n_nodes // 2, n)) >= 1           # low ids
    assert off(rng.integers(0, 8, n) * 250) >= 1                # stuck
    assert off(rng.permutation(n_nodes)[np.arange(n) % n_nodes]) >= 1
    from_pairs = ref.step_ids(
        cfg, {"paths": paths, "negs": sound}, "negatives_from_pairs")[2]
    assert off(from_pairs) >= 1
    # a reference in the program's place is judged as the program is
    start = {"key": np.asarray([0, 9], np.uint32)}
    batches = [{"paths": paths, "negs": sound}]
    assert ref.train_steps(cfg, start, batches)[2][ref.SAMPLER][0] == 0
    assert ref.train_steps(cfg, start, batches,
                           fault="negatives_from_pairs")[2][ref.SAMPLER][0] >= 1
    # ...and one statistic off fails the cell whatever else agrees
    ok = {"w": np.ones(4), ref.SAMPLER: np.zeros(1)}
    assert check.worst_leaf_gap(dict(ok, **{ref.SAMPLER: np.ones(1)}), ok) \
        >= 0.5


# ---------------------------------------------------------------------------
# the cell's files through the harness and train(), at the toy node count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", [TOY_CELL, TOY_HOST])
def test_cell_files_end_to_end_through_train(data_root, cell):
    r = _run(data_root, cell, seed=2**31 + 77)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"] == {}           # a CPU run is no measurement
    assert set(r["compared"]) == {
        "draw_foreign", "draw_skew", "loss_gap", "grad_gap", "change_gap",
        "compiles_in_window"}


def test_traced_toy_run_finds_the_readers(data_root, tmp_path):
    r = harness.run_cell(TOY, TOY_CELL, 7, 0.2, True, time.time(),
                         require_chip=False, data_root=data_root,
                         keep_trace=str(tmp_path / "trace"))
    assert r["correct"] is True and r["metrics"] == {}
    # the device readers are silent without a device trace
    assert not set(NEW_METRICS) & set(r["withheld_cpu"])
    assert "trainer.step_ms_p50" in r["withheld_cpu"]
    # the compiled step's text names the three scopes
    text = (tmp_path / "trace" / scopes.STEP_HLO_FILE).read_text()
    table = scopes.parse_hlo_scopes(text)
    assert {"walk", "negatives", "pair_rows", "loss", "optimizer"} <= \
        set(table.values())


def test_control_and_half_batch_fail_the_limits(data_root):
    r = _run(data_root, TOY_CELL, seed=11, calibrate=True)
    assert r["correct"] is True, r["compared"]
    limits = harness.Cell(TOY, TOY_CELL).cfg["limits"]
    for name in ("control_bf16", "fault_half_batch"):
        ok, table = check.verdict(r["calibration"][name], limits)
        assert not ok, (name, table)


# ---------------------------------------------------------------------------
# the faults: in the reference, put in the program's place (as the chip's
# calibration plants them), and in the program's own step
# ---------------------------------------------------------------------------

FAULTS = ("duplicates_overwritten", "context_table_is_target",
          "negatives_from_pairs", "window_one_sided", "adam_rows_only")


@pytest.fixture(scope="module")
def planted(data_root):
    """One sound run of the toy cell, and the reference with each fault
    in the program's place by the cell's numbers."""
    faults = harness.load_module(
        os.path.join(ROOT, "benchmark", "configs",
                     "scalable_sage_reddit_faults.py"), "test_walk_faults")
    prep = harness.Prepared(TOY, TOY_CELL, time.time(), require_chip=False,
                            data_root=data_root)
    try:
        hook = prep.drive(2**31 + 21, 0.0, first_steps_only=True)
        sound = prep.compare(hook)
        return prep.cfg["limits"], sound, faults.fault_numbers(prep, hook)
    finally:
        prep.close()


def test_reference_names_the_faults(ref):
    assert ref.FAULTS == FAULTS


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_planted_in_the_reference_is_not_correct(planted, fault):
    limits, sound, numbers = planted
    assert check.verdict(sound, limits)[0], sound
    ok, table = check.verdict(numbers["fault_" + fault], limits)
    assert not ok, table


def _patch_step(monkeypatch, wrap):
    from euler_tpu.models import base

    orig = base.Model.make_train_step

    def make(self, optimizer):
        return wrap(self, orig(self, optimizer))

    monkeypatch.setattr(base.Model, "make_train_step", make)


def _adam_rows_only(monkeypatch):
    """The lazy update: a row this step's gradient does not name keeps
    its parameters and both moments."""
    def wrap(model, step):
        def broken(state, batch):
            import jax
            import jax.numpy as jnp

            new, loss, metric = step(state, batch)
            old_mu = state["opt_state"][0].mu
            # a row is named where its first moment left the decay's path
            named = jax.tree_util.tree_map(
                lambda a, b: (b != 0.9 * a).any(axis=1, keepdims=True),
                old_mu, new["opt_state"][0].mu)
            keep = lambda n, a, b: jnp.where(n, b, a)    # noqa: E731
            opt0 = new["opt_state"][0]._replace(
                mu=jax.tree_util.tree_map(
                    keep, named, old_mu, new["opt_state"][0].mu),
                nu=jax.tree_util.tree_map(
                    keep, named, state["opt_state"][0].nu,
                    new["opt_state"][0].nu))
            params = jax.tree_util.tree_map(
                keep, named, state["params"], new["params"])
            return dict(new, params=params, opt_state=(
                opt0,) + tuple(new["opt_state"][1:])), loss, metric

        return broken
    _patch_step(monkeypatch, wrap)


def _context_table_is_target(monkeypatch):
    from euler_tpu.models import shallow

    monkeypatch.setattr(shallow._ShallowUnsupModule, "_context",
                        lambda self, x: self.target(x))


def _window_one_sided(monkeypatch):
    from euler_tpu import ops

    both = ops.walk.pair_indices

    def right_only(path_len, left_win, right_win):
        ti, ci = both(path_len, left_win, right_win)
        # as many pairs as before (the shapes stay), all to the right
        rt, rc = both(path_len, 0, right_win)
        reps = -(-len(ti) // len(rt))
        return (np.tile(rt, reps)[:len(ti)], np.tile(rc, reps)[:len(ti)])

    monkeypatch.setattr(ops.walk, "pair_indices", right_only)


def _duplicates_overwritten(monkeypatch):
    """The gather's transpose sets where it must add."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from euler_tpu.nn import encoders, layers

    @jax.custom_vjp
    def take(table, ids):
        return table[ids]

    def fwd(table, ids):
        return table[ids], (table.shape, ids)

    def bwd(res, g):
        shape, ids = res
        return jnp.zeros(shape, g.dtype).at[ids].set(g), None

    take.defvjp(fwd, bwd)

    class Embedding(layers.Embedding):     # the name is the parameter's path
        @nn.compact
        def __call__(self, ids):
            table = self.param(
                "embeddings",
                nn.initializers.truncated_normal(stddev=self.stddev),
                (self.num, self.dim))
            return take(table, jnp.clip(ids, 0, self.num - 1))

    monkeypatch.setattr(encoders, "Embedding", Embedding)


def _negatives_from_pairs(monkeypatch):
    """No draw from the sampler: a pair's negatives are the contexts of
    the pairs after it. The step and the ids the comparison is handed
    come from the same ``_inputs``, so the reference follows the program
    and every gap but the sampler leaf's reads nought."""
    import jax.numpy as jnp

    from euler_tpu.models import shallow

    drawn = shallow._ShallowUnsupModule._inputs

    def inputs(self, batch, consts):
        src, pos, _ = drawn(self, batch, consts)
        negs = jnp.stack([jnp.roll(pos["ids"], -(i + 1))
                          for i in range(self.num_negs)], axis=1).reshape(-1)
        return src, pos, self._feats(negs)

    monkeypatch.setattr(shallow._ShallowUnsupModule, "_inputs", inputs)


def _negatives_sampler_stuck(monkeypatch):
    """The global sampler draws from its first 64 nodes alone."""
    from euler_tpu.graph import device as device_graph

    drawn = device_graph.sample_node

    def stuck(sampler, key, count):
        return drawn(sampler, key, count) % 64

    monkeypatch.setattr(device_graph, "sample_node", stuck)


@pytest.mark.parametrize("plant", [
    _adam_rows_only, _context_table_is_target, _window_one_sided,
    _duplicates_overwritten, _negatives_from_pairs, _negatives_sampler_stuck,
], ids=["adam_rows_only", "context_table_is_target", "window_one_sided",
        "duplicates_overwritten", "negatives_from_pairs",
        "negatives_sampler_stuck"])
def test_fault_planted_in_the_program_is_not_correct(
        data_root, monkeypatch, plant):
    plant(monkeypatch)
    r = _run(data_root, TOY_CELL, seed=2**31 + 5)
    assert r["correct"] is False, r["compared"]
    if plant in (_negatives_from_pairs, _negatives_sampler_stuck):
        # nothing but the sampler leaf sees it: the reference is handed
        # the program's negatives
        gaps = {k: v["value"] for k, v in r["compared"].items()}
        assert gaps.pop("change_gap") >= 0.5
        assert all(v <= r["compared"][k]["limit"] for k, v in gaps.items())


# ---------------------------------------------------------------------------
# the embedding tables' gauges and route-log line
# ---------------------------------------------------------------------------


def test_train_says_what_the_device_made_of_the_embedding_tables(
        data_root, caplog):
    import logging

    from euler_tpu import telemetry

    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        r = _run(data_root, TOY_CELL, seed=3)
    assert r["correct"] is True
    lines = [rec.getMessage() for rec in caplog.records
             if rec.getMessage().startswith("embedding table:")]
    assert len(lines) == 1, lines
    assert "[2001, 256] float32 x 2" in lines[0]
    assert "rows contiguous, stored 256 wide" in lines[0]
    resource = telemetry.telemetry_json()["resource"]
    assert resource["store_table_width"] == 256
    assert resource["store_table_stored_width"] == 256


# ---------------------------------------------------------------------------
# a run leaves no process behind: finished, failed inside train(), and
# refused at load on a program from before the cell
# ---------------------------------------------------------------------------

CHILD = r"""
import functools, os, runpy, sys
sys.path.insert(0, {root!r})
os.chdir({root!r})
from benchmark import harness
harness.run_cell = functools.partial(
    harness.run_cell, require_chip=False, data_root={data!r})
if {fail!r} == "before_the_cell":
    # the parent commit's program: --model node2vec, no walk scopes.
    # Struck when the module is first loaded, whoever asks for it: the
    # harness has not loaded euler_tpu.trace by the time it loads the
    # reference, and the test must not load it in its place
    import importlib.abc, importlib.util
    class Strike(importlib.abc.MetaPathFinder):
        def find_spec(self, name, path, target=None):
            if name != "euler_tpu.trace":
                return None
            sys.meta_path.remove(self)
            spec = importlib.util.find_spec(name)
            run = spec.loader.exec_module
            def struck(module):
                run(module)
                module.STEP_SCOPES = tuple(
                    s for s in module.STEP_SCOPES
                    if s not in ("walk", "negatives", "pair_rows"))
            spec.loader.exec_module = struck
            return spec
    sys.meta_path.insert(0, Strike())
elif {fail!r}:
    capture = harness.Hook._capture
    def failing(self, step, loc):
        if step == 3:
            raise RuntimeError("planted: the program raised inside train()")
        return capture(self, step, loc)
    harness.Hook._capture = failing
sys.argv = ["benchmark/run.py", "--manifest", {manifest!r}, "--workload",
            {cell!r}, "--seed", "2147483655", "--seconds", "0.3",
            "--trace", "0"]
runpy.run_path("benchmark/run.py", run_name="__main__")
"""


def _session_members(sid: int) -> list:
    """(pid, command) of every process whose session id is ``sid``."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
        except OSError:
            continue        # gone between the listing and the read
        # pid (comm) state ppid pgrp session ...; comm may hold spaces
        fields = stat[stat.rindex(")") + 2:].split()
        if int(fields[3]) == sid and fields[0] != "Z":
            out.append((int(pid), stat[stat.index("(") + 1:stat.rindex(")")]))
    return out


@pytest.mark.parametrize(
    "fail", [False, True, "before_the_cell"],
    ids=["to_a_result", "raised_inside_train", "program_before_the_cell"])
def test_a_run_leaves_no_process_in_its_session(data_root, fail):
    """``benchmark/run.py`` itself, in a session of its own: once to its
    result line (it leaves by ``os._exit``), once with the program
    raising inside ``train()`` (it leaves by the interpreter's ordinary
    shutdown, which run.py does not shorten), and once on a program that
    lists no walk scopes, as the parent commit's does: the reference
    refuses it at load, exit code 1, no result line, before a model is
    built. Every way the child ends within its time and nothing of its
    session is alive after it."""
    code = CHILD.format(root=ROOT, data=data_root, fail=fail, manifest=TOY,
                        cell=TOY_CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.Popen(
        [sys.executable, "-c", code], env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    sid = child.pid
    try:
        out, err = child.communicate(timeout=240)
    finally:
        if child.poll() is None:
            os.killpg(sid, 9)
    if fail == "before_the_cell":
        assert child.returncode == 1, err[-2000:]
        assert "lacks walk, negatives, pair_rows" in err
        assert "this cell cannot run on it; no result" in err
        assert "Traceback" not in err
        assert not out.strip()
    elif fail:
        assert child.returncode == 1, err[-2000:]
        assert "planted: the program raised inside train()" in err
        assert not out.strip()
    else:
        assert child.returncode == 0, err[-2000:]
        line = json.loads(out.strip().splitlines()[-1])
        assert line["correct"] is True and line["attempted"] > 0
    assert _session_members(sid) == []
