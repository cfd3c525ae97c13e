"""The ``scalable_sage_reddit`` configuration's own files (its reference,
its cost function, its readers, its entries in BENCHMARK.json), on the
CPU: the reference against the program's step at a few hundred nodes, so
that the roots of one step were neighbours of the step before and the
stale gradients are not nought; the cell's files through the harness at
that node count; the control and every planted fault coming out as not
correct. Every width is the configuration's (602 features, dim 64, 41
classes): the node count, the batch and the ``draw_skew`` limit (256
draws a step where the cell has 4,000) are all that the toy copy cuts.
"""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmark import check, costs, graphgen, harness, manifest, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "scalable_sage_reddit.json")
CELL = "reddit_scalable_device_train"
HOST_CELL = "toy_scalable_host"
NODES, BATCH = 400, 64


def _cfg():
    with open(CONFIG) as f:
        return json.load(f)


def _toy_cfg():
    cfg = _cfg()
    cfg["graph"].update(num_nodes=NODES, num_partitions=2)
    cfg["flags"]["max_id"] = NODES - 1
    cfg["batch_size"] = BATCH
    cfg["limits"]["draw_skew"] = 0.2
    for key in ("reference", "costs"):
        cfg[key] = os.path.join(ROOT, cfg[key])
    return cfg


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(
        os.path.join(ROOT, _cfg()["reference"]), "test_scalable_reference")


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """A manifest beside a copy of the configuration at ``NODES`` nodes:
    the real cell, and the same configuration under the host-sampled
    traffic file."""
    d = tmp_path_factory.mktemp("scalable_toy")
    with open(MANIFEST) as f:
        m = json.load(f)
    with open(d / "toy_scalable.json", "w") as f:
        json.dump(_toy_cfg(), f)
    m["configs"] = [dict(c, file="toy_scalable.json") for c in m["configs"]
                    if c["name"] == "scalable_sage_reddit"]
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    m["workloads"] = [cell, dict(cell, name=HOST_CELL,
                                 traffic="train_host_sampled")]
    for x in m["per_layer"]:
        if CELL in x["workloads"]:
            x["workloads"] = [CELL, HOST_CELL]
    path = d / "BENCHMARK.json"
    with open(path, "w") as f:
        json.dump(m, f)
    return str(path), str(d / "data")


def _run(toy, cell, seed, **kw):
    path, data = toy
    return harness.run_cell(path, cell, seed, 0.2, False, time.time(),
                            require_chip=False, data_root=data, **kw)


# ---------------------------------------------------------------------------
# the files and the entries
# ---------------------------------------------------------------------------


def test_manifest_takes_the_configuration_as_files_and_entries():
    assert manifest.problems(MANIFEST) == []
    m = harness.load_json(MANIFEST)
    (entry,) = [c for c in m["configs"] if c["name"] == "scalable_sage_reddit"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    (cell,) = [w for w in m["workloads"] if w["config"] == entry["name"]]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train_device_sampled", 1)
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in ("step.store_read_ms", "step.store_write_ms",
                 "store.traffic_roofline"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "edges_per_s_chip"
        assert by_name[name]["layer"] == "model_step"
    # every reader that serves the cell unchanged lists it; the one that
    # reads collectives does not
    silent = {n for n, x in by_name.items() if CELL not in x["workloads"]}
    assert silent == {"mesh.collective_ms"}
    assert m["workloads"][-1] == cell and m["configs"][-1] == entry


def test_reference_and_costs_bind_the_protocol(ref):
    cfg = _cfg()
    for key, functions in manifest.CONFIG_FILES.items():
        bound = manifest.bound_names(os.path.join(ROOT, cfg[key]))
        assert set(functions) <= bound, key
    for name in manifest.CONFIG_FILES["reference"]:
        assert callable(getattr(ref, name))
    # the benchmark's copy stands alone: nothing of the program, nothing
    # of the tests' toy family
    with open(os.path.join(ROOT, cfg["reference"])) as f:
        text = f.read()
    assert "import euler_tpu" not in text and "from euler_tpu" not in text
    assert "toy_store" not in text.split('"""', 2)[2]


def test_configuration_states_the_recipe():
    cfg = _cfg()
    assert (cfg["batch_size"], cfg["fanouts"], cfg["dim"], cfg["concat"],
            cfg["feature_dim"], cfg["num_classes"]) == (
        1000, [4, 4], 64, True, 602, 41)
    assert (cfg["learning_rate"], cfg["store_learning_rate"],
            cfg["store_init_maxval"]) == (0.03, 0.001, 0.05)
    flags = cfg["flags"]
    assert flags["model"] == "scalable_sage" and flags["fanouts"] == "4,4"
    for k in ("store_learning_rate", "store_init_maxval", "dim",
              "learning_rate", "aggregator", "optimizer"):
        assert flags[k] == cfg[k], k
    assert flags["max_id"] == cfg["graph"]["num_nodes"] - 1
    # the graph of the two graphsage_reddit cells
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "graphsage_reddit.json")) as f:
        assert cfg["graph"] == json.load(f)["graph"]
    assert "precision" in cfg and "4,744 B" in cfg["why"]


def test_costs_by_hand():
    """4,000 edges a step and chip; the numbers as literals."""
    c = costs.step_costs(_cfg(), 1000, True)
    fwd0 = 2 * 1000 * 602 * 32 * 2      # 77,056,000
    fwd1 = 2 * 1000 * 64 * 32 * 2       # 8,192,000
    out = 2 * 1000 * 64 * 41            # 5,248,000
    assert c["flops"] == 3 * (fwd0 + fwd1 + out) == 271_488_000
    assert c["gather_bytes"] == 5000 * 602 * 4 + 1000 * 41 * 4 == 12_204_000
    # 4,000 rows read, 3 x 1,000 at the roots, 2 x 4,000 scatter-added
    assert c["store_bytes"] == 15_000 * 64 * 4 == 3_840_000
    assert c["draw_bytes"] == 1000 * 60 * 8 + 4000 * 4 == 496_000
    assert c["params"] == 45_289
    assert c["opt_bytes"] == 2 * 7 * 45_289 * 4 == 2_536_184
    assert c["bytes"] == 12_204_000 + 3_840_000 + 2_536_184 + 496_000 \
        == 19_076_184
    assert c["edges"] == 4_000
    host = costs.step_costs(_cfg(), 1000, False)
    assert host["draw_bytes"] == 0
    assert host["bytes"] == 19_076_184 - 496_000 + 5000 * 4
    assert set(costs.REQUIRED) <= set(c)


def test_every_program_scope_keeps_exactly_one_reader():
    from euler_tpu import trace as TR

    claimed = scopes.declared_scopes()
    assert {"stores_read", "stores_write", "draw"} <= set(TR.STEP_SCOPES)
    assert set(TR.STEP_SCOPES) == set(claimed)
    assert claimed["stores_read"] == "step.store_read_ms"
    assert claimed["stores_write"] == "step.store_write_ms"
    path = "jit(train_step)/stores_write/scatter-add"
    assert scopes.scope_of_op_name(path) == "stores_write"


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmark", "layers", name + ".py"),
        "test_layer_" + name.replace(".", "_"))


def test_store_readers_on_scope_times_and_on_a_program_without_them():
    ctx = harness.Context(costs={"store_bytes": 3_840_000.0},
                          peaks={"hbm_bytes_per_s": 819e9},
                          xplane_path=None)
    ctx._scope_ms = {"stores_read": 0.10, "stores_write": 0.15,
                     "dense": 0.2}
    assert _reader("step.store_read_ms").read(ctx) == pytest.approx(0.10)
    assert _reader("step.store_write_ms").read(ctx) == pytest.approx(0.15)
    # 3.84 MB at 819 GB/s is 4.689 us of 250 us
    assert _reader("store.traffic_roofline").read(ctx) == pytest.approx(
        100 * 3.84e6 / 819e9 / 0.25e-3)
    # the parent names no store scope; a family without stores counts no
    # store bytes; a CPU run has no capture: silent, and no error
    old = harness.Context(costs={"store_bytes": 3_840_000.0},
                          peaks={"hbm_bytes_per_s": 819e9}, xplane_path=None)
    old._scope_ms = {"dense": 0.2, "unscoped": 8.0}
    none = harness.Context(costs={}, peaks={"hbm_bytes_per_s": 819e9},
                           xplane_path=None)
    none._scope_ms = dict(ctx._scope_ms)
    cpu = harness.Context(costs={"store_bytes": 1.0}, peaks=None,
                          xplane_path=None)
    for name in ("step.store_read_ms", "step.store_write_ms",
                 "store.traffic_roofline", "store.layout_copy_ms"):
        assert _reader(name).read(old) is None
        assert _reader(name).read(cpu) is None
    assert _reader("store.traffic_roofline").read(none) is None


def test_layout_copies_of_the_stores_are_counted_to_the_store_layer(
        tmp_path):
    """An unscoped op whose result has the stores' shape is a whole-table
    copy the compiler put around the store scopes: ``store.layout_copy_ms``
    reads it, and the roofline divides by it too. A scatter of the same
    shape rides its scope; the feature table's shape is none of the
    stores' business."""
    from benchmark import xplane

    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    store = "f32[2090001,64]{1,0:T(8,128)}"
    hlo = [
        "ENTRY %main (p: f32[2]) -> f32[2] {",
        f"  %copy.72 = {store} copy(f32[2090001,64]{{0,1}} %stores)",
        "  %copy.80 = f32[2090001,64]{0,1:T(8,128)} "
        f"copy({store} %fusion.6)",
        f"  %fusion.6 = {store} fusion({store} %copy.72), kind=kLoop, "
        'metadata={op_name="jit(train_step)/stores_write/scatter-add"}',
        "  %copy.9 = f32[2090001,640]{1,0} copy(f32[2090001,640]{0,1} %f)",
        "}",
    ]
    (tmp_path / "train_step.hlo.txt").write_text("\n".join(hlo))
    ms = 1_000_000   # a millisecond of the capture's clock
    events = [
        (hlo[1].strip(), 0, 2 * ms), (hlo[3].strip(), 2 * ms, 3 * ms),
        (hlo[2].strip(), 3 * ms, 6 * ms), (hlo[4].strip(), 6 * ms, 7 * ms)]
    ctx = harness.Context(
        cfg=_cfg(), costs={"store_bytes": 3_840_000.0},
        peaks={"hbm_bytes_per_s": 819e9}, trace_steps=2,
        xplane_path=str(run / "host.xplane.pb"))
    ctx._capture = xplane.Capture([xplane.DeviceLane(0, events)], None)
    assert _reader("step.store_write_ms").read(ctx) == pytest.approx(0.5)
    assert _reader("step.unscoped_ms").read(ctx) == pytest.approx(3.0)
    assert _reader("store.layout_copy_ms").read(ctx) == pytest.approx(2.5)
    assert _reader("store.traffic_roofline").read(ctx) == pytest.approx(
        100 * 3.84e6 / 819e9 / 3.0e-3)
    # the stores in the layout the step works in: nought, and said so
    ctx = harness.Context(
        cfg=_cfg(), costs={"store_bytes": 3_840_000.0},
        peaks={"hbm_bytes_per_s": 819e9}, trace_steps=2,
        xplane_path=str(run / "host.xplane.pb"))
    ctx._capture = xplane.Capture([xplane.DeviceLane(0, events[1:2])], None)
    assert _reader("store.layout_copy_ms").read(ctx) == 0.0


# ---------------------------------------------------------------------------
# the reference by hand, and against the program's bare step
# ---------------------------------------------------------------------------


def test_last_occurrence_gives_the_scatter_distinct_rows(ref):
    """The program's rule and the reference's: of the rows that hold one
    id the last stays. What the program hands its scatter is then one
    row per id, so no order of writing can matter."""
    from euler_tpu.models import base

    rng = np.random.default_rng(0)
    for _ in range(20):
        ids = rng.integers(0, 12, 30)
        keep = np.asarray(base.last_occurrence(ids))
        assert sorted(ids[keep]) == sorted(set(ids))
        for i in np.flatnonzero(keep):
            assert ids[i] not in ids[i + 1:]
        assert sorted(ref.last_occurrence(ids)) == list(np.flatnonzero(keep))


def _model(tmp_path, device_sampling):
    import euler_tpu
    from euler_tpu import run_loop

    cfg = _toy_cfg()
    spec = graphgen.spec_from_config(cfg)
    data = spec.write(str(tmp_path / "g"))
    mod, attr = cfg["preset"]
    argv = list(getattr(__import__(mod, fromlist=[attr]), attr)) + [
        "--data_dir", data, "--device_features", "true",
        "--device_sampling", str(device_sampling).lower(),
        "--batch_size", str(cfg["batch_size"])]
    for k, v in cfg["flags"].items():
        argv += ["--" + k, str(v)]
    args = run_loop.define_flags().parse_args(argv)
    graph = euler_tpu.Graph(directory=data)
    return cfg, spec, graph, run_loop.build_model(args, graph)


def _hand_batch(roots, neighbours):
    """A batch as the host sampler hands it over, on chosen ids."""
    roots = np.asarray(roots, np.int32)
    neighbours = np.asarray(neighbours, np.int32)
    return {"node_feats": {"gids": roots}, "neigh_feats": {"gids": neighbours},
            "node_ids": roots, "neigh_ids": neighbours}


def _assert_close_after(ref, state, loss, result, start):
    """Every leaf the program's step left against the reference's."""
    r_loss, _, params, store, grad_store, opt, store_opt = result
    assert float(loss) == pytest.approx(r_loss, rel=2e-5)
    got = ref.compared_state(state)
    for k, v in params.items():
        # an Adam step is lr * m / sqrt(v): where a gradient element is
        # nought to rounding its step is rounding too, of lr's size
        np.testing.assert_allclose(got[k], v, rtol=1e-3, atol=1e-4,
                                   err_msg=k)
        np.testing.assert_allclose(got[ref.MU + k], opt["m"][k],
                                   rtol=1e-3, atol=1e-6, err_msg=k)
        np.testing.assert_allclose(got[ref.STORE_MU + k], store_opt["m"][k],
                                   rtol=1e-3, atol=1e-6, err_msg=k)
    import jax

    nu, store_nu = (ref.from_program(jax.device_get(state[k])[0].nu)
                    for k in ("opt_state", "store_opt_state"))
    for k in params:
        np.testing.assert_allclose(nu[k], opt["v"][k], rtol=2e-3, atol=1e-12)
        np.testing.assert_allclose(store_nu[k], store_opt["v"][k],
                                   rtol=2e-3, atol=1e-12)
    np.testing.assert_allclose(np.asarray(state["stores"][0]), store,
                               rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state["grad_stores"][0]),
                               grad_store, rtol=2e-3, atol=1e-7)
    assert got[ref.OUTSIDE][0] == 0
    # the row leaves are the rows the steps named, as changes
    named = np.unique(np.concatenate([i for h in ref._run["hops"] for i in h]))
    np.testing.assert_allclose(
        got[ref.ROWS], store[named] - start["store0"][named],
        rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(
        got[ref.GRAD_ROWS], grad_store[named] - start["grad_store0"][named],
        rtol=2e-3, atol=1e-7)


@pytest.mark.parametrize("device_sampling", [False, True],
                         ids=["host_sampled", "device_sampled"])
def test_bare_step_against_the_reference_after_one_and_three_steps(
        tmp_path, ref, device_sampling):
    """``make_train_step`` jitted alone, three steps of random roots:
    loss, first gradient, every parameter, the store, the gradient store
    and both Adams' first and second moments, after step 1 and after
    step 3. At 400 nodes step 2's roots were step 1's neighbours, so the
    second Adam has work."""
    import jax

    from euler_tpu import train as train_lib

    cfg, spec, graph, model = _model(tmp_path, device_sampling)
    opt = train_lib.get_optimizer("adam", cfg["learning_rate"])
    start, state = ref.init_state(cfg, jax.random.PRNGKey(3), opt)
    state["consts"] = model.build_consts(graph)
    step = jax.jit(model.make_train_step(opt))
    rng = np.random.default_rng(4)
    batches, after = [], []
    for _ in range(3):
        batch = model.sample(graph, rng.integers(0, NODES, BATCH))
        batches.append(ref.reference_batch(
            spec, ref.drawn_hops(model, state, batch)))
        state, loss, _ = step(state, batch)
        after.append((jax.tree_util.tree_map(np.asarray, state), loss))
    start = {k: np.asarray(v) for k, v in start.items()}
    results = []
    for r in ref.follow(cfg, start, batches):
        results.append(r[:3] + (r[3].copy(), r[4].copy()) + r[5:])
    assert len(set(batches[0]["roots"])) < BATCH    # a root came twice
    assert set(batches[0]["roots"]) & set(batches[0]["neighbours"])
    assert np.asarray(results[2][6]["m"]["w_self0"]).any()
    first = ref.first_gradient(after[0][0])
    for k, v in results[0][1].items():
        np.testing.assert_allclose(first[k], v, rtol=1e-4, atol=1e-8)
    for i in (0, 2):
        _assert_close_after(ref, after[i][0], after[i][1], results[i], start)


def test_duplicated_root_and_own_neighbour_follow_the_stated_rule(
        tmp_path, ref):
    """Root 7 comes three times with three different neighbourhoods: its
    last row stays, in either batch order. Root 9 is a neighbour in its
    own batch: it is read stale and written fresh, and its gradient row
    is cleared before this step's share is added."""
    import jax

    from euler_tpu import train as train_lib

    cfg, spec, graph, model = _model(tmp_path, False)
    opt = train_lib.get_optimizer("adam", cfg["learning_rate"])
    consts = model.build_consts(graph)
    step = jax.jit(model.make_train_step(opt))
    rng = np.random.default_rng(5)
    roots = np.array([7, 3, 7, 9, 11, 7, 20, 21])
    neigh = rng.integers(30, 200, 32)
    neigh[13] = 9                      # 9 is root 3's... and its own batch's
    warm = _hand_batch(rng.integers(0, NODES, 8), np.r_[roots, roots, roots,
                                                        roots])
    for order in (np.arange(8), np.arange(8)[::-1]):
        start, state = ref.init_state(cfg, jax.random.PRNGKey(6), opt)
        state["consts"] = consts
        start = {k: np.asarray(v) for k, v in start.items()}
        r = roots[order]
        n = neigh.reshape(8, 4)[order].reshape(-1)
        batches = []
        # the warm-up step makes every root of the second a neighbour
        # first, so that there is a stale gradient to read and clear
        for batch in (warm, _hand_batch(r, n)):
            batches.append(ref.reference_batch(
                spec, ref.drawn_hops(model, state, batch)))
            state, loss, _ = step(state, batch)
        results = list(ref.follow(cfg, start, batches))
        _assert_close_after(ref, state, loss, results[1], start)
        # by hand: the kept row is the last occurrence's fresh embedding
        _, _, params1, store1, grad1, _, _ = list(
            ref.follow(cfg, start, batches[:1]))[0]
        store1, grad1 = store1.copy(), grad1.copy()
        b = batches[1]
        _, h0 = ref.forward(params1, b["x0"], b["x1"], store1[b["neighbours"]],
                            b["y"], cfg)
        last7 = int(np.flatnonzero(r == 7)[-1])
        got = {"store0": np.asarray(state["stores"][0]),
               "grad_store0": np.asarray(state["grad_stores"][0])}
        np.testing.assert_allclose(got["store0"][7], np.asarray(h0)[last7],
                                   rtol=2e-3, atol=2e-5)
        # the leaf of roots drawn more than once holds that row alone
        np.testing.assert_array_equal(
            ref.compared_state(state)[ref.TWICE], got["store0"][[7]])
        others = [i for i in np.flatnonzero(r == 7)[:-1]]
        assert all(not np.allclose(np.asarray(h0)[i], np.asarray(h0)[last7],
                                   rtol=1e-3) for i in others)
        # 9: the row layer 1 read was the stale one
        assert not np.allclose(got["store0"][9], store1[9])
        # 9 held a stale gradient from the warm-up step; what it holds
        # now is this step's share alone
        assert grad1[9].any() and got["grad_store0"][9].any()
        assert not np.allclose(got["grad_store0"][9], grad1[9], rtol=1e-2)


# ---------------------------------------------------------------------------
# the cell's files through the harness and train(), at the toy node count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", [CELL, HOST_CELL])
def test_cell_files_end_to_end_through_train(toy, cell):
    r = _run(toy, cell, seed=2**31 + 77)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"] == {}           # a CPU run is no measurement
    assert set(r["compared"]) == {
        "draw_foreign", "draw_skew", "loss_gap", "grad_gap", "change_gap",
        "compiles_in_window"}


def test_control_and_half_batch_fail_the_limits(toy):
    r = _run(toy, CELL, seed=11, calibrate=True)
    assert r["correct"] is True, r["compared"]
    limits = harness.Cell(toy[0], CELL).cfg["limits"]
    for name in ("control_bf16", "fault_half_batch"):
        ok, table = check.verdict(r["calibration"][name], limits)
        assert not ok, (name, table)


def _patch_step(monkeypatch, wrap):
    from euler_tpu.models import base

    orig = base.ScalableStoreModel.make_train_step

    def make(self, optimizer):
        return wrap(self, orig(self, optimizer))

    monkeypatch.setattr(base.ScalableStoreModel, "make_train_step", make)


def _store_write_skipped(monkeypatch):
    def wrap(model, step):
        def broken(state, batch):
            new, loss, metric = step(state, batch)
            return dict(new, stores=state["stores"]), loss, metric
        return broken
    _patch_step(monkeypatch, wrap)


def _stale_gradient_not_cleared(monkeypatch):
    """The roots' rows keep what they held: this step's shares are added
    on top of the gradient that was read, which is then read again."""
    def wrap(model, step):
        def broken(state, batch):
            import jax.numpy as jnp

            new, loss, metric = step(state, batch)
            roots = model._expand_batch(batch, state.get("consts"))["node_ids"]
            old = state["grad_stores"][0]
            was_root = jnp.zeros(old.shape[0], old.dtype).at[roots].set(1.0)
            kept = new["grad_stores"][0] + was_root[:, None] * old
            return dict(new, grad_stores=[kept]), loss, metric
        return broken
    _patch_step(monkeypatch, wrap)


def _second_adam_skipped(monkeypatch):
    """The store loss's gradient reaches no optimizer: ``optax.adam`` as
    the store model sees it moves nothing and keeps no moment (the first
    Adam is the trainer's own and is left alone)."""
    import optax

    from euler_tpu.models import base

    def no_adam(learning_rate):
        real = optax.adam(learning_rate)

        def update(grads, state, params=None):
            import jax

            return jax.tree_util.tree_map(lambda g: g * 0, grads), state
        return optax.GradientTransformation(real.init, update)

    fake = types.SimpleNamespace(**vars(optax))
    fake.adam = no_adam
    monkeypatch.setattr(base, "optax", fake)


def _reads_fresh_instead_of_stale(monkeypatch):
    """Layer 1 reads the store after this step's write: a neighbour that
    is a root of the same batch is read fresh."""
    def wrap(model, step):
        def broken(state, batch):
            written, _, _ = step(state, batch)
            return step(dict(state, stores=written["stores"]), batch)
        return broken
    _patch_step(monkeypatch, wrap)


@pytest.mark.parametrize("cell,plant", [
    (CELL, _store_write_skipped),
    (CELL, _stale_gradient_not_cleared),
    (HOST_CELL, _stale_gradient_not_cleared),
    (CELL, _second_adam_skipped),
    (CELL, _reads_fresh_instead_of_stale),
    (HOST_CELL, _reads_fresh_instead_of_stale),
], ids=["store_write_skipped", "stale_gradient_not_cleared",
        "stale_gradient_not_cleared_host", "second_adam_skipped",
        "reads_fresh", "reads_fresh_host"])
@pytest.mark.parametrize("seed", [13, 2**31 + 5])
def test_planted_fault_is_not_correct(toy, monkeypatch, cell, plant, seed):
    plant(monkeypatch)
    r = _run(toy, cell, seed=seed)
    assert r["correct"] is False, r["compared"]


def test_rows_outside_the_steps_are_held_bit_identical(ref):
    """The exact statement: one bit of one row that no step named, in
    either table; and the row leaves beside it."""
    start = {"store0": np.full((10, 4), 0.5, np.float32),
             "grad_store0": np.full((10, 4), 1e-5, np.float32)}
    steps = [(np.array([1, 2, 1]), np.array([3, 3, 4, 5]))]

    def leaves(store, grad):
        return ref.row_leaves(store, grad, start, steps)

    store, grad = start["store0"], start["grad_store0"]
    assert leaves(store, grad)[ref.OUTSIDE][0] == 0
    touched = store.copy()
    touched[[1, 5]] = 9.0
    got = leaves(touched, grad)
    assert got[ref.OUTSIDE][0] == 0
    # rows 1..5 were named, in id order; root 1 came twice
    np.testing.assert_array_equal(
        got[ref.ROWS][:, 0], [8.5, 0, 0, 0, 8.5])
    np.testing.assert_array_equal(got[ref.TWICE], touched[[1]])
    assert not got[ref.GRAD_ROWS].any()
    moved = store.copy()
    moved[7, 2] = np.nextafter(np.float32(0.5), np.float32(1))
    filled = grad.copy()
    filled[0, 0] = 0.0
    assert leaves(moved, grad)[ref.OUTSIDE][0] == 1
    assert leaves(moved, filled)[ref.OUTSIDE][0] == 2
    # ...and one such row fails the cell whatever else agrees
    ok = {"w": np.ones(4), ref.OUTSIDE: np.zeros(1)}
    bad = dict(ok, **{ref.OUTSIDE: np.ones(1)})
    assert check.worst_leaf_gap(bad, ok) >= 0.5


# ---------------------------------------------------------------------------
# the faults as the chip's calibration plants them: in the reference, put
# in the program's place
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def planted(toy):
    """One sound run of the toy cell, and the reference with each fault
    in the program's place by the cell's numbers."""
    faults = harness.load_module(
        os.path.join(ROOT, "benchmark", "configs",
                     "scalable_sage_reddit_faults.py"), "test_store_faults")
    path, data = toy
    prep = harness.Prepared(path, CELL, time.time(), require_chip=False,
                            data_root=data)
    try:
        hook = prep.drive(2**31 + 21, 0.0, first_steps_only=True)
        sound = prep.compare(hook)
        return prep.cfg["limits"], sound, faults.fault_numbers(prep, hook)
    finally:
        prep.close()


def test_reference_names_the_faults_the_tests_plant_in_the_program(ref):
    assert set(ref.FAULTS) == {
        "store_write_skipped", "stale_gradient_not_cleared",
        "second_adam_skipped", "reads_fresh", "first_duplicate_kept"}


@pytest.mark.parametrize("fault", [
    "store_write_skipped", "stale_gradient_not_cleared",
    "second_adam_skipped", "reads_fresh", "first_duplicate_kept"])
def test_fault_planted_in_the_reference_is_not_correct(planted, fault):
    limits, sound, numbers = planted
    assert check.verdict(sound, limits)[0], sound
    ok, table = check.verdict(numbers["fault_" + fault], limits)
    assert not ok, table


def test_fault_in_the_reference_reads_as_the_fault_in_the_program(
        tmp_path, ref):
    """The reference with a fault planted follows the program with the
    same fault planted (here: the clear left out, the step wrapped as
    ``_stale_gradient_not_cleared`` wraps it), so the two ways of
    planting measure one thing."""
    import jax
    import jax.numpy as jnp

    from euler_tpu import train as train_lib

    cfg, spec, graph, model = _model(tmp_path, False)
    opt = train_lib.get_optimizer("adam", cfg["learning_rate"])
    start, state = ref.init_state(cfg, jax.random.PRNGKey(8), opt)
    state["consts"] = model.build_consts(graph)
    sound = jax.jit(model.make_train_step(opt))

    def broken(state, batch):
        new, loss, metric = sound(state, batch)
        old = state["grad_stores"][0]
        was_root = jnp.zeros(old.shape[0]).at[batch["node_ids"]].set(1.0)
        kept = new["grad_stores"][0] + was_root[:, None] * old
        return dict(new, grad_stores=[kept]), loss, metric

    rng = np.random.default_rng(9)
    batches = []
    for _ in range(3):
        batch = model.sample(graph, rng.integers(0, NODES, BATCH))
        batches.append(ref.reference_batch(
            spec, ref.drawn_hops(model, state, batch)))
        state, loss, _ = broken(state, batch)
    start = {k: np.asarray(v) for k, v in start.items()}
    faulty = list(ref.follow(cfg, start, batches,
                             fault="stale_gradient_not_cleared"))[-1]
    right = list(ref.follow(cfg, start, batches))[-1]
    got = np.asarray(state["grad_stores"][0])
    np.testing.assert_allclose(got, faulty[4], rtol=2e-3, atol=1e-7)
    assert not np.allclose(got, right[4], rtol=2e-3, atol=1e-7)
