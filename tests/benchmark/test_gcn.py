"""The ``gcn_ppi`` configuration's own files (its reference, its cost
function, its readers, its entries in BENCHMARK.json), on the CPU: the
ragged reference by hand on a graph of five nodes with a row that lists
one neighbour twice; the exact judgement of an expansion; the toy cell
(``toy/toy_gcn.json`` under ``BENCHMARK_toy_gcn.json``: the
configuration's reference and cost function at 2,000 nodes and 16 roots,
every width the recipe's, caps that hold) through the harness and
``train()``, device- and host-expanded; the control and every planted
fault coming out as not correct, in the reference put in the program's
place and in the program's own step; a cap made too small on purpose
counted and refused; the scopes, the counters and the route-log line.
"""

import json
import logging
import os
import time
import types

import numpy as np
import pytest

from benchmark import check, costs, graphgen, harness, manifest, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "gcn_ppi.json")
TOY = os.path.join(HERE, "BENCHMARK_toy_gcn.json")
TOY_CONFIG = os.path.join(HERE, "toy", "toy_gcn.json")
CELL, TOY_CELL, TOY_HOST = (
    "gcn_ppi_device_train", "toy_gcn_device", "toy_gcn_host")
NEW_METRICS = ("expand.scope_ms", "step.segment_agg_ms", "expand.slot_fill",
               "segment.traffic_roofline")
FAULTS = ("neighbour_dropped", "padding_counted", "shared_neighbour_once",
          "self_left_out", "second_hop_not_aggregated")
NODES, BATCH, WIDTH = 2000, 16, 12


def _cfg(path=CONFIG):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(
        os.path.join(ROOT, _cfg()["reference"]), "test_gcn_reference")


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("toy_gcn_data"))


def _run(data_root, cell, seed, **kw):
    return harness.run_cell(TOY, cell, seed, 0.2, False, time.time(),
                            require_chip=False, data_root=data_root, **kw)


def _counters():
    from euler_tpu.graph import native

    c = native.counters()
    return {k: c.get("expand_" + k, 0)
            for k in ("slots", "edges", "overflow_nodes")}


def _fresh_ledger():
    """Counters at nought, and the route log ready to say its line again
    (it says each expansion shape once a process)."""
    from euler_tpu.graph import device as device_graph
    from euler_tpu.graph import native

    native.reset_counters()
    device_graph._log_expand_route.cache_clear()


# ---------------------------------------------------------------------------
# the files and the entries
# ---------------------------------------------------------------------------


def test_manifest_takes_the_configuration_as_files_and_entries():
    """The entries are found by name, wherever later PRs put theirs."""
    assert manifest.problems(MANIFEST) == []
    m = harness.load_json(MANIFEST)
    (entry,) = [c for c in m["configs"] if c["name"] == "gcn_ppi"]
    (cell,) = [w for w in m["workloads"] if w["config"] == "gcn_ppi"]
    assert entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/gcn_ppi.json"
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train_device_sampled", 1)
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "edges_per_s_chip"
    assert by_name["expand.scope_ms"]["layer"] == "device_sampling"
    assert by_name["expand.slot_fill"]["source"] == "program_counter"
    # the readers of the other families' mechanisms stay silent here, and
    # so does the 99th percentile, which wants 1,000 steps of a window
    # where a 0.23 s device step leaves about 220
    silent = {n for n, x in by_name.items() if CELL not in x["workloads"]}
    assert silent == {
        "trainer.step_ms_p99",
        "draw.kernel_ms", "draw.kernel_roofline", "draw.scope_ms",
        "mesh.collective_ms", "step.store_read_ms", "step.store_write_ms",
        "store.traffic_roofline", "store.layout_copy_ms", "walk.scope_ms",
        "embed.pair_rows_ms", "embed.traffic_roofline",
        "optimizer.traffic_roofline"}
    # the whole step's share of the peak is reported beside the new share
    assert CELL in by_name["step.mfu_roofline"]["workloads"]


def test_reference_and_costs_bind_the_protocol(ref):
    cfg = _cfg()
    for key, functions in manifest.CONFIG_FILES.items():
        bound = manifest.bound_names(os.path.join(ROOT, cfg[key]))
        assert set(functions) <= bound, key
    for name in manifest.CONFIG_FILES["reference"]:
        assert callable(getattr(ref, name))
    assert ref.FAULTS == FAULTS
    # the benchmark's copy stands alone: nothing of the program, and
    # none of the mechanisms it is there to judge
    for key in ("reference", "costs"):
        with open(os.path.join(ROOT, cfg[key])) as f:
            text = f.read()
        for word in ("import euler_tpu", "from euler_tpu", "from benchmark",
                     "import benchmark", "segment_sum(", "argsort("):
            assert word not in text, (key, word)


def test_configuration_states_the_recipe_in_flags_the_program_has():
    from euler_tpu import run_loop

    cfg = _cfg()
    assert cfg["reduced"] == [] and len(cfg["guarantees"]) == 4
    assert (cfg["batch_size"], cfg["dim"], cfg["aggregator"],
            cfg["use_residual"], cfg["sigmoid_loss"], cfg["optimizer"],
            cfg["learning_rate"], cfg["feature_dim"], cfg["label_dim"]) == (
        512, 256, "mean", False, True, "adam", 0.01, 50, 121)
    flags = cfg["flags"]
    assert flags["model"] == "gcn"
    assert flags["max_id"] == cfg["graph"]["num_nodes"] - 1 == 2089999
    # the caps stand at the graph function's largest degree
    width = cfg["graph"]["max_degree"]
    assert flags["fanouts"] == "%d,%d" % (width, width) == "60,60"
    assert [cfg["batch_size"] * width ** h for h in (1, 2)] == [
        30720, 1843200]
    # every flag the cell sets but these three is define_flags()'s own
    # default: the upstream recipe
    defaults = run_loop.define_flags().parse_args([])
    for k, v in flags.items():
        assert hasattr(defaults, k), k
        if k not in ("model", "max_id", "fanouts"):
            assert getattr(defaults, k) == v, k
    assert defaults.batch_size == cfg["batch_size"]
    assert defaults.use_residual is cfg["use_residual"]
    assert defaults.sigmoid_loss is cfg["sigmoid_loss"]
    toy = _cfg(TOY_CONFIG)
    for k in ("dim", "aggregator", "feature_dim", "label_dim", "num_classes",
              "optimizer", "learning_rate", "limits", "guarantees"):
        assert toy[k] == cfg[k], k
    assert toy["fanouts"] == [WIDTH, WIDTH] == [
        toy["graph"]["max_degree"]] * 2


# ---------------------------------------------------------------------------
# the reference by hand
# ---------------------------------------------------------------------------


class FiveNodes:
    """A graph function of five nodes, rows up to three wide. Node 0
    lists node 3 twice; nodes 1 and 2 share the neighbour 4."""

    num_nodes, max_degree = 5, 3
    slab = np.array([[3, 3, 1], [4, 2, 0], [4, 0, 0], [0, 0, 0], [1, 0, 0]])
    deg = np.array([3, 2, 1, 1, 1])

    def degrees(self, ids):
        return self.deg[np.asarray(ids)]

    def neighbor_slab(self, ids):
        return self.slab[np.asarray(ids)]

    def features(self, ids):
        ids = np.asarray(ids, np.float32)
        return np.stack([ids + 1.0, (ids - 2.0) ** 2], axis=1)

    def labels(self, ids):
        return (np.asarray(ids)[:, None] % 2 == np.arange(3)[None, :] % 2
                ).astype(np.float32)


def test_ragged_expansion_and_means_by_hand(ref):
    spec = FiveNodes()
    x = ref.expand(spec, [0, 2])
    assert x["s1"].tolist() == [1, 3, 4] and x["s2"].tolist() == [0, 1, 2, 4]
    # root 0's three edges: node 3 twice, node 1 once; root 2's one
    assert list(zip(*x["e0"])) == [(0, 1), (0, 1), (0, 0), (1, 2)]
    assert list(zip(*x["e1"])) == [(0, 3), (0, 2), (1, 0), (2, 1)]
    a, _ = ref.step_arrays(spec, [0, 2])
    f = spec.features(np.arange(5))
    # the message of the twice-listed neighbour twice, the degree three
    np.testing.assert_allclose(a["m0"][0], (2 * f[3] + f[1]) / 3, rtol=1e-6)
    np.testing.assert_allclose(a["m0"][1], f[4], rtol=1e-6)
    np.testing.assert_allclose(a["m1"], [(f[4] + f[2]) / 2, f[0], f[1]],
                               rtol=1e-6)
    np.testing.assert_allclose(
        a["a"], [[1 / 3, 2 / 3, 0], [0, 0, 1]], rtol=1e-6)
    # the faults' own expansions
    once = ref.expand(spec, [1, 2], "shared_neighbour_once")
    assert list(zip(*once["e0"])) == [(0, 1), (0, 0)]   # 2 -> 4 is left out
    padded, _ = ref.step_arrays(spec, [0, 2], "padding_counted")
    np.testing.assert_allclose(padded["m0"][1], f[4] / 3, rtol=1e-6)
    dropped = ref.expand(RingOfTwelve(), [0, 6], "neighbour_dropped")
    assert dropped["dropped"] == 1 and len(dropped["s2"]) == 9


class RingOfTwelve(FiveNodes):
    """Twelve nodes in a ring of out-degree three: a hop-2 set of ten."""

    num_nodes = 12
    slab = (np.arange(12)[:, None] + np.array([1, 2, 5])[None, :]) % 12
    deg = np.full(12, 3)


def test_loss_and_gradient_by_hand(ref):
    import jax
    import jax.numpy as jnp

    spec = FiveNodes()
    cfg = dict(feature_dim=2, dim=4, num_classes=3, aggregator="mean",
               fanouts=[3, 3], learning_rate=0.01)
    p = jax.jit(lambda k: ref.init_params(cfg, k))(jax.random.PRNGKey(3))
    a, _ = ref.step_arrays(spec, [0, 2])
    loss = float(ref.loss_fn(p, a))
    n = {k: np.asarray(v, np.float64) for k, v in p.items()}
    relu = lambda t: np.maximum(t, 0)        # noqa: E731
    h0 = relu(a["x0"] @ n["w_self0"]) + relu(a["m0"] @ n["w_neigh0"])
    h1 = relu(a["x1"] @ n["w_self0"]) + relu(a["m1"] @ n["w_neigh0"])
    z = h0 @ n["w_self1"] + (a["a"] @ h1) @ n["w_neigh1"]
    logits = z @ n["w_out"] + n["b_out"]
    per = np.maximum(logits, 0) - logits * a["y"] + np.log1p(
        np.exp(-np.abs(logits)))
    assert abs(loss - per.mean()) < 1e-6
    # the fill to a compile bucket changes neither loss nor gradient
    filled = ref.bucketed(a)
    assert len(filled["x1"]) == ref.BUCKET == filled["a"].shape[1]
    assert float(ref.loss_fn(p, filled)) == pytest.approx(loss, rel=1e-6)
    grads = [jax.grad(lambda q, arrays=arrays: ref.loss_fn(q, arrays))(p)
             for arrays in (a, filled)]
    for k in p:
        np.testing.assert_allclose(grads[0][k], grads[1][k], rtol=1e-5,
                                   atol=1e-8)
    # the faults change the loss, each by its own rule
    for fault in ("self_left_out", "second_hop_not_aggregated"):
        assert abs(float(ref.loss_fn(p, a, fault=fault)) - loss) > 1e-4
    start = dict(p)
    start[ref.EXPANSION] = start[ref.OVERFLOW] = np.zeros(1, np.float32)
    batch = {"spec": spec, "roots": np.array([0, 2]), "off": 0}
    losses, g, end = ref.train_steps(cfg, start, [batch] * 3)
    assert abs(losses[0] - loss) < 1e-6 and losses[2] < losses[0]
    # Adam's first step moves every element with a gradient by lr
    moved = np.abs(np.asarray(end["b_out"])) > 0
    assert moved.all() and float(end[ref.EXPANSION][0]) == 0
    assert set(g) == set(ref.param_shapes(cfg))
    assert jnp.isfinite(g["w_self0"]).all()


def test_exact_judgement_of_an_expansion(ref):
    """The unmasked edges as a multiset against the graph function's,
    whatever the padded layout: sound, then one of each thing off."""
    spec = FiveNodes()
    parents = np.array([0, 2, 5, 5])          # two roots, two padding ids
    nodes = np.array([1, 3, 4, 5, 5, 5])      # the set, padded with 5
    pos = np.array([0, 0, 0, 1])
    child = np.array([3, 3, 1, 4])
    sound = ref.judge_hop(spec, parents, nodes, pos, child)
    assert [sound[c] for c in ref.COUNTS] == [0, 0, 0, 0, 0]
    assert (sound["set"], sound["edges"], sound["listed_twice"]) == (3, 4, 1)
    read = lambda **kw: {                      # noqa: E731
        c: v for c, v in ref.judge_hop(spec, **dict(dict(
            parents=parents, nodes=nodes, e_pos=pos, e_child=child),
            **kw)).items() if c in ref.COUNTS and v}
    # the twice-listed neighbour's second edge dropped with its duplicate
    assert read(e_pos=pos[1:], e_child=child[1:]) == {"missing": 1}
    # a padded slot unmasked: an edge from a padding parent
    assert read(e_pos=np.append(pos, 2), e_child=np.append(child, 1)) == {
        "extra": 1}
    # an edge moved to another parent
    assert read(e_pos=np.array([0, 0, 1, 1])) == {"missing": 1, "extra": 1}
    assert read(nodes=np.array([1, 3, 4, 2, 5, 5])) == {"foreign": 1}
    assert read(nodes=np.array([1, 3, 3, 4, 5, 5])) == {"twice": 1}
    assert read(nodes=np.array([1, 3, 5, 5, 5, 5]),
                e_pos=pos[:3], e_child=child[:3]) == {
        "missing": 1, "dropped": 1}
    assert read(nodes=np.array([1, 3, 4, -7, 9, 5])) == {"foreign": 2}


# ---------------------------------------------------------------------------
# the cost function
# ---------------------------------------------------------------------------


def test_cost_function_counts_true_edges_never_slots():
    cfg = _cfg()
    c = costs.step_costs(cfg, 512, True)
    slots = 512 * 60 + 512 * 60 * 60
    assert c["edges"] == 414371 and c["draw_bytes"] == 0
    assert 0.21 < c["edges"] / slots < 0.23
    assert c["unique_nodes"] == 378879
    # every unique node's row once, every true edge's message once
    assert abs(c["gather_bytes"] - (378879 * 200 + 512 * 484)) < 200
    assert c["bytes"] == pytest.approx(
        c["gather_bytes"] + c["message_bytes"] + c["expand_bytes"]
        + c["opt_bytes"])
    assert c["opt_bytes"] == 7 * 4 * c["params"]
    assert c["params"] == 2 * 50 * 256 + 2 * 256 * 256 + 256 * 121 + 121
    # the same on the host-expanded path, and per chip
    assert costs.step_costs(cfg, 512, False)["edges"] == c["edges"]


def test_expected_edges_are_the_programs_unmasked_edges(ref, data_root):
    """The cost function's expected true edges against the mean count of
    unmasked edges of the program's own expansion over seeded steps of
    the toy cell: within 1%. 3,000 steps (three root streams of 1,000)
    where 200 would do at the cell's size: a toy step's count spreads by
    a tenth (70 of 656), so 200 steps' mean is known to 0.75% and 3,000
    steps' to 0.2%."""
    prep = harness.Prepared(TOY, TOY_CELL, time.time(), require_chip=False,
                            data_root=data_root)
    try:
        fn = ref._expansion_fn(prep.model.module)
        counts = []
        for seed in (77, 5, 1234):
            for step in range(1000):
                roots = harness.roots_for_step(seed, step, NODES, BATCH)
                batch = prep.model.device_sample_batch(roots)
                _, coo, over = fn(batch, prep.consts)
                counts.append(sum(float(mask.sum()) for _, _, mask in coo))
            assert int(over) == 0
        expected = costs.step_costs(prep.cfg, BATCH, True, root=HERE)["edges"]
        assert abs(np.mean(counts) / expected - 1) < 0.01, (
            np.mean(counts), expected)
    finally:
        prep.close()


# ---------------------------------------------------------------------------
# the toy cell through the harness and train()
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", [TOY_CELL, TOY_HOST])
def test_toy_cell_is_correct_and_says_what_it_expanded(
        data_root, caplog, cell):
    _fresh_ledger()
    with caplog.at_level(logging.INFO):
        r = _run(data_root, cell, seed=2**31 + 5, calibrate=True)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["draw_skew"]["value"] == 0.5     # no draw to judge
    assert r["compared"]["draw_foreign"]["value"] == 0
    limits = _cfg(TOY_CONFIG)["limits"]
    # the control and the half batch are not correct
    for name in ("control_bf16", "fault_half_batch"):
        ok, table = check.verdict(r["calibration"][name], limits)
        assert not ok, table
    said = [rec.getMessage() for rec in caplog.records]
    judged = [s for s in said if s.startswith(
        "gcn reference: a step's expansion against the graph function")]
    assert len(judged) == harness.CAPTURED_STEPS
    assert all("foreign 0, missing 0, extra 0, twice 0, dropped 0" in s
               for s in judged)
    # the toy graph holds rows that list one neighbour twice
    assert any("(0 a second time)" not in s.split("hop 2")[1]
               for s in judged), judged
    route = [s for s in said if s.startswith("expand path:")]
    c = _counters()
    if cell == TOY_CELL:
        assert route == ["expand path: full neighbourhood 16 -> 192 -> 2304 "
                         "slots (sort dedup, XLA)"]
        steps = c["slots"] // (192 + 2304)
        assert c["slots"] == steps * (192 + 2304) and steps >= 100
        assert 0.1 < c["edges"] / c["slots"] < 0.4
        assert c["overflow_nodes"] == 0
    else:
        assert route == [] and c == {
            "slots": 0, "edges": 0, "overflow_nodes": 0}


@pytest.fixture(scope="module")
def planted(data_root):
    """One sound run of the toy cell, and the reference with each fault
    in the program's place by the cell's numbers."""
    faults = harness.load_module(
        os.path.join(ROOT, "benchmark", "configs",
                     "scalable_sage_reddit_faults.py"), "test_gcn_faults")
    prep = harness.Prepared(TOY, TOY_CELL, time.time(), require_chip=False,
                            data_root=data_root)
    try:
        hook = prep.drive(2**31 + 21, 0.0, first_steps_only=True)
        sound = prep.compare(hook)
        return prep.cfg["limits"], sound, faults.fault_numbers(prep, hook)
    finally:
        prep.close()


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_planted_in_the_reference_is_not_correct(planted, fault):
    limits, sound, numbers = planted
    assert check.verdict(sound, limits)[0], sound
    ok, table = check.verdict(numbers["fault_" + fault], limits)
    assert not ok, table


# ---- the same faults in the program's own step ----

def _wrap_expansion(monkeypatch, change):
    """``change(hops, adjs, roots, caps)`` on what multi_hop_neighbor
    returns, where the GCN module calls it."""
    from euler_tpu.graph import device as device_graph

    orig = device_graph.multi_hop_neighbor

    def wrapped(adjs, roots, caps):
        return change(orig, adjs, roots, caps)

    monkeypatch.setattr(device_graph, "multi_hop_neighbor", wrapped)


def _cap_too_small(monkeypatch):
    """Hop 2's cap at an eighth of what holds: the largest ids are
    dropped, and counted."""
    _wrap_expansion(monkeypatch, lambda orig, adjs, roots, caps: orig(
        adjs, roots, [caps[0], caps[1] // 8]))


def _padding_counted(monkeypatch):
    import jax
    import jax.numpy as jnp

    from euler_tpu.nn import sparse_aggregators

    monkeypatch.setattr(
        sparse_aggregators, "_degree",
        lambda src, mask, n: jax.ops.segment_sum(
            jnp.ones_like(mask), src, num_segments=n))


def _shared_neighbour_once(monkeypatch):
    """A dedup that drops edges with their duplicate nodes: only the
    first edge, in slot order, to each node of the next hop stays."""
    import jax
    import jax.numpy as jnp

    def change(orig, adjs, roots, caps):
        hops = orig(adjs, roots, caps)
        for h, cap in zip(hops, caps):
            at = jnp.arange(h["mask"].shape[0])
            first = jax.ops.segment_min(
                jnp.where(h["mask"] > 0, at, at.shape[0]), h["dst"],
                num_segments=cap)
            h["mask"] = h["w"] = h["mask"] * (first[h["dst"]] == at)
        return hops

    _wrap_expansion(monkeypatch, change)


def _self_left_out(monkeypatch):
    """The aggregator with the self branch taken out: its input at nought
    gives relu(0) = 0, or 0, and a zero gradient for its kernel."""
    from euler_tpu.nn import sparse_aggregators

    sound = sparse_aggregators.MeanAggregator

    class MeanAggregator(sound):      # the name is the parameters' path
        def __call__(self, inputs):
            self_emb, neigh_emb, adj = inputs
            return super().__call__((self_emb * 0.0, neigh_emb, adj))

    monkeypatch.setitem(sparse_aggregators.AGGREGATORS, "mean",
                        MeanAggregator)


def _second_hop_not_aggregated(monkeypatch):
    def change(orig, adjs, roots, caps):
        hops = orig(adjs, roots, caps)
        hops[-1]["mask"] = hops[-1]["w"] = hops[-1]["mask"] * 0.0
        return hops

    _wrap_expansion(monkeypatch, change)


@pytest.mark.parametrize("plant", [
    _cap_too_small, _padding_counted, _shared_neighbour_once,
    _self_left_out, _second_hop_not_aggregated,
], ids=["neighbour_dropped", "padding_counted", "shared_neighbour_once",
        "self_left_out", "second_hop_not_aggregated"])
def test_fault_planted_in_the_program_is_not_correct(
        data_root, monkeypatch, caplog, plant):
    _fresh_ledger()
    plant(monkeypatch)
    with caplog.at_level(logging.INFO, logger="benchmark"):
        r = _run(data_root, TOY_CELL, seed=2**31 + 5)
    assert r["correct"] is False, r["compared"]
    judged = [rec.getMessage() for rec in caplog.records
              if "expansion against the graph function" in rec.getMessage()]
    off = any("missing 0, extra 0, twice 0, dropped 0" not in s
              for s in judged)
    if plant is _cap_too_small:
        # dropped without a word no longer: counted by the program in its
        # step, and found by the judgement
        assert _counters()["overflow_nodes"] > 0
        assert off and any("dropped 0" not in s for s in judged)
        assert r["compared"]["change_gap"]["value"] >= 1
    elif plant in (_shared_neighbour_once, _second_hop_not_aggregated):
        assert off and _counters()["overflow_nodes"] == 0
    else:
        # the expansion is sound: the aggregation's numbers see these
        assert not off
        gaps = r["compared"]
        assert gaps["loss_gap"]["value"] > gaps["loss_gap"]["limit"]


# ---------------------------------------------------------------------------
# scopes, readers
# ---------------------------------------------------------------------------


def test_step_names_the_two_scopes_and_readers_claim_them(data_root):
    import jax

    from euler_tpu import trace

    assert {"expand", "segment_agg"} <= set(trace.STEP_SCOPES)
    claimed = scopes.declared_scopes()
    assert claimed["expand"] == "expand.scope_ms"
    assert claimed["segment_agg"] == "step.segment_agg_ms"
    prep = harness.Prepared(TOY, TOY_CELL, time.time(), require_chip=False,
                            data_root=data_root)
    try:
        from euler_tpu import train as train_lib

        opt = train_lib.get_optimizer("adam", 0.01)
        _, state = prep.ref.init_state(prep.cfg, jax.random.PRNGKey(0), opt)
        state["consts"] = prep.consts
        batch = prep.model.device_sample_batch(np.arange(BATCH))
        text = jax.jit(prep.model.make_train_step(opt)).lower(
            state, batch).compile().as_text()
    finally:
        prep.close()
    table = scopes.parse_hlo_scopes(text)
    assert {"expand", "segment_agg", "dense", "gather_features"} <= set(
        table.values())
    # the sort is the expansion's, the matmuls stay the dense layers'
    named = {}
    for line in text.splitlines():
        m = scopes._INSTRUCTION.match(line)
        if m:
            named[scopes._bare(m.group(1))] = line
    sorts = [n for n, ln in named.items() if " sort(" in ln]
    dots = [n for n, ln in named.items()
            if " dot(" in ln or " convolution(" in ln]
    assert sorts and all(table[n] == "expand" for n in sorts)
    assert dots and all(table[n] == "dense" for n in dots), {
        n: table[n] for n in dots}


def _ctx(scope_ms=None, **kw):
    ctx = types.SimpleNamespace(
        capture=object(), xplane_path="x", trace_steps=100,
        peaks={"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}, **kw)
    ctx._scope_ms = scope_ms
    return ctx


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmark", "layers", name + ".py"),
        "test_gcn_layer_" + name.replace(".", "_"))


def test_readers_read_the_scopes_and_stay_silent_on_a_program_without():
    c = costs.step_costs(_cfg(), 512, True)
    ms = {"expand": 30.0, "segment_agg": 150.0, "gather_features": 80.0,
          "gather_labels": 0.5, "dense": 2.0}
    ctx = _ctx(ms, costs=c)
    assert _reader("expand.scope_ms").read(ctx) == 30.0
    assert _reader("step.segment_agg_ms").read(ctx) == 150.0
    share = _reader("segment.traffic_roofline").read(ctx)
    least_ms = (c["gather_bytes"] + c["message_bytes"]) / 819e9 * 1e3
    assert share == pytest.approx(100 * least_ms / 230.5) and share < 1
    # the parent's program names neither scope: nothing is reported
    before = _ctx({"gather_features": 80.0, "dense": 2.0}, costs=c)
    for name in ("expand.scope_ms", "step.segment_agg_ms",
                 "segment.traffic_roofline"):
        assert _reader(name).read(before) is None, name
    # another family's cost function counts no message
    other = _ctx(ms, costs={"gather_bytes": 1.0})
    assert _reader("segment.traffic_roofline").read(other) is None


def test_slot_fill_reads_the_programs_counters():
    from euler_tpu import telemetry
    from euler_tpu.graph.native import counter_add

    _fresh_ledger()
    reader = _reader("expand.slot_fill")
    assert reader.read(_ctx()) is None          # nothing expanded
    counter_add("expand_slots", 1873920 * 3)
    counter_add("expand_edges", 414371 * 3)
    assert reader.read(_ctx()) == pytest.approx(22.1125, rel=1e-4)
    text = telemetry.metrics_text()
    assert "eg_expand_slots 5621760" in text
    assert "eg_expand_overflow_nodes 0" in text
    _fresh_ledger()


def test_graph_function_is_the_ppi_cells(ref):
    """The degree law and widths of graphsage_ppi under another seed."""
    cfg, ppi = _cfg(), _cfg(
        os.path.join(ROOT, "benchmark", "configs", "graphsage_ppi.json"))
    a, b = dict(cfg["graph"]), dict(ppi["graph"])
    assert a.pop("graph_seed") != b.pop("graph_seed") and a == b
    spec = graphgen.spec_from_config(cfg)
    pos, child = ref.neighbours(spec, np.arange(1000, 1100))
    assert len(pos) == int(spec.degrees(np.arange(1000, 1100)).sum())
    assert child.min() >= 0 and child.max() < spec.num_nodes
