"""The ``gcn_attention_ppi`` configuration's own files (its reference, its
cost function, its two readers, its entries in BENCHMARK.json), on the
CPU: the ragged reference against a softmax written out dense ``[n, m]``
on a graph of five nodes with a row that lists one neighbour twice; the
toy cell (``toy/toy_gcn_attention.json`` under
``BENCHMARK_toy_gcn_attention.json``: the configuration's reference and
cost function at 2,000 nodes and 16 roots, every width the recipe's)
through the harness and ``train()``, device- and host-expanded; the
control and every planted fault coming out as not correct, in the
reference put in the program's place and (padding in the softmax) in the
program's own step, the one share for an edge listed twice on a graph of
256 nodes where such rows are common (``toy_gcn_attention_twice``); the
scope, the readers, the route-log lines; the refusal of a program from
before the cell.
"""

import json
import logging
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from benchmark import check, costs, harness, manifest, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "gcn_attention_ppi.json")
MEAN_CONFIG = os.path.join(ROOT, "benchmark", "configs", "gcn_ppi.json")
TOY = os.path.join(HERE, "BENCHMARK_toy_gcn_attention.json")
TOY_CONFIG = os.path.join(HERE, "toy", "toy_gcn_attention.json")
CELL, MEAN_CELL = "gcn_attention_ppi_device_train", "gcn_ppi_device_train"
TOY_CELL, TOY_HOST = "toy_gcn_attention_device", "toy_gcn_attention_host"
# 256 nodes: about one row in seventeen lists a neighbour twice
TOY_TWICE = "toy_gcn_attention_twice_device"
NEW_METRICS = ("step.edge_softmax_ms", "attention.traffic_roofline")
FAULTS = ("padding_in_softmax", "duplicate_edge_once", "self_left_out",
          "heads_averaged", "one_gate", "slope_0p2",
          "second_hop_not_aggregated")
NODES, BATCH, WIDTH = 2000, 16, 12


def _cfg(path=CONFIG):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(
        os.path.join(ROOT, _cfg()["reference"]), "test_attention_reference")


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("toy_gcn_attention_data"))


def _run(data_root, cell, seed, **kw):
    return harness.run_cell(TOY, cell, seed, 0.2, False, time.time(),
                            require_chip=False, data_root=data_root, **kw)


def _fresh_route_log():
    """The route log ready to say its lines again (each is said once a
    shape and process)."""
    from euler_tpu.models import gcn as gcn_models
    from euler_tpu.nn import sparse_aggregators

    gcn_models._log_message_route.cache_clear()
    sparse_aggregators._log_aggregate_route.cache_clear()
    sparse_aggregators._log_attention_route.cache_clear()


# ---------------------------------------------------------------------------
# the files and the entries
# ---------------------------------------------------------------------------


def test_manifest_takes_the_configuration_as_files_and_entries():
    assert manifest.problems(MANIFEST) == []
    m = harness.load_json(MANIFEST)
    (entry,) = [c for c in m["configs"] if c["name"] == "gcn_attention_ppi"]
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert entry["reduced"] == [] and cell["config"] == "gcn_attention_ppi"
    assert entry["file"] == "benchmark/configs/gcn_attention_ppi.json"
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert (cell["traffic"], cell["chips"]) == ("train_device_sampled", 1)
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "edges_per_s_chip"
        assert by_name[name]["layer"] == "model_step"
    # the cell reports what the mean cell reports, but the mean's own
    # share of its roofline (the cost function counts no message_bytes);
    # every other metric stays without it
    with_mean = {n for n, x in by_name.items() if MEAN_CELL in x["workloads"]}
    with_cell = {n for n, x in by_name.items() if CELL in x["workloads"]}
    assert with_cell == (
        with_mean - {"segment.traffic_roofline"}) | set(NEW_METRICS)
    assert "step.mfu_roofline" in with_cell
    assert "trainer.step_ms_p99" not in with_cell


def test_reference_and_costs_bind_the_protocol(ref):
    cfg = _cfg()
    for key, functions in manifest.CONFIG_FILES.items():
        bound = manifest.bound_names(os.path.join(ROOT, cfg[key]))
        assert set(functions) <= bound, key
    for name in manifest.CONFIG_FILES["reference"]:
        assert callable(getattr(ref, name))
    assert ref.FAULTS == FAULTS
    # the benchmark's copy imports nothing of the program
    for key in ("reference", "costs"):
        with open(os.path.join(ROOT, cfg[key])) as f:
            text = f.read()
        for word in ("import euler_tpu", "from euler_tpu"):
            assert word not in text, (key, word)


def test_configuration_states_the_recipe_in_flags_the_program_has():
    from euler_tpu import run_loop
    from euler_tpu.nn import sparse_aggregators

    cfg, mean = _cfg(), _cfg(MEAN_CONFIG)
    assert cfg["reduced"] == [] and len(cfg["guarantees"]) == 4
    assert (cfg["batch_size"], cfg["dim"], cfg["aggregator"],
            cfg["use_residual"], cfg["sigmoid_loss"], cfg["optimizer"],
            cfg["learning_rate"], cfg["feature_dim"], cfg["label_dim"]) == (
        512, 256, "attention", False, True, "adam", 0.01, 50, 121)
    # gcn_ppi but for the aggregator: the same graph, caps and flags
    assert cfg["graph"] == mean["graph"]
    assert cfg["flags"] == dict(mean["flags"], aggregator="attention")
    assert cfg["limits"].keys() == mean["limits"].keys()
    assert cfg["assumed"][:len(mean["assumed"])] == mean["assumed"]
    # every flag the cell sets but these four is define_flags()'s own
    # default: the upstream recipe
    defaults = run_loop.define_flags().parse_args([])
    for k, v in cfg["flags"].items():
        assert hasattr(defaults, k), k
        if k not in ("model", "aggregator", "max_id", "fanouts"):
            assert getattr(defaults, k) == v, k
    assert defaults.batch_size == cfg["batch_size"]
    assert defaults.use_residual is cfg["use_residual"]
    # what no flag sets: the aggregator's own default heads, and the
    # slope the program computes
    agg = sparse_aggregators.AttentionAggregator(dim=cfg["dim"])
    assert agg.num_heads == cfg["num_heads"] == 4 and agg.renorm is False
    import flax.linen as nn

    assert float(nn.leaky_relu(-1.0)) == pytest.approx(
        -cfg["attention_leaky_slope"])
    toy = _cfg(TOY_CONFIG)
    for k in ("dim", "aggregator", "num_heads", "attention_leaky_slope",
              "feature_dim", "label_dim", "num_classes", "optimizer",
              "learning_rate", "limits", "guarantees"):
        assert toy[k] == cfg[k], k
    assert toy["fanouts"] == [WIDTH, WIDTH] == [
        toy["graph"]["max_degree"]] * 2


# ---------------------------------------------------------------------------
# the reference against a softmax written out dense
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
        return np.stack([np.cos(ids), np.sin(2.0 * ids)], axis=1)

    def labels(self, ids):
        return (np.asarray(ids)[:, None] % 2 == np.arange(3)[None, :] % 2
                ).astype(np.float32)


TINY = dict(feature_dim=2, dim=8, num_classes=3, aggregator="attention",
            num_heads=4, attention_leaky_slope=0.01, fanouts=[3, 3],
            learning_rate=0.01, graph={"max_degree": 3})
KEY = (4, 0.01, 3)


def _dense_loss(spec, roots, p, slope=0.01):
    """The step on ``roots`` with every softmax written out dense
    ``[n, m]`` in float64: ``count[i, j]`` edges from ``i`` to ``j``,
    each with a share of its own."""
    ids = np.arange(spec.num_nodes)
    count = np.zeros((spec.num_nodes,) * 2)
    for i in ids:
        for j in spec.slab[i][:spec.deg[i]]:
            count[i, j] += 1
    # the hops' sets as indicator vectors: a node off a set has no row
    s0 = np.asarray(roots)
    s1 = np.flatnonzero(count[s0].sum(0))
    n = {k: np.asarray(v, np.float64) for k, v in p.items()}

    def layer(tail, x, parents, act):
        """Rows of ``parents`` (ids, a root twice has two rows) from
        every node's row ``x`` [N, F]."""
        heads = []
        for k in range(4):
            w, u, v = (n["%s%s_%d" % (c, tail, k)] for c in "wuv")
            proj = x @ w
            logit = (proj @ u)[parents, None] + (proj @ v)[None, :]
            e = count[parents] * np.exp(
                np.where(logit > 0, logit, slope * logit))
            h = proj[parents] + e @ proj / e.sum(1, keepdims=True)
            heads.append(np.maximum(h, 0) if act else h)
        return np.concatenate(heads, 1)

    x = spec.features(ids).astype(np.float64)
    h = np.zeros((spec.num_nodes, 8))
    h[s1] = layer("0", x, s1, True)
    h0 = layer("0", x, s0, True)
    # layer 2 on the roots: their own layer-1 rows, hop 1's as neighbours
    heads = []
    for k in range(4):
        w, u, v = (n["%s1_%d" % (c, k)] for c in "wuv")
        p_s, p_a = h0 @ w, h @ w
        logit = (p_s @ u)[:, None] + (p_a @ v)[None, :]
        e = count[s0] * np.exp(np.where(logit > 0, logit, slope * logit))
        heads.append(p_s + e @ p_a / e.sum(1, keepdims=True))
    logits = np.concatenate(heads, 1) @ n["w_out"] + n["b_out"]
    y = spec.labels(s0)
    return (np.maximum(logits, 0) - logits * y
            + np.log1p(np.exp(-np.abs(logits)))).mean()


def test_ragged_softmax_is_the_dense_softmax(ref):
    import jax

    spec = FiveNodes()
    p = jax.jit(lambda k: ref.init_params(TINY, k))(jax.random.PRNGKey(3))
    assert set(p) == set(ref.param_shapes(TINY)) and len(p) == 26
    assert p["w0_2"].shape == (2, 2) and p["u1_3"].shape == (2,)
    for roots in ([0, 2], [0, 0, 4], [3]):
        a = ref.step_arrays(spec, roots)
        loss = float(ref.loss_fn(p, a, KEY))
        assert loss == pytest.approx(_dense_loss(spec, roots, p), rel=1e-5)
    # root 0's three edges: node 3 twice (two shares), node 1 once
    a = ref.step_arrays(spec, [0, 2])
    assert list(zip(a["p0"], a["c0"])) == [(0, 1), (0, 1), (0, 0), (1, 2)]
    loss = float(ref.loss_fn(p, a, KEY))
    # the fill to a compile bucket changes neither loss nor gradient: an
    # edge of the parent past the last is in no node's softmax
    filled = ref.bucketed(a)
    assert len(filled["x1"]) == 8 and filled["p0"].tolist() == [
        0, 0, 0, 1, 2, 2, 2, 2]
    assert filled["p1"][-1] == len(filled["x1"]) == 8
    assert float(ref.loss_fn(p, filled, KEY)) == pytest.approx(loss, rel=1e-6)
    grads = [jax.grad(lambda q, arrays=arrays: ref.loss_fn(q, arrays, KEY))(p)
             for arrays in (a, filled)]
    for k in p:
        np.testing.assert_allclose(grads[0][k], grads[1][k], rtol=1e-5,
                                   atol=1e-8)
    # each fault changes the loss by its own rule; the other slope is the
    # dense form's at that slope
    for fault in FAULTS:
        arrays = ref.step_arrays(spec, [0, 2], fault)
        assert abs(float(ref.loss_fn(p, arrays, KEY, fault=fault))
                   - loss) > 1e-5, fault
    assert float(ref.loss_fn(p, a, KEY, fault="slope_0p2")) == pytest.approx(
        _dense_loss(spec, [0, 2], p, slope=0.2), rel=1e-5)
    once = ref.step_arrays(spec, [0, 2], "duplicate_edge_once")
    assert list(zip(once["p0"], once["c0"])) == [(0, 1), (0, 0), (1, 2)]
    start = dict(p)
    start[ref.EXPANSION] = start[ref.OVERFLOW] = np.zeros(1, np.float32)
    batch = {"spec": spec, "roots": np.array([0, 2]), "off": 0}
    losses, g, end = ref.train_steps(TINY, start, [batch] * 3)
    assert abs(losses[0] - loss) < 1e-6 and losses[2] < losses[0]
    assert set(g) == set(p) and float(end[ref.EXPANSION][0]) == 0
    # a planted fault adds nothing of its own to the judgement's leaf:
    # what the comparison sees of it, it sees in the numbers
    end = ref.train_steps(TINY, start, [batch], fault="duplicate_edge_once")[2]
    assert float(end[ref.EXPANSION][0]) == 0
    assert all(np.isfinite(np.asarray(v)).all() for v in g.values())


def test_adapter_maps_the_names_onto_the_programs_tree(ref):
    import jax

    p = jax.jit(lambda k: ref.init_params(TINY, k))(jax.random.PRNGKey(1))
    tree = ref.to_program(p)
    head = tree["encoder"]["AttentionAggregator_1"][
        "SingleAttentionAggregator_2"]
    assert head["Dense_0"]["Dense_0"]["kernel"].shape == (8, 2)
    # a gate is a [D, 1] kernel in the program
    assert head["Dense_2"]["Dense_0"]["kernel"].shape == (2, 1)
    back = ref.from_program(tree, tuple(p))
    assert all((np.asarray(back[k]) == np.asarray(p[k])).all() for k in p)


# ---------------------------------------------------------------------------
# the cost function
# ---------------------------------------------------------------------------


def test_cost_function_counts_true_edges_never_slots():
    cfg = _cfg()
    c = costs.step_costs(cfg, 512, True)
    mean = costs.step_costs(_cfg(MEAN_CONFIG), 512, True)
    # the mean cell's constant, edge for edge
    assert c["edges"] == mean["edges"] == 414371
    assert c["unique_nodes"] == mean["unique_nodes"] == 378879
    assert c["gather_bytes"] == mean["gather_bytes"]
    assert c["draw_bytes"] == 0 and "message_bytes" not in c
    # per true edge and head a 64-wide message and two scalars; layer 2's
    # edges twice more
    e1 = 512 * 28.0
    assert c["attention_bytes"] == pytest.approx(
        (414371 + 2 * e1) * 4 * 66 * 4, rel=1e-4)
    assert c["bytes"] == pytest.approx(
        c["gather_bytes"] + c["attention_bytes"] + c["expand_bytes"]
        + c["opt_bytes"])
    assert c["params"] == (50 * 256 + 2 * 256) + (256 * 256 + 2 * 256) \
        + 256 * 121 + 121
    # the projections of 378,879 unique rows dominate the operations
    assert c["flops"] > 2 * 2 * 378879 * 50 * 256
    assert costs.step_costs(cfg, 512, False)["edges"] == c["edges"]


def test_expected_edges_are_the_programs_unmasked_edges(ref, data_root):
    """The cost function's expected true edges against the mean count of
    unmasked edges of the program's own expansion over seeded steps of
    the toy cell: within 1% (3,000 steps, as ``test_gcn.py`` reckons)."""
    prep = harness.Prepared(TOY, TOY_CELL, time.time(), require_chip=False,
                            data_root=data_root)
    try:
        fn = ref._gcn._expansion_fn(prep.model.module)
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
def test_toy_cell_is_correct_and_says_the_forms_it_took(
        data_root, caplog, cell):
    _fresh_route_log()
    with caplog.at_level(logging.INFO):
        r = _run(data_root, cell, seed=2**31 + 5, calibrate=True)
    assert r["correct"] is True, r["compared"]
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
    routes = [s.split(": ", 1)[1] for s in said if s.startswith(
        ("message path:", "aggregate path:", "attention path:"))]
    if cell == TOY_CELL:
        assert routes == [
            "hop 1 192 slots -> one pass from the stored table (128 lanes)",
            "hop 2 2304 slots -> one pass from the stored table (128 lanes)",
            "192 slots -> row sum over 12",
            "192 slots x 4 heads -> row softmax over 12",
            "2304 slots -> row sum over 12",
            "2304 slots x 4 heads -> row softmax over 12",
        ]
    else:
        # a host-expanded batch's lists arrive as arguments of the step
        assert routes and all(
            "from the hop's rows (host-expanded batch)" in s
            or s.endswith(("segment sum (traced src)", "segment softmax"))
            for s in routes)
        assert any(s.endswith("x 4 heads -> segment softmax")
                   for s in routes)


def _planted(data_root, cell):
    """One sound run of a toy cell, and the reference with each fault in
    the program's place by the cell's numbers."""
    faults = harness.load_module(
        os.path.join(ROOT, "benchmark", "configs",
                     "scalable_sage_reddit_faults.py"),
        "test_attention_faults")
    prep = harness.Prepared(TOY, cell, time.time(), require_chip=False,
                            data_root=data_root)
    try:
        hook = prep.drive(2**31 + 21, 0.0, first_steps_only=True)
        sound = prep.compare(hook)
        return prep.cfg["limits"], sound, faults.fault_numbers(prep, hook)
    finally:
        prep.close()


@pytest.fixture(scope="module")
def planted(data_root):
    return _planted(data_root, TOY_CELL)


@pytest.fixture(scope="module")
def planted_twice(data_root):
    return _planted(data_root, TOY_TWICE)


@pytest.mark.parametrize(
    "fault", [f for f in FAULTS if f != "duplicate_edge_once"])
def test_fault_planted_in_the_reference_is_not_correct(planted, fault):
    limits, sound, numbers = planted
    assert check.verdict(sound, limits)[0], sound
    ok, table = check.verdict(numbers["fault_" + fault], limits)
    assert not ok, table


def test_one_share_for_an_edge_listed_twice_is_not_correct(planted_twice):
    """The softmax's two-share rule, where rows that list a neighbour
    twice are common (at the timed cell's 2M nodes they are one to six
    edges of 414,000 a step, and no number sees the fault there): the
    program is correct, and the reference with ``duplicate_edge_once``
    in its place fails by the gradient alone, not by a leaf of the
    judgement that the fault filled in itself."""
    limits, sound, numbers = planted_twice
    assert check.verdict(sound, limits)[0], sound
    once = numbers["fault_duplicate_edge_once"]
    assert once["grad_gap"] > 10 * limits["grad_gap"], once
    assert sound["grad_gap"] < limits["grad_gap"] / 10, sound


def test_padding_in_the_programs_softmax_is_not_correct(
        data_root, monkeypatch):
    """The same fault in the program's own step: the mask kept off the
    softmax's support, so that a padded slot takes a share."""
    import jax.numpy as jnp

    from euler_tpu.nn import sparse_aggregators

    sound = sparse_aggregators._edge_weights
    monkeypatch.setattr(
        sparse_aggregators, "_edge_weights",
        lambda logits, segments, n, mask, *rest: sound(
            logits, segments, n, jnp.ones_like(mask), *rest))
    r = _run(data_root, TOY_CELL, seed=2**31 + 5)
    assert r["correct"] is False, r["compared"]
    gaps = r["compared"]
    assert gaps["grad_gap"]["value"] > gaps["grad_gap"]["limit"]


# ---------------------------------------------------------------------------
# the scope, the readers, the refusal
# ---------------------------------------------------------------------------


def test_step_names_the_scope_and_a_reader_claims_it(data_root):
    import jax

    from euler_tpu import trace

    assert "edge_softmax" in trace.STEP_SCOPES
    claimed = scopes.declared_scopes()
    assert claimed["edge_softmax"] == "step.edge_softmax_ms"
    # every scope of the program is claimed by exactly one reader
    # (declared_scopes raises where two claim one)
    assert set(trace.STEP_SCOPES) <= set(claimed)
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
    assert {"expand", "segment_agg", "edge_softmax", "dense",
            "gather_features"} <= set(table.values())
    named = {}
    for line in text.splitlines():
        m = scopes._INSTRUCTION.match(line)
        if m:
            named[scopes._bare(m.group(1))] = line
    # the softmax's exponentials are the scope's own, innermost; the
    # matmuls stay the dense layers' but the one that hands a head's
    # weight to its lanes; no scatter is left in the step but the
    # expansion's and the transposed gather of layer 2's messages
    exps = [n for n, ln in named.items() if " exponential(" in ln]
    dots = [n for n, ln in named.items()
            if " dot(" in ln or " convolution(" in ln]
    scatters = [n for n, ln in named.items() if " scatter(" in ln]
    assert exps and all(table[n] in ("edge_softmax", "loss") for n in exps)
    assert any(table[n] == "edge_softmax" for n in exps)
    assert {table[n] for n in dots} == {"dense", "edge_softmax"}, {
        n: table[n] for n in dots}
    assert all(table[n] in ("expand", "segment_agg") for n in scatters), {
        n: table[n] for n in scatters}


def _ctx(scope_ms=None, **kw):
    ctx = types.SimpleNamespace(
        capture=object(), xplane_path="x", trace_steps=100,
        peaks={"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}, **kw)
    ctx._scope_ms = scope_ms
    return ctx


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmark", "layers", name + ".py"),
        "test_attention_layer_" + name.replace(".", "_"))


def test_readers_read_the_scope_and_stay_silent_on_a_program_without():
    c = costs.step_costs(_cfg(), 512, True)
    ms = {"expand": 33.0, "segment_agg": 2.0, "edge_softmax": 60.0,
          "gather_features": 27.0, "gather_labels": 0.5, "dense": 20.0}
    ctx = _ctx(ms, costs=c)
    assert _reader("step.edge_softmax_ms").read(ctx) == 60.0
    share = _reader("attention.traffic_roofline").read(ctx)
    least_ms = (c["gather_bytes"] + c["attention_bytes"]) / 819e9 * 1e3
    assert share == pytest.approx(100 * least_ms / 89.0) and share < 1
    # the mean's share of its roofline finds no message_bytes here
    assert _reader("segment.traffic_roofline").read(ctx) is None
    # the parent's program names no such scope: nothing is reported
    before = _ctx({"gather_features": 27.0, "segment_agg": 700.0}, costs=c)
    for name in NEW_METRICS:
        assert _reader(name).read(before) is None, name
    # another family's cost function counts no attention_bytes
    other = _ctx(ms, costs=costs.step_costs(_cfg(MEAN_CONFIG), 512, True))
    assert _reader("attention.traffic_roofline").read(other) is None


CHILD = r"""
import functools, os, runpy, sys
sys.path.insert(0, {root!r})
os.chdir({root!r})
from benchmark import harness
harness.run_cell = functools.partial(
    harness.run_cell, require_chip=False, data_root={data!r})
# the parent commit's program: --aggregator attention, no edge_softmax
# scope. Struck when the module is first loaded, whoever asks for it
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
                s for s in module.STEP_SCOPES if s != "edge_softmax")
        spec.loader.exec_module = struck
        return spec
sys.meta_path.insert(0, Strike())
sys.argv = ["benchmark/run.py", "--manifest", {manifest!r}, "--workload",
            {cell!r}, "--seed", "2147483655", "--seconds", "0.3",
            "--trace", "0"]
runpy.run_path("benchmark/run.py", run_name="__main__")
"""


def test_a_program_from_before_the_cell_is_refused_at_load(data_root):
    """``benchmark/run.py`` itself on a program that lists no
    ``edge_softmax`` scope, as the commit before the cell's does: the
    reference refuses it at load, before a model is built: exit code 1,
    a message that names the scope, no traceback and no result line."""
    code = CHILD.format(root=ROOT, data=data_root, manifest=TOY,
                        cell=TOY_CELL)
    child = subprocess.run(
        [sys.executable, "-c", code], env=dict(os.environ,
                                               JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=240)
    assert child.returncode == 1, child.stderr[-2000:]
    assert "trace.STEP_SCOPES lacks edge_softmax" in child.stderr
    assert "this cell cannot run on it; no result" in child.stderr
    assert "Traceback" not in child.stderr
    assert not child.stdout.strip()
