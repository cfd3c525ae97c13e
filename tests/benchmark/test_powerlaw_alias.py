"""The heavy-tailed Reddit cell's mechanism at toy size (PR 44): the
sampling form belongs to ``run_loop.build_model``, and the exact alias
draw goes through ``harness.run_cell`` as files and entries.

* the two toy cells of ``BENCHMARK_toy_powerlaw_alias.json`` (the toy
  power-law graph, and the cell's own graph function at 3,000 nodes) run
  the alias draw on both hops, build no slab, and read ``correct`` true;
* planted faults read ``correct`` false: a uniform draw over slots (the
  weight-ranked ``draw_skew``), a slab kept to each row's 60 heaviest
  edges (``draw_skew``), a drawn id that is no neighbour
  (``draw_foreign``);
* the alias draw's pmf on the longest rows against the exact pmf from the
  graph function, under the bound ``scripts/powerlaw_alias_study.py``
  states on the chip;
* ``build_model`` applies ``--alias_sampling`` / ``--max_degree`` for
  every caller (``serve.py`` too), and the full-neighbourhood models still
  refuse the alias form;
* the cell's reference refuses, in a child ``benchmark/run.py`` and
  within seconds, a program whose ``build_model`` ignores the flag, and
  leaves nothing alive;
* ``build_adjacency`` refuses a slab the device cannot hold; the alias
  tables' gauges; the cost function against ``graphsage_costs.py``; the
  new reader.
"""

import importlib.util
import json
import logging
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from benchmark import costs, harness

from .test_node2vec import _session_members

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "BENCHMARK_toy_powerlaw_alias.json")
TOY_CELL = "toy_powerlaw_alias_device"
CELL = "toy_reddit_powerlaw_device"
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
GRAPH = os.path.join(CONFIGS, "graphsage_reddit_powerlaw_graph.py")


def _study():
    spec = importlib.util.spec_from_file_location(
        "powerlaw_alias_study",
        os.path.join(ROOT, "scripts", "powerlaw_alias_study.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(name="toy_reddit_powerlaw"):
    return harness.load_json(os.path.join(HERE, "toy", name + ".json"))


@pytest.fixture(scope="module")
def spec():
    return harness.load_module(GRAPH, "test_reddit_powerlaw_graph") \
        .spec_from_config(_cfg())


@pytest.fixture(scope="module")
def toy_graph(tmp_path_factory, spec):
    import euler_tpu

    d = str(tmp_path_factory.getbasetemp() / "benchmark_toy_data"
            / "reddit_powerlaw_direct")
    graph = euler_tpu.Graph(directory=spec.write(d))
    yield d, graph
    graph.close()


def _args(data_dir, *extra, cfg=None):
    from euler_tpu import run_loop

    cfg = cfg or _cfg()
    cell = types.SimpleNamespace(cfg=cfg, global_batch=cfg["batch_size"],
                                 chips=1, traffic={"flags": {}})
    argv = harness.Cell.argv(cell, data_dir) + [
        "--device_sampling", "true"] + list(extra)
    return run_loop.define_flags().parse_args(argv)


def _run(tmp_path_factory, cell, seed, manifest=MANIFEST):
    from benchmark import sage_reference

    # the adapter keeps its jitted draw entry by module
    sage_reference._hops_fn.cache_clear()
    data = tmp_path_factory.getbasetemp() / "benchmark_toy_data"
    try:
        return harness.run_cell(manifest, cell, seed, 0.2, False, time.time(),
                                require_chip=False, data_root=str(data))
    finally:
        sage_reference._hops_fn.cache_clear()


# ---------------------------------------------------------------------------
# the cells
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", [TOY_CELL, CELL])
def test_toy_cell_draws_through_the_alias_tables(tmp_path_factory, caplog,
                                                 cell):
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        r = _run(tmp_path_factory, cell, 2147483659)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["draw_foreign"]["value"] == 0
    b = _cfg("toy_powerlaw_alias" if cell == TOY_CELL
             else "toy_reddit_powerlaw")["batch_size"]
    f1 = 3 if cell == TOY_CELL else 4
    for m in (b, b * f1):
        assert ("-> alias draw (flat-CSR alias adjacency)" in caplog.text
                and f"neighbor draw {m}x" in caplog.text), m
    assert "alias tables for edge types [0]:" in caplog.text
    # no slab was built, so none was packed or left unpacked
    assert "device sampling et0" not in caplog.text
    assert "Pallas" not in caplog.text


def test_the_cells_degree_law_is_as_its_file_states():
    """The real configuration's graph function, at 2,090,000 nodes: the
    ``assumed`` list's numbers (degrees only; no list is expanded)."""
    cfg = harness.load_json(os.path.join(CONFIGS,
                                         "graphsage_reddit_powerlaw.json"))
    law = harness.load_module(GRAPH, "test_reddit_powerlaw_graph_real") \
        .spec_from_config(cfg).degree_law()
    assert abs(law["mean"] / 50 - 1) < 0.02
    assert law["edges"] == 105_962_867 and law["max"] >= 20_000
    assert law["nodes_at_cap"] < cfg["graph"]["num_nodes"] / 10_000
    assert law["p50"] == 6 and law["edge_share_over_60"] > 0.8


# ---------------------------------------------------------------------------
# planted faults
# ---------------------------------------------------------------------------

def _uniform_over_slots(monkeypatch):
    import jax.numpy as jnp

    from euler_tpu.graph import device as device_graph

    real = device_graph._alias_sample_neighbor

    def uniform(adj, nodes, key, count):
        # every slot keeps itself: a uniform draw over the row's slots
        return real(dict(adj, prob=jnp.ones_like(adj["prob"])), nodes, key,
                    count)

    monkeypatch.setattr(device_graph, "_alias_sample_neighbor", uniform)


def _no_neighbour(monkeypatch, spec):
    from euler_tpu.graph import device as device_graph

    _, nbr, _ = spec.neighbours(np.arange(spec.num_nodes))
    nobodys = int(np.nonzero(
        np.bincount(nbr, minlength=spec.num_nodes) == 0)[0][0])
    real = device_graph.sample_fanout

    def altered(adjs, roots, key, counts):
        ids = list(real(adjs, roots, key, counts))
        ids[1] = ids[1].at[0].set(nobodys)
        return ids

    monkeypatch.setattr(device_graph, "sample_fanout", altered)


def _truncated_slab_manifest(tmp_path) -> str:
    """The toy cell with its flags turned to a padded slab kept to each
    row's 60 heaviest edges (``--max_degree 60``, no alias tables)."""
    m = harness.load_json(MANIFEST)
    cfg = _cfg()
    cfg["flags"] = dict(cfg["flags"], alias_sampling="false", max_degree=60)
    for key in ("reference", "costs", "graph_function"):
        cfg[key] = os.path.normpath(os.path.join(HERE, cfg[key]))
    (tmp_path / "toy_reddit_powerlaw.json").write_text(json.dumps(cfg))
    for c in m["configs"]:
        c["file"] = str(tmp_path / "toy_reddit_powerlaw.json") \
            if c["name"] == "toy_reddit_powerlaw" \
            else os.path.join(HERE, c["file"])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    return str(path)


@pytest.mark.parametrize("fault,number", [
    ("uniform_over_slots", "draw_skew"),
    ("slab_of_60", "draw_skew"),
    ("no_neighbour", "draw_foreign"),
])
def test_a_planted_fault_is_not_correct(tmp_path_factory, tmp_path,
                                        monkeypatch, spec, fault, number):
    manifest = MANIFEST
    if fault == "uniform_over_slots":
        _uniform_over_slots(monkeypatch)
    elif fault == "no_neighbour":
        _no_neighbour(monkeypatch, spec)
    else:
        manifest = _truncated_slab_manifest(tmp_path)
    r = _run(tmp_path_factory, CELL, 2147483671, manifest)
    assert r["correct"] is False
    row = r["compared"][number]
    assert row["value"] > row["limit"], r["compared"]
    if fault == "uniform_over_slots":
        # 0.146 on a long row; hop 1's short rows dilute it (0.126 read)
        assert row["value"] > 0.06
        assert r["compared"]["draw_foreign"]["value"] == 0


# ---------------------------------------------------------------------------
# the draw's distribution
# ---------------------------------------------------------------------------

def test_alias_draw_matches_the_exact_pmf_on_the_longest_rows(toy_graph,
                                                             spec):
    """200,000 draws of each of the five longest rows and five rows
    shorter than 10, through the step's own draw over the tables the
    cell's model builds: each within the study's bound of the exact pmf,
    and a uniform draw over slots is not."""
    from euler_tpu import run_loop

    study = _study()
    data_dir, graph = toy_graph
    model = run_loop.build_model(_args(data_dir), graph)
    adj = model.build_consts(graph)["adj"]["et0"]
    deg = spec.degrees(np.arange(spec.num_nodes))
    rows = np.concatenate([np.argsort(deg, kind="stable")[-5:],
                           np.nonzero(deg < 10)[0][:5]])
    for row, draws in zip(rows, study.draw_rows(adj, rows, 200_000)):
        pmf = study.exact_pmf(spec, int(row))
        bound = study.tvd_bound(pmf, draws.size)
        assert study.tvd(draws, pmf) < bound, row
        assert study.pmf_tvd(study.slot_pmf(spec, int(row)), pmf) > bound
    assert deg[rows[-6]] > 400  # the rows are long


# ---------------------------------------------------------------------------
# build_model applies the sampling form
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra,form", [
    ((), "slab"),
    (("--alias_sampling", "true"), "alias"),
    (("--max_degree", "50"), "slab of 50"),
])
def test_build_model_applies_the_sampling_flags(toy_graph, spec, extra, form):
    from euler_tpu import run_loop

    data_dir, graph = toy_graph
    flags = ("--alias_sampling", "false") + tuple(extra)
    model = run_loop.build_model(_args(data_dir, *flags), graph)
    adj = model.build_consts(graph)["adj"]["et0"]
    if form == "alias":
        assert model.sampling_alias
        assert "off" in adj and "cum" not in adj and adj["nbr"].ndim == 1
        assert adj["nbr"].shape[0] == int(spec.degrees(
            np.arange(spec.num_nodes)).sum())
    else:
        assert not model.sampling_alias and "off" not in adj
        width = 50 if extra else int(spec.degrees(
            np.arange(spec.num_nodes)).max())
        assert adj["nbr"].shape == adj["cum"].shape == (
            spec.num_nodes + 1, width)


def test_serve_builds_the_model_the_flags_ask_for(toy_graph, monkeypatch):
    from euler_tpu import serve

    seen = {}

    def captured(model, graph, args, mesh, block=False):
        seen["model"] = model

    monkeypatch.setattr(serve, "run_serve", captured)
    data_dir, _ = toy_graph
    argv = harness.Cell.argv(types.SimpleNamespace(
        cfg=_cfg(), global_batch=8, chips=1, traffic={"flags": {}}),
        data_dir)
    assert serve.main(argv + ["--device_sampling", "true"]) == 0
    assert seen["model"].sampling_alias is True


@pytest.mark.parametrize("model", ["gcn", "scalable_gcn"])
def test_the_full_neighbourhood_models_still_refuse_the_alias_form(
        toy_graph, model):
    from euler_tpu import run_loop

    data_dir, graph = toy_graph
    with pytest.raises(ValueError, match="slab"):
        run_loop.build_model(_args(data_dir, "--model", model), graph)
    built = run_loop.build_model(
        _args(data_dir, "--model", model, "--alias_sampling", "false"),
        graph)
    assert not built.sampling_alias


# ---------------------------------------------------------------------------
# the reference refuses a program that ignores the flag
# ---------------------------------------------------------------------------

CHILD = r"""
import functools, os, runpy, sys
sys.path.insert(0, {root!r})
os.chdir({root!r})
from benchmark import harness
from euler_tpu import run_loop
# a program from before the cell: build_model leaves the flag to main()
run_loop.build_model = run_loop._dispatch_model
harness.run_cell = functools.partial(
    harness.run_cell, require_chip=False, data_root={data!r})
sys.argv = ["benchmark/run.py", "--manifest", {manifest!r}, "--workload",
            {cell!r}, "--seed", "2147483693", "--seconds", "0.3",
            "--trace", "0"]
runpy.run_path("benchmark/run.py", run_name="__main__")
"""


def test_the_reference_refuses_a_build_model_that_ignores_the_flag(
        tmp_path_factory):
    data = tmp_path_factory.getbasetemp() / "benchmark_toy_data"
    t0 = time.time()
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD.format(
            root=ROOT, data=str(data), manifest=MANIFEST, cell=CELL)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    sid = child.pid
    try:
        out, err = child.communicate(timeout=240)
    finally:
        if child.poll() is None:
            os.killpg(sid, 9)
    assert child.returncode == 1, err[-2000:]
    assert "does not apply --alias_sampling" in err
    assert "Traceback" not in err and not out.strip()
    # refused at the reference's load, before any table was built
    assert "alias tables" not in err and "feature table" not in err
    assert time.time() - t0 < 120
    assert _session_members(sid) == []


# ---------------------------------------------------------------------------
# the slab's guard, the gauges, the costs, the reader
# ---------------------------------------------------------------------------

def test_build_adjacency_refuses_a_slab_the_device_cannot_hold(
        toy_graph, spec, monkeypatch):
    from euler_tpu.graph import device as device_graph

    _, graph = toy_graph
    assert device_graph.device_memory_bytes() > 1 << 30
    n = spec.num_nodes
    width = int(spec.degrees(np.arange(n)).max())
    monkeypatch.setattr(device_graph, "device_memory_bytes",
                        lambda: (n + 1) * width * 8 - 1)
    with pytest.raises(ValueError, match="--alias_sampling true"):
        device_graph.build_adjacency(graph, [0], n - 1)
    # a cap that fits, and the alias tables, are built
    slab = device_graph.build_adjacency(graph, [0], n - 1, max_degree=60)
    assert slab["nbr"].shape == (n + 1, 60)
    assert "off" in device_graph.build_alias_adjacency(graph, [0], n - 1)


def test_alias_tables_set_their_gauges_and_say_so(toy_graph, spec, caplog):
    import euler_tpu
    from euler_tpu import telemetry as T
    from euler_tpu.graph import device as device_graph

    _, graph = toy_graph
    n = spec.num_nodes
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        adj = device_graph.build_alias_adjacency(graph, [0], n - 1)
    deg = spec.degrees(np.arange(n))
    nbytes = sum(a.nbytes for a in adj.values())
    assert nbytes == 12 * int(deg.sum()) + 9 * (n + 1) + len(
        adj["bisect_steps"])
    T.telemetry_reset()  # the gauges describe the tables: they stay
    res = T.telemetry_json()["resource"]
    assert (res["alias_edges"], res["alias_max_degree"],
            res["alias_table_bytes"]) == (int(deg.sum()), int(deg.max()),
                                          nbytes)
    text = euler_tpu.metrics_text()
    assert f"eg_alias_edges {int(deg.sum())}" in text
    assert f"eg_alias_max_degree {int(deg.max())}" in text
    assert (f"alias tables for edge types [0]: {int(deg.sum())} edges, "
            f"longest row {int(deg.max())}") in caplog.text


def test_alias_costs_are_graphsage_costs_but_the_draws():
    cfg = harness.load_json(os.path.join(CONFIGS, "graphsage_reddit.json"))
    alias_cfg = dict(cfg, costs="benchmark/configs/graphsage_alias_costs.py")
    for sampled in (True, False):
        slab = costs.step_costs(cfg, 1000, sampled)
        alias = costs.step_costs(alias_cfg, 1000, sampled)
        for k in slab:
            if k not in ("draw_bytes", "bytes"):
                assert alias[k] == slab[k], k
        assert alias["bytes"] - alias["draw_bytes"] == \
            slab["bytes"] - slab["draw_bytes"]
    # 5,000 rows drawn from at 9 B, 20,000 draws at 16 B
    alias = costs.step_costs(alias_cfg, 1000, True)
    assert alias["draw_bytes"] == 5000 * 9 + 20000 * 16 == 365_000
    assert alias["alias_draws"] == 20_000 and alias["edges"] == 20_000
    assert costs.step_costs(alias_cfg, 1000, False)["draw_bytes"] == 0
    # the cell's own file names it, and needs no slab width
    real = harness.load_json(os.path.join(
        CONFIGS, "graphsage_reddit_powerlaw.json"))
    assert "max_degree" not in real["graph"]
    assert costs.step_costs(real, 1000, True) == alias


def test_the_alias_roofline_reader(monkeypatch):
    from benchmark import scopes

    reader = harness.load_module(
        os.path.join(ROOT, "benchmark", "layers", "alias.traffic_roofline.py"),
        "test_alias_traffic_roofline")
    peaks = {"hbm_bytes_per_s": 819e9}
    ctx = types.SimpleNamespace(
        costs={"draw_bytes": 365_000.0, "alias_draws": 20_000}, peaks=peaks)
    monkeypatch.setattr(scopes, "scopes_ms", lambda c, *names: (
        0.5 if names == ("draw",) else None))
    assert reader.read(ctx) == pytest.approx(
        100 * 365_000 / 819e9 * 1e3 / 0.5)
    # silent on a slab cell's costs, on a CPU run, without a scope table
    assert reader.read(types.SimpleNamespace(
        costs={"draw_bytes": 1.0}, peaks=peaks)) is None
    assert reader.read(types.SimpleNamespace(
        costs=ctx.costs, peaks=None)) is None
    monkeypatch.setattr(scopes, "scopes_ms", lambda c, *names: None)
    assert reader.read(ctx) is None


def test_the_cell_is_in_the_manifest_with_its_entries():
    from benchmark import manifest

    path = os.path.join(ROOT, "BENCHMARK.json")
    assert manifest.problems(path) == []
    m = harness.load_json(path)
    (cell,) = [w for w in m["workloads"]
               if w["name"] == "reddit_powerlaw_device_train"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "graphsage_reddit_powerlaw", "train_device_sampled", 1)
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert by_name["alias.traffic_roofline"]["workloads"] == [cell["name"]]
    silent = {n for n, x in by_name.items() if cell["name"] not in
              x["workloads"]}
    # no kernel and no pack on the alias path
    assert {"draw.kernel_ms", "draw.kernel_roofline", "setup.pack_s"} \
        <= silent
    assert {"draw.scope_ms", "setup.adjacency_s", "step.mfu_roofline",
            "trainer.step_ms_p50"} & silent == set()


def test_the_cells_program_spans_read_on_a_traced_cpu_line(tmp_path,
                                                          tmp_path_factory):
    """Lesson (5) of ROADMAP "Reach": every per-layer metric that lists
    the cell and reads the program's spans finds something on a traced
    toy line of the same path, and ``setup.pack_s``, which does not list
    it, is silent there (no pack on the alias path). Device-trace
    readers need the chip."""
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = [x for x in real["per_layer"]
              if "reddit_powerlaw_device_train" in x["workloads"]]
    pack = [x for x in real["per_layer"] if x["name"] == "setup.pack_s"]
    m = harness.load_json(MANIFEST)
    m["workloads"] = [w for w in m["workloads"] if w["name"] == CELL]
    m["configs"] = [c for c in m["configs"]
                    if c["name"] == "toy_reddit_powerlaw"]
    cfg = _cfg()
    for key in ("reference", "costs", "graph_function"):
        cfg[key] = os.path.normpath(os.path.join(HERE, cfg[key]))
    m["configs"][0]["file"] = str(tmp_path / "cfg.json")
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    m["per_layer"] = [dict(x, workloads=[CELL]) for x in listed + pack]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    data = tmp_path_factory.getbasetemp() / "benchmark_toy_data"
    r = harness.run_cell(str(tmp_path / "BENCHMARK.json"), CELL, 2147483659,
                         0.3, True, time.time(), require_chip=False,
                         data_root=str(data),
                         keep_trace=str(tmp_path / "trace"))
    read = set(r["withheld_cpu"])
    spans = {x["name"] for x in listed if x["source"] == "program_span"}
    assert len(spans) == 18 and spans <= read, spans - read
    assert "setup.adjacency_s" in read and "setup.pack_s" not in read
