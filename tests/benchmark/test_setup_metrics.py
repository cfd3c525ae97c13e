"""The set-up metrics (PR 39: ``setup.*``, ``compile.trace_s``,
``compile.lower_s``, ``device.step_temp_gb``), on the CPU: their entries in
BENCHMARK.json found by name, each reader on a traced toy run of the
harness (a number) and on what the parent hands it (nothing, silently),
and the sums held against the wall time they cover."""

import json
import os
import time
import types

import pytest

from benchmark import harness, manifest, setup_spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
LAYERS = os.path.join(ROOT, "benchmark", "layers")
TOY = os.path.join(HERE, "BENCHMARK_toy.json")
# metric -> (the eg_phase histogram it reads, its layer)
SPAN_METRICS = {
    "setup.graph_load_s": ("setup_graph_load", "host_engine"),
    "setup.table_export_s": ("setup_table_export", "host_engine"),
    "setup.adjacency_s": ("setup_adjacency", "device_sampling"),
    "setup.pack_s": ("setup_pack", "device_sampling"),
    "setup.upload_s": ("setup_upload", "device"),
    "setup.state_place_s": ("setup_state_place", "trainer"),
    "compile.trace_s": ("trace", "compile_cache"),
    "compile.lower_s": ("lower", "compile_cache"),
}
GAUGE = "device.step_temp_gb"
HOST_CELL, WALK_CELL = "reddit_host_train", "node2vec_device_train"


def reader(name):
    return harness.load_module(
        os.path.join(LAYERS, name + ".py"),
        "test_setup_" + name.replace(".", "_"))


def _ctx(phases):
    return types.SimpleNamespace(at_open={"phases": phases}, peaks=None)


# ---------------------------------------------------------------------------
# the entries
# ---------------------------------------------------------------------------


def test_manifest_holds_the_entries_by_name():
    assert manifest.problems(MANIFEST) == []
    m = harness.load_json(MANIFEST)
    cells = [w["name"] for w in m["workloads"]]
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name, (_, layer) in SPAN_METRICS.items():
        x = by_name[name]
        assert (x["unit"], x["better"], x["source"], x["moves"]) == (
            "s", "lower", "program_span", "setup_s"), name
        assert x["layer"] == layer, name
        assert os.path.isfile(os.path.join(LAYERS, name + ".py"))
    g = by_name[GAUGE]
    assert (g["unit"], g["better"], g["source"], g["layer"], g["moves"]) == (
        "GB", "lower", "program_counter", "device", "edges_per_s_chip")
    # as `device.peak_hbm_gb`, whose blind spot it covers
    peak = by_name["device.peak_hbm_gb"]
    assert (g["layer"], g["moves"]) == (peak["layer"], peak["moves"])
    everywhere = ("setup.graph_load_s", "setup.upload_s",
                  "setup.state_place_s", "compile.trace_s",
                  "compile.lower_s", GAUGE)
    for name in everywhere:
        assert by_name[name]["workloads"] == cells, name
    # the walk family keeps no feature or label table; the host cell
    # builds no slab
    assert by_name["setup.table_export_s"]["workloads"] == [
        c for c in cells if c != WALK_CELL]
    for name in ("setup.adjacency_s", "setup.pack_s"):
        assert by_name[name]["workloads"] == [
            c for c in cells if c != HOST_CELL], name
    # the one older metric of set-up stays as it was
    first = by_name["compile.first_step_ms"]
    assert first["moves"] == "setup_s" and first["workloads"] == cells


def test_every_setup_phase_of_the_program_has_a_reader():
    from euler_tpu import devprof
    from euler_tpu import telemetry as T

    read = {phase for phase, _ in SPAN_METRICS.values()}
    assert set(T.SETUP_PHASES) <= read
    # `compile` is read by compile.first_step_ms, through compile_summary
    assert set(devprof.EVENT_PHASE.values()) - {"compile"} <= read
    assert read <= set(T.PHASES)


# ---------------------------------------------------------------------------
# the readers by hand
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_reader_reads_seconds_as_of_the_windows_opening(name):
    phase, _ = SPAN_METRICS[name]
    ctx = _ctx({phase: (3, 2_500_000), "step": (100, 9_000_000)})
    assert reader(name).read(ctx) == 2.5
    # what happened inside the window is not set-up: at_close is not read
    ctx.at_close = {"phases": {phase: (9, 99_000_000)}}
    assert reader(name).read(ctx) == 2.5


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_reader_is_silent_on_a_program_without_the_phase(name):
    # the parent's ledger: fifteen histograms, none of these
    parent = {p: (1, 10) for p in (
        "input_stall", "sample", "h2d", "device", "host", "step", "compile",
        "input_other", "dispatch", "fence", "hook", "log_flush",
        "checkpoint", "host_other", "stall")}
    assert reader(name).read(_ctx(parent)) is None
    # and on a program that has the phase and recorded nothing under it
    phase, _ = SPAN_METRICS[name]
    assert reader(name).read(_ctx({phase: (0, 0)})) is None
    assert setup_spans.seconds(_ctx({}), phase) is None


def test_gauge_reader_follows_the_programs_ledger(monkeypatch):
    from euler_tpu import devprof
    from euler_tpu import telemetry as T

    class Compiled:
        def memory_analysis(self):
            return types.SimpleNamespace(
                temp_size_in_bytes=1_774_210_048,
                argument_size_in_bytes=3, output_size_in_bytes=2,
                alias_size_in_bytes=1)

    sizes = devprof.record_step_memory(Compiled())
    try:
        assert sizes == {"temp": 1_774_210_048, "argument": 3, "output": 2,
                         "alias": 1}
        assert reader(GAUGE).read(_ctx({})) == pytest.approx(1.774210048)
    finally:
        from euler_tpu.graph.native import lib

        lib().eg_devprof_set_step_temp(0)
    assert reader(GAUGE).read(_ctx({})) == 0.0
    # the parent's resource section has no such key
    real = T.telemetry_json

    def parent_json():
        data = real()
        del data["resource"]["step_temp_bytes"]
        return data

    monkeypatch.setattr(T, "telemetry_json", parent_json)
    assert reader(GAUGE).read(_ctx({})) is None

    class NoAnalysis:
        def memory_analysis(self):
            raise NotImplementedError

    assert devprof.record_step_memory(NoAnalysis()) is None


# ---------------------------------------------------------------------------
# a traced toy run of the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    """``toy_device`` of BENCHMARK_toy.json, traced, under a manifest that
    also lists this PR's nine entries for it (the toy manifests are the
    benchmark's and name their own metrics)."""
    d = tmp_path_factory.mktemp("toy_setup")
    m = harness.load_json(TOY)
    for c in m["configs"]:
        cfg = harness.load_json(os.path.join(HERE, c["file"]))
        for key in ("reference", "costs"):
            cfg[key] = os.path.normpath(os.path.join(HERE, cfg[key]))
        c["file"] = str(d / (c["name"] + ".json"))
        with open(c["file"], "w") as f:
            json.dump(cfg, f)
    added = [x for x in harness.load_json(MANIFEST)["per_layer"]
             if x["name"] in SPAN_METRICS or x["name"] == GAUGE]
    assert len(added) == 9
    m["per_layer"] += [dict(x, workloads=["toy_device"]) for x in added]
    path = d / "BENCHMARK.json"
    with open(path, "w") as f:
        json.dump(m, f)
    from euler_tpu import telemetry as T

    T.telemetry_reset()
    t_start = time.time()
    result = harness.run_cell(
        str(path), "toy_device", 23, 0.2, True, t_start, require_chip=False,
        data_root=str(tmp_path_factory.getbasetemp() / "benchmark_toy_data"),
        keep_trace=str(d / "trace"))
    result["wall_s"] = time.time() - t_start
    return result, T.phase_hists(), T.telemetry_json()["resource"]


def test_every_reader_finds_its_phase_in_a_traced_toy_run(toy_run):
    result, hists, resource = toy_run
    assert result["correct"] is True
    # a CPU run is no measurement: the names only
    assert result["metrics"] == {}
    found = set(result["withheld_cpu"])
    # (no chip, no draw kernels: the toy's slab is not packed)
    assert found >= (set(SPAN_METRICS) | {GAUGE}) - {"setup.pack_s"}
    assert "setup.pack_s" not in found
    assert resource["step_temp_bytes"] > 0


def test_inside_and_outside_agree_on_the_toy_run(toy_run):
    """The program's spans against the harness's marks around its calls:
    the graph load inside its interval, the tables' four leaves inside
    theirs, and all set-up leaves with the listener's three phases inside
    the run's wall time (self times: nothing is counted twice)."""
    result, hists, _ = toy_run
    marks = result["setup_marks_s"]

    def secs(*names):
        return sum(hists[n]["sum_us"] for n in names) / 1e6

    assert 0 < secs("setup_graph_load") <= (
        marks["graph_load"] - marks["graph_files"])
    tables = marks["tables"] - marks["graph_load"]
    inside = secs("setup_table_export", "setup_adjacency", "setup_pack")
    assert 0 < inside <= tables
    from euler_tpu import telemetry as T

    # (the ledger was reset as the run began and read as it returned)
    everything = secs(*T.SETUP_PHASES, "trace", "lower", "compile")
    assert everything <= result["wall_s"]
