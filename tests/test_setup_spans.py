"""What the program times of its way to the first step (OBSERVABILITY.md
"Set-up phases"): the set-up leaves on ``now_us``'s clock, self times
where one span holds another, the compile listener's ``trace`` and
``lower`` beside ``compile`` as a union, the kill-switches, the step's
temporaries gauge, and the set-up lane of ``run_loop --trace_file``."""

import json
import re
import time

import jax
import jax.numpy as jnp
import pytest

from euler_tpu import devprof, run_loop
from euler_tpu import telemetry as T
from euler_tpu import trace as TR
from euler_tpu import train as train_lib
from euler_tpu.graph import pallas_sampling
from euler_tpu.parallel import make_mesh

FLAGS = [
    "--max_id", "16", "--feature_idx", "0", "--feature_dim", "2",
    "--label_idx", "2", "--label_dim", "3", "--train_edge_type", "0,1",
    "--all_edge_type", "0,1", "--fanouts", "3,2", "--dim", "8",
    "--batch_size", "8", "--log_steps", "2",
    "--model", "graphsage_supervised", "--device_features", "true",
    "--device_sampling", "true",
]
LISTENER_PHASES = ("trace", "lower", "compile")


@pytest.fixture(autouse=True)
def _clean_slate():
    def reset():
        devprof.uninstall()
        devprof.set_devprof(True)
        devprof.devprof_reset()
        T.telemetry_reset()
        T.set_telemetry(True)
        T.set_trace_sink(None)

    reset()
    yield
    reset()


def _args(data_dir, *extra):
    return run_loop.define_flags().parse_args(
        ["--data_dir", data_dir] + FLAGS + list(extra))


def _train(model, graph, steps=4, **kw):
    return train_lib.train(
        model, graph, lambda s: graph.sample_node(8, -1), num_steps=steps,
        learning_rate=0.01, optimizer="adam", log_every=2,
        mesh=make_mesh(1), **kw)


def _sums(*phases):
    h = T.phase_hists()
    return {p: h[p]["sum_us"] for p in phases}


# ---------------------------------------------------------------------------
# (a) the leaves, through the program's own entry
# ---------------------------------------------------------------------------


def test_every_setup_leaf_is_recorded_once_the_program_is_driven(
        fixture_dir, monkeypatch):
    """build_graph -> build_model -> build_consts -> train(): each leaf at
    least once, on now_us's clock, in the order the work happens, and no
    two of the main thread's spans overlap."""
    rec = TR.TraceRecorder().start()
    t0 = TR.now_us()
    args = _args(fixture_dir)
    graph, services = run_loop.build_graph(args)
    try:
        model = run_loop.build_model(args, graph)
        # the slab's packed copy is numpy on any backend; only the draw
        # kernels that read it need a chip
        monkeypatch.setattr(pallas_sampling, "available", lambda: True)
        consts = model.build_consts(graph)
        monkeypatch.undo()
        assert "packed" in next(iter(consts["adj"].values()))
        _train(model, graph)
    finally:
        rec.stop()
        for s in services:
            s.stop()
        graph.close()
    t1 = TR.now_us()
    spans = [e for e in rec.events()
             if e[0] in T.SETUP_PHASES and e[4] == "MainThread"]
    assert {e[0] for e in spans} == set(T.SETUP_PHASES)
    first = {}
    for name, ts, dur, args_, _thread in spans:
        assert t0 <= ts and ts + dur <= t1, (name, ts, dur)
        assert args_ is None or set(args_) == {"bytes"}
        first.setdefault(name, ts)
    assert sorted(first, key=first.get) == [
        "setup_graph_load", "setup_table_export", "setup_upload",
        "setup_adjacency", "setup_pack", "setup_state_place"]
    by_start = sorted((ts, ts + dur, name) for name, ts, dur, _, _ in spans)
    for (_, end, a), (start, _, b) in zip(by_start, by_start[1:]):
        assert start >= end, (a, b)
    # the spans that moved something say how much
    moved = {e[0] for e in spans if e[3]}
    assert moved == {"setup_graph_load", "setup_table_export",
                     "setup_upload"}
    # and the histograms hold what the sink saw, piece by piece
    h = T.phase_hists()
    for name in T.SETUP_PHASES:
        pieces = sum(e[2] for e in spans if e[0] == name)
        assert h[name]["count"] >= 1
        assert h[name]["sum_us"] == pytest.approx(pieces, abs=50), name
    summary = T.setup_summary()
    assert set(summary) == set(T.SETUP_PHASES)
    assert summary["setup_graph_load"][1] > 0
    assert summary["setup_adjacency"][1] == 0


def test_a_span_that_holds_another_records_its_self_time():
    rec = TR.TraceRecorder().start()
    with T.setup_span("setup_state_place"):
        time.sleep(0.01)
        with T.setup_span("setup_upload", nbytes=7) as inner:
            time.sleep(0.02)
            inner.nbytes += 5
        time.sleep(0.005)
        with T.setup_span("setup_adjacency"):
            time.sleep(0.005)
        time.sleep(0.001)
    rec.stop()
    events = rec.events()
    assert [e[0] for e in events] == [
        "setup_state_place", "setup_upload", "setup_state_place",
        "setup_adjacency", "setup_state_place"]
    assert events[1][3] == {"bytes": 12}
    for (_, ts, dur, _, _), (_, ts2, _, _, _) in zip(events, events[1:]):
        assert ts + dur == ts2  # one reading ends a piece and starts the next
    s = _sums("setup_state_place", "setup_upload", "setup_adjacency")
    assert s["setup_upload"] >= 20_000 and s["setup_adjacency"] >= 5_000
    assert 16_000 <= s["setup_state_place"] < 16_000 + 10_000
    whole = events[-1][1] + events[-1][2] - events[0][1]
    assert sum(s.values()) == pytest.approx(whole, abs=5)
    assert T.setup_summary()["setup_upload"] == (
        s["setup_upload"] / 1e6, 12)


def test_an_exception_leaves_no_span_open():
    with pytest.raises(ValueError):
        with T.setup_span("setup_state_place"):
            with T.setup_span("setup_upload"):
                raise ValueError("upload failed")
    assert T._span_stack() == []
    h = T.phase_hists()
    assert h["setup_upload"]["count"] == h["setup_state_place"]["count"] == 1


# ---------------------------------------------------------------------------
# (b) the kill-switches
# ---------------------------------------------------------------------------


def test_telemetry_off_records_no_setup_span(fixture_dir):
    T.set_telemetry(False)
    seen = []
    T.set_trace_sink(lambda *a: seen.append(a))
    args = _args(fixture_dir)
    graph, services = run_loop.build_graph(args)
    try:
        _train(run_loop.build_model(args, graph), graph)
    finally:
        for s in services:
            s.stop()
        graph.close()
    assert seen == []
    T.set_telemetry(True)
    assert all(h["count"] == 0 for h in T.phase_hists().values())
    assert T.setup_summary() == {}


def test_phase_profile_off_mutes_the_spans_under_train(graph):
    seen = []
    T.set_trace_sink(lambda *a: seen.append(a[0]))
    args = _args("unused")
    model = run_loop.build_model(args, graph)
    _train(model, graph, phase_profile=False)
    assert seen == []
    assert all(T.phase_hists()[n]["count"] == 0 for n in T.SETUP_PHASES)
    # outside train() the same model's tables are timed again
    model.build_consts(graph)
    assert {"setup_table_export", "setup_adjacency"} <= set(seen)


class _CompileCounter:
    """jax's own count of the step function's lowerings and compiles."""

    def __init__(self):
        self.seen = []

    def __call__(self, event, duration, **kw):
        if "train_step" in str(kw.get("fun_name")):
            self.seen.append(event.rsplit("/", 1)[-1])

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def test_an_unprofiled_run_lowers_and_compiles_its_step_once(
        graph, tmp_path, monkeypatch):
    """The temporaries gauge rides the compile a profiled run makes for
    the step's text: without ``profile_dir`` the step is traced, lowered
    and compiled once, telemetry on or off. (A device-sampled step is
    traced as a jit of its own inside the program that runs a chunk of
    them, and jax stamps each call of it there: the step's own function
    runs under a trace once.)"""
    # the gauge outlives a telemetry reset (it is set once, at a compile):
    # a profiled run earlier in this process would have left it set
    devprof.lib().eg_devprof_set_step_temp(0)
    model = run_loop.build_model(_args("unused"), graph)
    written, traced = [], []
    real = train_lib.write_step_hlo
    monkeypatch.setattr(
        train_lib, "write_step_hlo",
        lambda *a, **k: (written.append(1), real(*a, **k)))
    make = model.make_train_step

    def counting(opt):
        step = make(opt)

        def train_step(state, batch):
            traced.append(1)
            return step(state, batch)

        return train_step

    monkeypatch.setattr(model, "make_train_step", counting)
    for on in (True, False):
        T.set_telemetry(on)
        traced.clear()
        with _CompileCounter() as counted:
            _train(model, graph)
        assert traced == [1], on
        assert sorted(set(counted.seen)) == [
            "backend_compile_duration", "jaxpr_to_mlir_module_duration",
            "jaxpr_trace_duration"], (on, counted.seen)
        assert counted.seen.count("jaxpr_to_mlir_module_duration") == 1
        assert counted.seen.count("backend_compile_duration") == 1
    assert written == []
    T.set_telemetry(True)
    assert T.telemetry_json()["resource"]["step_temp_bytes"] == 0
    with _CompileCounter() as counted:
        _train(model, graph, profile_dir=str(tmp_path / "prof"),
               profile_steps=(1, 2))
    assert written == [1]
    # (the text's lower and compile may be answered from jax's own caches)
    assert 1 <= counted.seen.count("backend_compile_duration") <= 2


# ---------------------------------------------------------------------------
# (c) the listener keeps what it is handed
# ---------------------------------------------------------------------------


def test_listener_event_keys_match_the_live_jax():
    from jax._src import dispatch

    assert set(devprof.EVENT_PHASE) == {
        dispatch.JAXPR_TRACE_EVENT, dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
        dispatch.BACKEND_COMPILE_EVENT}
    assert devprof.EVENT_PHASE[devprof.COMPILE_EVENT] == "compile"
    assert set(devprof.EVENT_PHASE.values()) == set(LISTENER_PHASES)


def test_a_jit_traced_inside_a_jit_is_counted_once():
    devprof.install()

    @jax.jit
    def inner(x):
        time.sleep(0.05)  # (runs while inner is traced, inside outer's)
        return jnp.sin(x) * 2

    @jax.jit
    def outer(x):
        time.sleep(0.02)
        return inner(x) + jnp.arange(4.0).sum()

    x = jnp.ones(4)
    jax.block_until_ready(x)
    before = _sums(*LISTENER_PHASES)
    t0 = TR.now_us()
    jax.block_until_ready(outer(x))
    wall = TR.now_us() - t0
    after = _sums(*LISTENER_PHASES)
    spent = {p: after[p] - before[p] for p in LISTENER_PHASES}
    # jax timed inner's 50 ms twice (alone, and inside outer's 70): the
    # phase holds it once
    assert 70_000 <= spent["trace"] <= wall
    assert sum(spent.values()) <= wall
    ms = devprof.function_compile_ms("outer")
    assert set(ms) == set(LISTENER_PHASES)
    assert 20 <= ms["trace"] < 50 <= devprof.function_compile_ms(
        "inner")["trace"]
    cs = devprof.compile_summary()
    assert cs["trace_ms_total"] == pytest.approx(after["trace"] / 1e3, abs=.1)
    assert cs["lower_ms_total"] == pytest.approx(after["lower"] / 1e3, abs=.1)
    assert T._span_stack() == []


def test_listener_spans_reach_the_sink_with_the_function_name():
    devprof.install()
    rec = TR.TraceRecorder().start()

    @jax.jit
    def named_for_the_test(x):
        return x * 3

    jax.block_until_ready(named_for_the_test(jnp.ones(3)))
    rec.stop()
    mine = [e for e in rec.events()
            if (e[3] or {}).get("fn") == "named_for_the_test"]
    # (its trace goes out in pieces, around the trace of the `multiply`)
    assert list(dict.fromkeys(e[0] for e in mine)) == list(LISTENER_PHASES)
    for (_, ts, dur, _, _), (_, ts2, _, _, _) in zip(mine, mine[1:]):
        assert ts + dur <= ts2
    events = TR.validate_chrome_trace(TR.chrome_trace(rec.events()))
    assert {"fn": "named_for_the_test"} in [e.get("args") for e in events]


def test_the_listener_swallows_a_raising_sink():
    devprof.install()

    def sink(*a):
        raise RuntimeError("sink down")

    T.set_trace_sink(sink)

    @jax.jit
    def survives(x):
        return x + 1

    assert float(survives(jnp.zeros(()))) == 1.0
    T.set_trace_sink(None)
    h = T.phase_hists()
    assert all(h[p]["count"] >= 1 for p in LISTENER_PHASES)
    assert T._span_stack() == []


def test_devprof_off_records_none_of_the_three():
    devprof.install()
    devprof.set_devprof(False)

    @jax.jit
    def unseen(x):
        return x - 1

    jax.block_until_ready(unseen(jnp.ones(2)))
    assert _sums(*LISTENER_PHASES) == dict.fromkeys(LISTENER_PHASES, 0)
    assert T._span_stack() == []


# ---------------------------------------------------------------------------
# (d) the summary line, the gauge, the export
# ---------------------------------------------------------------------------


def test_one_line_at_the_first_step_names_the_leaves_and_the_step(graph):
    devprof.install()
    lines = []
    model = run_loop.build_model(_args("unused"), graph)
    _train(model, graph, log_fn=lines.append)
    first = [ln for ln in lines if ln.startswith("first step dispatched")]
    assert len(first) == 1
    (line,) = first
    # (chip_smoke.py reads the count and the milliseconds off its head)
    assert re.search(r"(\d+) XLA compile\(s\), (\d+) ms compile time", line)
    assert re.search(
        r"train_step: trace \d+\.\d / lower \d+\.\d / compile \d+\.\d s", line)
    for leaf in ("table_export", "adjacency", "upload", "state_place"):
        assert re.search(leaf + r" \d+\.\d s", line), (leaf, line)
    assert "GB)" in line
    assert set(devprof.function_compile_ms("train_step")) == set(
        LISTENER_PHASES)


def test_a_profiled_run_sets_the_step_temporaries_gauge(
        graph, tmp_path, caplog):
    model = run_loop.build_model(_args("unused"), graph)
    with caplog.at_level("INFO", logger="euler_tpu"):
        _train(model, graph, profile_dir=str(tmp_path / "prof"),
               profile_steps=(1, 2))
    temp = T.telemetry_json()["resource"]["step_temp_bytes"]
    assert temp > 0
    assert devprof.compile_summary()["step_temp_bytes"] == temp
    assert f"eg_step_temp_bytes {temp}" in T.metrics_text()
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("train step memory:")]
    assert "temporaries" in line and "aliased" in line
    # a reset of the measurements keeps it, as it keeps the tables' widths
    T.telemetry_reset()
    assert T.telemetry_json()["resource"]["step_temp_bytes"] == temp


def test_trace_file_carries_the_setup_lane(fixture_dir, tmp_path):
    tf = str(tmp_path / "run_trace.json")
    assert run_loop.main(
        ["--data_dir", fixture_dir, "--model_dir", str(tmp_path / "ck"),
         "--num_epochs", "2", "--mode", "train", "--trace_file", tf]
        + FLAGS) == 0
    with open(tf) as f:
        events = TR.validate_chrome_trace(json.load(f))
    phases = [e for e in events if e.get("cat") == "phase"]
    names = {e["name"] for e in phases}
    assert {"setup_graph_load", "setup_table_export", "setup_adjacency",
            "setup_upload", "setup_state_place"} <= names
    assert set(LISTENER_PHASES) <= names
    load = min(e["ts"] for e in phases if e["name"] == "setup_graph_load")
    step0 = min(e["ts"] for e in phases if e["name"] == "step")
    assert load < step0
    assert any("bytes" in (e.get("args") or {}) for e in phases)
    assert any((e.get("args") or {}).get("fn") == "train_step"
               for e in phases)
    # the recorder went out with the run
    assert T._trace_sink is None
