"""Step-phase profiler: input-stall attribution, prefetch gauges, and
the merged Perfetto trace export (OBSERVABILITY.md "Step phases").

The determinism spine is the same as test_telemetry's: PR-2's seeded
`handler_stall:delay@25` failpoint pins log2 bucket placement — a 25 ms
stall in the sampler must land in `sample` and `input_stall` at bucket
15 ([16384, 32768) µs) or above, never below, and NEVER in `device`.
"""

import io
import json
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

from euler_tpu import telemetry as T
from euler_tpu import trace as TR
from euler_tpu.graph import native
from euler_tpu.graph.graph import Graph
from euler_tpu.graph.service import GraphService
from euler_tpu.parallel import prefetch
from tests.fixture_graph import write_fixture

IDS = np.array([10, 11, 12, 13], dtype=np.int64)
STALL_BUCKET = 15  # 25 ms -> [16384, 32768) µs


@pytest.fixture(autouse=True)
def _clean_slate():
    native.fault_clear()
    native.reset_counters()
    T.telemetry_reset()
    T.set_telemetry(True)
    T.set_trace_sink(None)
    yield
    native.fault_clear()
    native.reset_counters()
    T.telemetry_reset()
    T.set_telemetry(True)
    T.set_trace_sink(None)


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("phase_data"))
    write_fixture(d, num_partitions=2)
    return d


def _graph(svcs, **kw):
    kw.setdefault("retries", 2)
    kw.setdefault("timeout_ms", 5000)
    return Graph(mode="remote", shards=[s.address for s in svcs], **kw)


# ---------------------------------------------------------------------------
# the phase enum + bucket arithmetic pins
# ---------------------------------------------------------------------------


def test_phase_names_pin_the_native_enum_order():
    """record_phase() indexes the native enum by PHASES order — each
    name must land in its own histogram cell."""
    for i, name in enumerate(T.PHASES):
        T.record_phase(name, 10 * (i + 1))
    hists = T.phase_hists()
    assert set(hists) == set(T.PHASES)
    for i, name in enumerate(T.PHASES):
        assert hists[name]["count"] == 1, name
        assert hists[name]["sum_us"] == 10 * (i + 1), name


def test_phase_names_are_the_native_table_letter_for_letter():
    """PHASES against eg_phase.h itself: the names of ``kPhaseNames`` in
    order, one per enumerator up to ``kPhaseCount``; the set-up leaves are
    the ``setup_*`` of them, all under the parent ``setup``."""
    import os
    import re

    header = os.path.join(
        os.path.dirname(os.path.abspath(native.__file__)), "_native",
        "eg_phase.h")
    with open(header) as f:
        text = f.read()
    table = re.search(
        r"kPhaseNames\[kPhaseCount\] = \{(.*?)\};", text, re.DOTALL)
    assert tuple(re.findall(r'"([a-z0-9_]+)"', table.group(1))) == T.PHASES
    enum = re.search(r"enum StepPhase : int \{(.*?)\};", text, re.DOTALL)
    members = re.findall(r"^\s*(kPhase[A-Za-z0-9]+)", enum.group(1),
                         re.MULTILINE)
    assert members[-1] == "kPhaseCount"
    assert len(members) - 1 == len(T.PHASES)
    assert T.SETUP_PHASES == T.PHASES[-len(T.SETUP_PHASES):]
    assert all(T.PHASE_PARENT[leaf] == "setup" for leaf in T.SETUP_PHASES)
    assert {"trace", "lower", "compile"} <= set(T.PHASES)


def test_record_phase_exact_bucket_and_reset():
    T.record_phase("input_stall", 25_000)
    h = T.phase_hists()["input_stall"]
    assert h["b"][STALL_BUCKET] == 1 and h["count"] == 1
    T.telemetry_reset()  # must clear phase cells too
    assert T.phase_hists()["input_stall"]["count"] == 0


def test_prefetch_gauge_value_histograms():
    T.record_prefetch_gauges(3, 2)
    T.record_prefetch_gauges(0, 1)
    data = T.telemetry_json()
    depth, busy = data["hist"]["prefetch_depth"], data["hist"]["prefetch_busy"]
    assert depth["count"] == 2 and depth["sum_us"] == 3
    assert busy["count"] == 2 and busy["sum_us"] == 3
    assert depth["b"][0] == 1  # the zero-depth dequeue
    assert depth["b"][T.bucket_of(3)] == 1


# ---------------------------------------------------------------------------
# stall attribution under a seeded failpoint (the ISSUE's acceptance
# drill): delay lands in sample/input_stall, NEVER in device
# ---------------------------------------------------------------------------


def test_seeded_stall_lands_in_sample_and_input_stall_never_device(data_dir):
    svc = GraphService(data_dir, 0, 1)
    try:
        g = _graph([svc])
        try:
            g.node_types(IDS)  # dial/warm outside the pinned window
            native.fault_config("handler_stall:delay@25", 7)
            T.telemetry_reset()
            # synchronous prefetch path: the consumer IS the producer,
            # so each of the 3 steps is one full 25 ms stall on BOTH
            # phase histograms — bucket 15 or above (a loaded host adds
            # to the stall, never takes from it), none below
            steps = 3
            for _ in prefetch(
                lambda s: g.node_types(IDS), steps, depth=0, num_threads=1
            ):
                pass
            native.fault_clear()
            hists = T.phase_hists()
            for phase in ("sample", "input_stall"):
                h = hists[phase]
                assert h["count"] == steps, (phase, h)
                assert sum(h["b"][:STALL_BUCKET]) == 0, (phase, h["b"])
                assert sum(h["b"][STALL_BUCKET:]) == steps, (phase, h["b"])
            assert hists["device"]["count"] == 0, hists["device"]
            # mean stall (the ROADMAP input_stall_ms metric) moved by
            # at least the injected 25 ms
            snap = T.snapshot()
            assert snap["input_stall_ms"] >= 25.0
            assert snap["phases"]["sample"]["p50_us"] >= 16384
        finally:
            g.close()
    finally:
        svc.stop()


def test_threaded_prefetch_attributes_stall_and_leaves_device_alone(
    data_dir,
):
    svc = GraphService(data_dir, 0, 1)
    try:
        g = _graph([svc])
        try:
            g.node_types(IDS)
            native.fault_config("handler_stall:delay@25", 7)
            T.telemetry_reset()
            native.reset_counters()
            steps = 4
            got = list(prefetch(
                lambda s: (s, g.node_types(IDS))[0], steps,
                depth=1, num_threads=2,
            ))
            native.fault_clear()
            assert got == list(range(steps))
            hists = T.phase_hists()
            sample = hists["sample"]
            assert sample["count"] == steps
            # every produce stalled >= 25 ms: nothing below bucket 15
            assert sum(sample["b"][:STALL_BUCKET]) == 0, sample["b"]
            # the consumer stalled on at least the first batch; the
            # delay shows up in input_stall, not device
            stall = hists["input_stall"]
            assert stall["count"] == steps
            assert sum(stall["b"][STALL_BUCKET:]) >= 1, stall["b"]
            assert hists["device"]["count"] == 0
            # pipeline gauges: one dequeue sample per consumed step
            data = T.telemetry_json()
            assert data["hist"]["prefetch_depth"]["count"] == steps
            assert data["hist"]["prefetch_busy"]["count"] == steps
            assert native.counters()["prefetch_produced"] == steps
        finally:
            g.close()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# prefetch pipeline ledger: dropped batches + the kill-switch
# ---------------------------------------------------------------------------


def test_abandoned_iterator_counts_dropped_batches():
    native.reset_counters()
    it = prefetch(lambda s: s, 10, depth=3, num_threads=2)
    assert next(it) == 0
    time.sleep(0.05)  # let workers fill the depth window
    it.close()
    ctr = native.counters()
    assert ctr["prefetch_dropped"] >= 1, ctr
    assert ctr["prefetch_produced"] >= ctr["prefetch_dropped"]


def test_kill_switch_disables_phase_recording_and_ledger():
    T.set_telemetry(False)
    try:
        native.reset_counters()
        got = list(prefetch(lambda s: s, 4, depth=2, num_threads=2))
        assert got == [0, 1, 2, 3]
        T.record_phase("device", 1000)  # native gate drops it too
        data = T.telemetry_json()
        assert all(h["count"] == 0 for h in data["hist"].values())
        assert native.counters()["prefetch_produced"] == 0
    finally:
        T.set_telemetry(True)


# ---------------------------------------------------------------------------
# exposition surfaces: Prometheus families, JSONL snapshot, console
# ---------------------------------------------------------------------------


def test_metrics_text_renders_phase_and_prefetch_families():
    T.record_phase("input_stall", 25_000)
    T.record_prefetch_gauges(2, 1)
    text = T.metrics_text()
    assert ('eg_step_phase_us_bucket{phase="input_stall",le="32768"} 1'
            in text)
    assert 'eg_step_phase_us_count{phase="device"} 0' in text
    assert "eg_prefetch_queue_depth_sum 2" in text
    assert "eg_prefetch_workers_busy_count 1" in text
    assert 'eg_counter_total{name="prefetch_worker_errors"} 0' in text


def test_snapshot_carries_phases_and_prefetch_means():
    T.record_phase("input_stall", 2_000)
    T.record_phase("input_stall", 4_000)
    T.record_phase("device", 500)
    T.record_prefetch_gauges(4, 2)
    snap = T.snapshot(step=3)
    assert snap["input_stall_ms"] == 3.0  # mean of 2 ms + 4 ms
    assert snap["phases"]["input_stall"]["count"] == 2
    assert snap["phases"]["device"]["count"] == 1
    assert snap["prefetch"] == {
        "mean_queue_depth": 4.0, "mean_workers_busy": 2.0,
    }


def test_console_stats_phases():
    from euler_tpu.console import Console

    T.record_phase("input_stall", 25_000)
    T.record_prefetch_gauges(1, 1)
    native.counter_add("prefetch_worker_errors", 2)
    buf = io.StringIO()
    with redirect_stdout(buf):
        Console().do_stats(["phases"])
    out = buf.getvalue()
    assert "input_stall" in out
    assert "queue depth" in out
    assert "'prefetch_worker_errors': 2" in out


# ---------------------------------------------------------------------------
# trace recorder + merged Perfetto export
# ---------------------------------------------------------------------------


def test_trace_recorder_captures_phase_events_with_thread_lanes():
    rec = TR.TraceRecorder(capacity=8).start()
    try:
        T.record_phase("sample", 100, step=1)
        T.record_phase("device", 50, step=1)
        for i in range(10):
            T.record_phase("host", 10, step=i)
    finally:
        rec.stop()
    events = rec.events()
    assert len(events) == 8  # ring capacity
    assert rec.dropped == 4
    trace = TR.chrome_trace(events, [])
    evs = TR.validate_chrome_trace(trace)
    slices = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["pid"] == TR.PID_TRAIN for e in slices)
    # stopped: further records don't land
    T.record_phase("sample", 100)
    assert len(rec.events()) == 8


def test_span_end_us_is_stamped_on_the_monotonic_clock():
    before = TR.now_us()
    T.record_span(1234, op=5)
    span = T.slow_spans()[0]
    assert before <= span["end_us"] <= TR.now_us()
    assert span["total_us"] == 1234


def test_merged_trace_correlates_client_and_server_by_trace_id(data_dir):
    svc = GraphService(data_dir, 0, 1)
    try:
        g = _graph([svc])
        try:
            T.telemetry_reset()
            rec = TR.TraceRecorder().start()
            # a seeded 5 ms stall beats the journal floor on both sides
            native.fault_config("handler_stall:delay@5", 3)
            for _ in prefetch(
                lambda s: g.node_types(IDS), 3, depth=1, num_threads=2
            ):
                pass
            native.fault_clear()
            rec.stop()
            # the server journals its span right after replying — give
            # the racing worker a moment, like test_telemetry does
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if any(s["side"] == "server" for s in T.slow_spans()):
                    break
                time.sleep(0.01)
            trace = TR.chrome_trace(
                rec.events(), TR.gather_span_sources(g)
            )
            events = TR.validate_chrome_trace(trace)
            # phase slices and rpc slices share the timeline
            assert any(e.get("cat") == "phase" for e in events)
            correlated = TR.correlated_trace_ids(trace)
            assert correlated, [
                e for e in events if e.get("cat") == "rpc"
            ]
            # flow arrows exist for the correlated ids
            flows = {e["id"] for e in events if e["ph"] in ("s", "f")}
            assert correlated <= flows
            # round-trips through JSON untouched
            reread = json.loads(json.dumps(trace))
            assert TR.correlated_trace_ids(reread) == correlated
        finally:
            g.close()
    finally:
        svc.stop()


def test_trace_dump_smoke_end_to_end():
    """The scripts/trace_dump.py --smoke gate as a tier-1 member: a
    live 2-shard cluster's merged export is valid Chrome-trace JSON
    whose slow-span slices carry matching wire-v3 trace ids on both
    sides (the ISSUE acceptance line)."""
    from scripts.trace_dump import run_smoke

    assert run_smoke() == 0
