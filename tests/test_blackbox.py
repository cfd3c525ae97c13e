"""eg_blackbox: the always-on flight recorder, fatal-signal postmortem
dumps, and cluster incident collection (OBSERVABILITY.md "Postmortems").

Exact-arithmetic where the machinery allows it: ring eviction order is
pinned slot-by-slot, the seeded `crash` failpoint's ledger is audited
against the dead shard's own postmortem, and the merged incident
timeline must correlate the client journal with the postmortem rings by
the fatal call's wire-v3 trace id. Crash paths run in subprocesses (a
SIGSEGV, even a handled one, must never ride the test process).
"""

import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import euler_tpu
from euler_tpu import blackbox as B
from euler_tpu import telemetry as T
from euler_tpu.graph import native
from euler_tpu.graph.graph import Graph
from euler_tpu.graph.service import GraphService
from tests.fixture_graph import write_fixture

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RING_SLOTS = 256  # eg_blackbox.h kBbRingSlots, pinned by the wrap test


@pytest.fixture(autouse=True)
def _clean_slate():
    native.fault_clear()
    native.reset_counters()
    T.telemetry_reset()
    B.blackbox_reset()
    B.set_blackbox(True)
    yield
    # a test that armed the recorder in this process started its
    # sampler: end it, or it ticks into every later file's stall journal
    B.stop_sampler()
    native.fault_clear()
    native.reset_counters()
    T.telemetry_reset()
    B.blackbox_reset()
    B.set_blackbox(True)


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("blackbox_data"))
    write_fixture(d, num_partitions=2)
    return d


def _subprocess(code: str, timeout=120.0):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


# ---------------------------------------------------------------------------
# flight recorder ring semantics
# ---------------------------------------------------------------------------


def test_ring_eviction_is_oldest_first_under_wraparound():
    """Writing slots+44 events leaves exactly the newest `slots`, read
    back oldest-first — the window is [head - slots, head), no
    reordering, no gaps."""
    total = RING_SLOTS + 44
    for i in range(total):
        B.record("app", value=i)
    d = B.blackbox_json()
    my_rings = [r for r in d["rings"] if r["head"] >= total]
    assert my_rings, d["rings"]
    ring = my_rings[0]
    assert ring["head"] == total
    values = [e["value"] for e in ring["events"]]
    assert values == list(range(total - RING_SLOTS, total))


def test_record_points_roster_matches_native_names():
    for point in B.POINTS:
        B.record(point, value=1)
    d = B.blackbox_json()
    seen = {e["point"] for r in d["rings"] for e in r["events"]}
    assert set(B.POINTS) <= seen, seen


def test_kill_switch_records_nothing():
    B.set_blackbox(False)
    for i in range(10):
        B.record("app", value=i)
    d = B.blackbox_json()
    assert d["enabled"] == 0
    assert all(r["head"] == 0 for r in d["rings"]), d["rings"]


def test_client_and_server_hooks_feed_the_rings(data_dir):
    """Remote traffic against an in-process shard lands client_call,
    server_recv and server_reply events — with the SAME trace id on
    both sides of one exchange (the correlation the postmortem merge
    keys on)."""
    svc = GraphService(data_dir, 0, 1)
    try:
        g = Graph(mode="remote", shards=[svc.address], retries=2)
        try:
            g.node_types(np.array([10, 11], dtype=np.int64))
        finally:
            g.close()
    finally:
        svc.stop()
    d = B.blackbox_json()
    evs = [e for r in d["rings"] for e in r["events"]]
    by_point: dict = {}
    for e in evs:
        by_point.setdefault(e["point"], []).append(e)
    for point in ("client_call", "server_recv", "server_reply",
                  "dispatch"):
        assert by_point.get(point), f"no {point} events: {sorted(by_point)}"
    client_traces = {e["trace"] for e in by_point["client_call"]
                     if int(e["trace"])}
    server_traces = {e["trace"] for e in by_point["server_recv"]
                     if int(e["trace"])}
    assert client_traces & server_traces
    # wire bytes ride the value field on rpc points
    assert any(e["value"] > 0 for e in by_point["client_call"])


# ---------------------------------------------------------------------------
# resource gauges
# ---------------------------------------------------------------------------


def test_resource_gauges_in_metrics_text_with_plausible_bounds():
    text = euler_tpu.metrics_text()

    def value_of(fam):
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith(fam + " ")]
        return float(line.split()[-1])

    assert value_of("eg_rss_bytes") > 0
    assert value_of("eg_open_fds") >= 3  # stdin/stdout/stderr at least
    assert value_of("eg_threads") >= 1
    assert value_of("eg_cache_bytes") >= 0


def test_history_scrape_opcode_against_live_shard(data_dir):
    svc = GraphService(data_dir, 0, 1)
    try:
        g = Graph(mode="remote", shards=[svc.address], retries=2)
        try:
            h = B.history(g, 0)
        finally:
            g.close()
    finally:
        svc.stop()
    assert h["shard"] == 0
    assert h["resource"]["rss_bytes"] > 0
    assert h["resource"]["open_fds"] >= 3
    # in-process shard: no Install ran, so the ring may be empty — the
    # latest live sample above is the contract; a real shard process
    # (service.py --postmortem_dir) fills `history` too
    assert isinstance(h["history"], list)


def test_cache_bytes_gauge_tracks_the_feature_cache(data_dir):
    svc = GraphService(data_dir, 0, 1)
    try:
        g = Graph(mode="remote", shards=[svc.address], retries=2,
                  feature_cache_mb=8)
        try:
            g.get_dense_feature(np.array([10, 11, 12], dtype=np.int64),
                                [0], [2])
            with_rows = B.blackbox_json()["resource"]["cache_bytes"]
            assert with_rows > 0, with_rows
        finally:
            g.close()
        # graph teardown returns its resident bytes to the gauge
        after = B.blackbox_json()["resource"]["cache_bytes"]
        assert after < with_rows
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# manual dumps + postmortem_read
# ---------------------------------------------------------------------------


def test_manual_dump_roundtrip(tmp_path):
    B.install(str(tmp_path), shard=3, sample_ms=100)
    B.record("app", value=42, trace=777)
    path = B.write_postmortem(str(tmp_path / "postmortem.1.json"))
    doc = euler_tpu.postmortem_read(path)
    assert doc["kind"] == "postmortem"
    assert doc["signal"] == 0 and doc["signal_name"] == "exception"
    assert doc["shard"] == 3
    # the counter ledger matches the live roster exactly (same names)
    assert set(doc["counters"]) == set(euler_tpu.counters())
    evs = [e for r in doc["rings"] for e in r["events"]]
    assert any(e["value"] == 42 and e["trace"] == "777" for e in evs)
    assert doc["resource_history"], "sampler never seeded the ring"
    dumps = euler_tpu.postmortem_read(str(tmp_path))
    assert [d["path"] for d in dumps] == [path]


def test_install_rejects_unwritable_dir():
    with pytest.raises(RuntimeError, match="not writable"):
        B.install("/proc/definitely-not-writable")


# ---------------------------------------------------------------------------
# fatal-signal path (subprocesses: the dump must survive what kills it)
# ---------------------------------------------------------------------------


def test_fatal_signal_writes_postmortem_and_reraises(tmp_path):
    pm = str(tmp_path)
    p = _subprocess(f"""
import os, signal
from euler_tpu import blackbox as B
B.install({pm!r}, shard=5, sample_ms=100)
B.record("app", value=9)
os.kill(os.getpid(), signal.SIGSEGV)
""")
    assert p.returncode == -signal.SIGSEGV, (p.returncode, p.stderr)
    (doc,) = euler_tpu.postmortem_read(pm)
    assert doc["signal_name"] == "SIGSEGV"
    assert doc["shard"] == 5
    assert doc["backtrace"], "no backtrace addresses captured"
    assert doc["backtrace_symbols"], "no symbolized frames after the JSON"
    evs = [e for r in doc["rings"] for e in r["events"]]
    assert any(e["value"] == 9 for e in evs)


def test_blackbox_disabled_writes_nothing(tmp_path):
    """blackbox=0 is a real kill-switch: the handler still re-raises
    (same exit status) but writes NO dump."""
    pm = str(tmp_path)
    p = _subprocess(f"""
import os, signal
from euler_tpu import blackbox as B
B.install({pm!r}, shard=5, sample_ms=100)
B.set_blackbox(False)
os.kill(os.getpid(), signal.SIGSEGV)
""")
    assert p.returncode == -signal.SIGSEGV, (p.returncode, p.stderr)
    assert euler_tpu.postmortem_read(pm) == []


def test_crash_failpoint_at_dial_raises_chosen_signal(tmp_path):
    """crash:delay@6 raises SIGABRT at the client's dial point (the
    grammar's signal-selection form), and the dump still lands."""
    pm = str(tmp_path)
    p = _subprocess(f"""
import euler_tpu
from euler_tpu import blackbox as B
B.install({pm!r}, shard=-1, sample_ms=100)
euler_tpu.fault_config("crash:delay@6@1#1", 3)
try:
    euler_tpu.Graph(mode="remote", shards=["127.0.0.1:1"], retries=0,
                    timeout_ms=200)
except Exception:
    pass
""")
    assert p.returncode == -signal.SIGABRT, (p.returncode, p.stderr)
    (doc,) = euler_tpu.postmortem_read(pm)
    assert doc["signal_name"] == "SIGABRT"
    assert doc["counters"]["crashes"] == 1


def test_run_loop_dumps_on_unhandled_exception(tmp_path):
    """The Python twin of the signal path: run_loop with
    --postmortem_dir writes an .exception.json dump when training dies
    on an unhandled exception (here: a nonexistent data_dir)."""
    pm = str(tmp_path / "pm")
    p = _subprocess(f"""
import sys
from euler_tpu import run_loop
sys.argv = ["run_loop", "--mode", "train",
            "--data_dir", {str(tmp_path / 'missing')!r},
            "--postmortem_dir", {pm!r}]
try:
    run_loop.main(sys.argv[1:])
except Exception:
    sys.exit(3)
""")
    assert p.returncode == 3, (p.returncode, p.stderr)
    dumps = euler_tpu.postmortem_read(pm)
    assert len(dumps) == 1 and dumps[0]["signal_name"] == "exception"
    assert dumps[0]["path"].endswith(".exception.json")


# ---------------------------------------------------------------------------
# the incident: seeded crash on a live cluster -> one merged timeline
# ---------------------------------------------------------------------------


def _launch_shard(idx, data, reg, fault=None, pmdir=None):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "euler_tpu.graph.service",
           "--data_dir", data, "--shard_idx", str(idx),
           "--shard_num", "2", "--registry", reg]
    if fault:
        cmd += ["--fault", fault, "--fault_seed", "11"]
    if pmdir:
        cmd += ["--postmortem_dir", pmdir]
    return subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL, env=env)


def _wait_up(idx, reg, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for f in os.listdir(reg):
            if not f.startswith(f"{idx}#"):
                continue
            host, port = f.split("#", 1)[1].rsplit("_", 1)
            try:
                with socket.create_connection((host, int(port)), 1.0):
                    return
            except OSError:
                continue
        time.sleep(0.1)
    raise TimeoutError(f"shard {idx} never registered in {reg}")


def test_crash_incident_merges_into_one_timeline(tmp_path):
    """Acceptance (ISSUE 7): a seeded `crash` failpoint on a live
    2-shard cluster yields a postmortem per dead shard whose counter
    ledger matches the injection count and whose flight-recorder tail
    carries the fatal call's trace id; scripts/postmortem.py merges the
    dumps with the client trace into one timeline keyed by that id."""
    from euler_tpu import trace as trace_mod
    from scripts.postmortem import correlated_fatal_ids, merge_trace

    data = str(tmp_path / "data")
    os.makedirs(data)
    write_fixture(data, num_partitions=2)
    reg = str(tmp_path / "reg")
    os.makedirs(reg)
    pm = str(tmp_path / "pm")
    os.makedirs(pm)

    procs = [_launch_shard(0, data, reg)]
    try:
        procs.append(_launch_shard(1, data, reg))
        _wait_up(0, reg)
        _wait_up(1, reg)
        g = Graph(mode="remote", registry=reg, retries=1,
                  timeout_ms=1500, backoff_ms=10, rediscover_ms=200)
        try:
            ids = np.array(sorted([10, 11, 12, 13, 15, 17]),
                           dtype=np.int64)
            g.node_types(ids)  # cluster warm, both shards answering

            # restart shard 1 armed to die on its next request
            procs[1].terminate()
            procs[1].wait(timeout=30)
            for f in list(os.listdir(reg)):
                if f.startswith("1#"):
                    os.unlink(os.path.join(reg, f))
            procs[1] = _launch_shard(1, data, reg, fault="crash:err@1#1",
                                     pmdir=pm)
            _wait_up(1, reg)
            time.sleep(0.5)  # re-discovery picks up the new port

            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                g.node_types(ids)
                if any(f.startswith("postmortem.")
                       for f in os.listdir(pm)):
                    break
                time.sleep(0.2)

            dumps = euler_tpu.postmortem_read(pm)
            assert len(dumps) == 1, [d["path"] for d in dumps]
            dump = dumps[0]
            # ledger matches the seeded injection count exactly:
            # crash:err@1#1 fires once, counted before the raise
            assert dump["signal_name"] == "SIGSEGV"
            assert dump["counters"]["crashes"] == 1, dump["counters"]
            assert dump["shard"] == 1
            # the client OBSERVED the kill: its call to the dead shard
            # exhausted retries (non-strict mode degrades, but counts)
            client = euler_tpu.counters()
            assert client["calls_failed"] >= 1 or client["rpc_errors"] >= 1

            # the fatal call's trace id sits in the recorder tail AND
            # in this client's journal
            fatal_traces = {
                int(e["trace"])
                for ring in dump["rings"] for e in ring["events"]
                if e["point"] == "server_recv" and int(e["trace"])
            }
            assert fatal_traces, dump["rings"]
            client_traces = {s["trace"] for s in T.slow_spans()
                            if s["side"] == "client"}
            assert fatal_traces & client_traces

            # merge: client trace + postmortems -> one timeline keyed
            # by the fatal trace id
            trace_path = str(tmp_path / "client.trace.json")
            client_trace = trace_mod.write_trace(trace_path, None, g)
            merged = merge_trace(dumps, client_trace)
            trace_mod.validate_chrome_trace(merged)
            linked = correlated_fatal_ids(merged)
            assert linked, "no client<->postmortem correlation"
            assert {int(t, 16) for t in linked} & fatal_traces
        finally:
            g.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)


# ---------------------------------------------------------------------------
# metrics_dump --watch rides out an unreachable shard (satellite)
# ---------------------------------------------------------------------------


def test_watch_skips_unreachable_shard_and_recovers(data_dir):
    import io

    from scripts.metrics_dump import watch_cluster

    svcs = [GraphService(data_dir, s, 2) for s in range(2)]
    g = None
    try:
        g = Graph(mode="remote", shards=[s.address for s in svcs],
                  retries=0, timeout_ms=500, backoff_ms=0)
        g.sample_node(4, -1)
        buf = io.StringIO()
        watch_cluster(g, 0.01, iterations=1, out=buf)
        # shard 1 dies mid-watch: the watch notes and continues
        svcs[1].stop()
        watch_cluster(g, 0.01, iterations=1, out=buf)
        out = buf.getvalue()
        assert "unreachable — skipped" in out, out
        # the surviving shard was still scraped in the same iteration
        lines = [ln for ln in out.splitlines() if "shard 0" in ln]
        assert any("served" in ln for ln in lines), out
    finally:
        if g is not None:
            g.close()
        for s in svcs:
            s.stop()
