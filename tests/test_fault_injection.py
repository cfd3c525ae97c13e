"""Deterministic failpoints (_native/eg_fault) + failure counters.

Every failure path in the remote stack used to be reachable only by real
process kills; these tests drive each one through the seeded failpoint
layer and pin the exact counter arithmetic: each counter increments
precisely when its failpoint fires, the injected-fault ledger matches,
and a fault seed replays the identical failure sequence (the property
the chaos soak in test_chaos_soak.py builds on).

The injector is process-global (like the stats it feeds), so every test
clears it on the way out — a leaked failpoint would chaos-test the rest
of the suite.
"""

import time

import numpy as np
import pytest

from euler_tpu.graph import native
from euler_tpu.graph.graph import Graph
from euler_tpu.graph.service import GraphService

COUNTER_NAMES = {
    "dials_failed", "retries", "quarantines", "failovers", "calls_failed",
    "deadlines_exceeded", "frames_rejected", "rediscoveries",
    "heartbeat_misses",
    # remote hot-path efficiency ledger (PR 3): dedup/cache/chunking
    # wins plus op-level shard failures
    "ids_deduped", "cache_hits", "cache_misses", "rpc_chunks", "rpc_errors",
    # server-side survivability ledger (PR 4): bounded admission, wedge
    # timeouts, deadline refusals, drains, wire downgrades
    "busy_rejects", "busy_failovers", "handler_timeouts",
    "deadline_rejects", "draining", "wire_downgrades",
    # training input pipeline ledger (PR 6): prefetch production/drop
    # accounting and dead-worker visibility
    "prefetch_produced", "prefetch_dropped", "prefetch_worker_errors",
    # postmortem ledger (PR 7): fires of the seeded crash failpoint,
    # counted before the raise so the dump's snapshot includes them
    "crashes",
    # locality ledger (PR 9): neighbor-list cache hits/misses, TinyLFU
    # admission rejections, and placement-map fallbacks to hash routing
    "nbr_cache_hits", "nbr_cache_misses", "cache_admit_rejects",
    "placement_fallbacks",
    # serving ledger (PR 11): admitted embed requests, admission sheds
    # (batcher queue cap + frontend connection cap), deadline expiries
    # caught before dispatch, and coalesced device dispatches
    "serve_requests", "serve_busy_rejects", "serve_deadline_rejects",
    "serve_batches",
    # device-plane ledger (PR 15): XLA compiles/recompiles, the serve
    # compile-storm guard, and host<->device transfer bytes
    "device_compiles", "device_recompiles", "serve_recompiles",
    "h2d_bytes", "d2h_bytes",
    # async-sampler ledger (PR 18): completion-queue submissions, the
    # high-water mark of concurrently running ops, and hop/slice
    # continuations re-enqueued by job completions
    "async_submits", "async_inflight_peak", "async_continuations",
    # snapshot-epoch ledger (PR 19): delta flips, retired-epoch drains,
    # stale cache generations evicted on touch, and refused delta loads
    "epoch_flips", "epoch_drains", "epoch_stale_hits_evicted",
    "delta_loads_failed",
    # full-neighbourhood expansion ledger (PR 37): padded slots, true
    # edges and unique neighbours past a hop's cap, counted in the step
    "expand_slots", "expand_edges", "expand_overflow_nodes",
    # the slots of those whose rows layer 0's messages read
    "expand_gathered_slots",
}
FAULT_NAMES = {
    "dial", "send_frame", "recv_frame", "service_reply", "registry_reply",
    "heartbeat", "accept", "handler_stall", "busy_force", "crash",
    "delta_load", "epoch_flip",
}


@pytest.fixture(autouse=True)
def _clean_faults():
    """No failpoint may outlive its test (process-global injector)."""
    native.fault_clear()
    native.reset_counters()
    yield
    native.fault_clear()
    native.reset_counters()


@pytest.fixture(scope="module")
def shard(tmp_path_factory):
    """One live shard on an ephemeral port + its flat-file registry."""
    from tests.fixture_graph import write_fixture

    data = str(tmp_path_factory.mktemp("fault_data"))
    write_fixture(data, num_partitions=2)
    reg = str(tmp_path_factory.mktemp("fault_reg"))
    svc = GraphService(data, 0, 1, registry=reg)
    yield svc, reg
    svc.stop()


def nonzero(d):
    return {k: v for k, v in d.items() if v}


# ---------------------------------------------------------------------------
# surface: spec grammar, names, Python round-trip
# ---------------------------------------------------------------------------


def test_counters_round_trip_to_python():
    import euler_tpu

    snap = euler_tpu.counters()
    assert set(snap) == COUNTER_NAMES
    assert all(isinstance(v, int) for v in snap.values())
    euler_tpu.counters_reset()
    assert nonzero(euler_tpu.counters()) == {}


def test_fault_ledger_names():
    assert set(native.fault_injected()) == FAULT_NAMES


@pytest.mark.parametrize(
    "bad",
    [
        "bogus:err@0.5",          # unknown point
        "dial",                   # no action
        "dial:explode@1",         # unknown action
        "dial:err@0.0",           # probability out of (0,1]
        "dial:err@2.0",
        "dial:err@x",
        "dial:delay@-5",
        "dial:err@0.5#x",         # bad limit
        "dial:err@0.5,dial:err@0.5",  # duplicate point
    ],
)
def test_malformed_specs_raise_and_install_nothing(bad):
    with pytest.raises(ValueError):
        native.fault_config(bad, 1)
    assert nonzero(native.fault_injected()) == {}


def test_valid_spec_forms_accepted():
    native.fault_config(
        "dial:err@1.0#2,send_frame:delay@10,recv_frame:delay@5@0.5#3", 9
    )
    native.fault_config("", 0)  # empty spec clears


def test_graph_rejects_fault_on_local_mode(shard, tmp_path):
    svc, reg = shard
    with pytest.raises(ValueError, match="remote"):
        Graph(directory=str(tmp_path), fault="dial:err@0.5")


# ---------------------------------------------------------------------------
# each counter increments exactly when its failpoint fires
# ---------------------------------------------------------------------------


def test_dial_fault_counts_exactly(shard):
    svc, reg = shard
    # Init performs exactly one kInfo Call; dial:err@1.0#2 fails the
    # first two attempts, the third dials clean — each number is forced.
    g = Graph(mode="remote", registry=reg, retries=3, timeout_ms=2000,
              backoff_ms=1, fault="dial:err@1.0#2", fault_seed=1)
    try:
        assert native.fault_injected()["dial"] == 2
        ctr = native.counters()
        assert ctr["dials_failed"] == 2
        assert ctr["retries"] == 2
        assert ctr["quarantines"] == 2
        assert ctr["failovers"] == 1
        assert ctr["calls_failed"] == 0
    finally:
        g.close()


def test_send_frame_fault_counts_exactly(shard):
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=3, timeout_ms=2000,
              backoff_ms=1)
    try:
        ids = np.array([10, 11, 12, 13], dtype=np.int64)
        g.node_types(ids)  # warm the pooled connection
        native.fault_config("send_frame:err@1.0#1", 5)
        native.counters_reset()
        t = g.node_types(ids)
        np.testing.assert_array_equal(t, [0, 1, 0, 1])  # retried through
        assert native.fault_injected()["send_frame"] == 1
        ctr = native.counters()
        assert ctr["retries"] == 1
        assert ctr["quarantines"] == 1
        assert ctr["failovers"] == 1
        assert ctr["dials_failed"] == 0  # the redial succeeded
    finally:
        g.close()


def test_recv_frame_fault_counts_exactly(shard):
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=3, timeout_ms=2000,
              backoff_ms=1)
    try:
        ids = np.array([10, 11], dtype=np.int64)
        g.node_types(ids)
        # the in-process shard shares the injector, and recv_frame fires
        # only once a frame has begun arriving — so the one fire lands
        # deterministically on the shard reading the request (the request
        # header always precedes the reply header); the client sees its
        # connection die mid-exchange and must fail over
        native.fault_config("recv_frame:err@1.0#1", 5)
        native.counters_reset()
        t = g.node_types(ids)
        np.testing.assert_array_equal(t, [0, 1])
        assert native.fault_injected()["recv_frame"] == 1
        ctr = native.counters()
        assert ctr["retries"] == 1, ctr
        assert ctr["quarantines"] == 1, ctr
        assert ctr["failovers"] == 1, ctr
    finally:
        g.close()


def test_deadline_spans_all_retries(shard):
    svc, reg = shard
    # recv always fails; generous retries but a 150 ms overall budget.
    # Without the per-call deadline this would grind through 10 backoff
    # sleeps; with it the call must abort quickly and say so.
    g = Graph(mode="remote", registry=reg, retries=10, timeout_ms=2000,
              backoff_ms=400, deadline_ms=150)
    try:
        g.node_types(np.array([10], dtype=np.int64))  # warm up, no faults
        native.fault_config("recv_frame:err@1.0", 3)
        native.counters_reset()
        t0 = time.monotonic()
        t = g.node_types(np.array([10], dtype=np.int64))
        elapsed = time.monotonic() - t0
        assert elapsed < 1.5, "deadline did not bound the retry loop"
        assert t[0] == -1  # degraded to default, not wedged
        ctr = native.counters()
        assert ctr["deadlines_exceeded"] == 1
        assert ctr["calls_failed"] == 1
    finally:
        native.fault_clear()
        g.close()


def test_frames_rejected_on_error_status_reply(shard):
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=1, timeout_ms=2000)
    try:
        native.counters_reset()
        # a request whose result cannot fit a reply frame gets an error
        # status from the shard (OversizedResult) — the client must count
        # the refusal, not silently zero-fill
        out = g.get_dense_feature(
            np.array([10], dtype=np.int64), [0], [2 ** 29]
        )
        assert float(np.abs(out).sum()) == 0.0
        assert native.counters()["frames_rejected"] >= 1
    finally:
        g.close()


def test_delay_fault_injects_latency_without_failing(shard):
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=1, timeout_ms=2000)
    try:
        ids = np.array([10, 11], dtype=np.int64)
        g.node_types(ids)
        native.fault_config("send_frame:delay@80", 11)
        native.counters_reset()
        t0 = time.monotonic()
        t = g.node_types(ids)
        elapsed = time.monotonic() - t0
        np.testing.assert_array_equal(t, [0, 1])  # slow, not wrong
        assert elapsed >= 0.08
        assert native.fault_injected()["send_frame"] >= 1
        assert native.counters()["retries"] == 0  # delay is not a failure
    finally:
        g.close()


def test_service_reply_fault_forces_client_retry(shard):
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=3, timeout_ms=2000,
              backoff_ms=1)
    try:
        ids = np.array([10, 11, 12, 13], dtype=np.int64)
        g.node_types(ids)
        # the shard runs in-process here, so its failpoints and the
        # client's share one injector — exactly one computed reply is
        # dropped on the floor before send
        native.fault_config("service_reply:err@1.0#1", 5)
        native.counters_reset()
        t = g.node_types(ids)
        np.testing.assert_array_equal(t, [0, 1, 0, 1])
        assert native.fault_injected()["service_reply"] == 1
        assert native.counters()["retries"] >= 1
    finally:
        g.close()


def test_heartbeat_fault_counts_misses_and_survives(tmp_path):
    from euler_tpu.graph import registry as registry_mod
    from tests.fixture_graph import write_fixture

    import os

    data = str(tmp_path / "data")
    os.makedirs(data)
    write_fixture(data, num_partitions=2)
    reg = registry_mod.RegistryServer(host="127.0.0.1", ttl_ms=600)
    svc = None
    try:
        svc = GraphService(data, 0, 1, registry=reg.address)
        # beats run every max(ttl/3, 150) = 200 ms; force the next two to
        # miss — each miss must redial and re-REG so the entry stays live
        native.fault_config("heartbeat:err@1.0#2", 21)
        native.counters_reset()
        deadline = time.monotonic() + 5.0
        while (native.fault_injected()["heartbeat"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert native.fault_injected()["heartbeat"] == 2
        assert native.counters()["heartbeat_misses"] == 2
        # despite two missed beats the shard never expired from LIST
        time.sleep(0.7)  # > ttl: only the redial re-REGs keep it alive
        assert 0 in registry_mod.query(reg.address)
    finally:
        native.fault_clear()
        if svc is not None:
            svc.stop()
        reg.stop()


def test_registry_reply_fault_fails_one_list(tmp_path):
    from euler_tpu.graph import registry as registry_mod

    reg = registry_mod.RegistryServer(host="127.0.0.1", ttl_ms=5000)
    try:
        registry_mod.query(reg.address)  # clean LIST works
        native.fault_config("registry_reply:err@1.0#1", 3)
        with pytest.raises(ConnectionError):
            registry_mod.query(reg.address)
        assert native.fault_injected()["registry_reply"] == 1
        registry_mod.query(reg.address)  # next LIST answers again
    finally:
        native.fault_clear()
        reg.stop()


def test_dispatcher_chunked_call_retries_through_faults(shard):
    """The persistent-dispatcher + chunked path must keep every transport
    guarantee of the old per-call-thread path: a chunk whose send fails
    retries through a redial, the counters account for it exactly, and
    the merged result is still correct."""
    svc, reg = shard
    # chunk_ids=2 forces the 6-unique-id request below into 3 chunks on
    # the single shard; cache off so the second call re-issues them
    g = Graph(mode="remote", registry=reg, retries=3, timeout_ms=2000,
              backoff_ms=1, chunk_ids=2, feature_cache_mb=0)
    try:
        ids = np.array([10, 11, 12, 13, 14, 15], dtype=np.int64)
        g.node_types(ids)  # warm pooled connections, pre-fault
        native.fault_config("send_frame:err@1.0#1", 7)
        native.counters_reset()
        t = g.node_types(ids)
        np.testing.assert_array_equal(t, [0, 1, 0, 1, 0, 1])
        assert native.fault_injected()["send_frame"] == 1
        ctr = native.counters()
        assert ctr["rpc_chunks"] == 3, ctr      # ceil(6 / 2) chunks issued
        assert ctr["retries"] == 1, ctr         # exactly the faulted chunk
        assert ctr["failovers"] == 1, ctr
        assert ctr["rpc_errors"] == 0, ctr      # the retry succeeded
    finally:
        g.close()


def test_rpc_errors_counts_exhausted_shard_call(shard):
    """When every retry of a chunk fails, the op-level failure (rows
    degraded to defaults) must be visible in rpc_errors — the counter
    the old ForShards bool-discard made impossible to observe."""
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=1, timeout_ms=2000,
              backoff_ms=1, deadline_ms=300)
    try:
        one = np.array([10], dtype=np.int64)
        g.node_types(one)  # warm up pre-fault
        native.fault_config("send_frame:err@1.0", 13)  # every send fails
        native.counters_reset()
        t = g.node_types(one)
        assert t[0] == -1  # degraded to default, not wedged
        ctr = native.counters()
        assert ctr["rpc_errors"] == 1, ctr
        assert ctr["calls_failed"] == 1, ctr
    finally:
        native.fault_clear()
        g.close()


# ---------------------------------------------------------------------------
# server-side survivability failpoints (eg_admission.cc): BUSY shedding,
# handler stalls -> deadline replies, accept-path drops — each counted
# exactly
# ---------------------------------------------------------------------------


def test_busy_force_fail_fast_failover(shard):
    """A forced-BUSY admission answer must trigger the client's
    fail-fast path: immediate redial, no retry burned, no backoff
    slept, no quarantine of the (alive, just shedding) server."""
    svc, reg = shard
    # armed BEFORE the client exists: Init's kInfo call dials fresh, so
    # each of the three forced BUSYs lands on a new connection
    native.fault_config("busy_force:err@1.0#3", 7)
    native.reset_counters()
    g = Graph(mode="remote", registry=reg, retries=2, timeout_ms=2000)
    try:
        t = g.node_types(np.array([10, 11], dtype=np.int64))
        np.testing.assert_array_equal(t, [0, 1])
        assert native.fault_injected()["busy_force"] == 3
        ctr = native.counters()
        assert ctr["busy_rejects"] == 3, ctr
        assert ctr["busy_failovers"] == 3, ctr
        assert ctr["retries"] == 0, ctr       # BUSY burns no attempt
        assert ctr["quarantines"] == 0, ctr   # and no quarantine
        assert ctr["calls_failed"] == 0, ctr
    finally:
        g.close()


def test_handler_stall_delay_forces_deadline_reply(shard):
    """A stalled handler must answer DEADLINE instead of computing a
    dead answer: the stall outlives the client's stamped budget, the
    server refuses pre-dispatch (deadline_rejects), and the client ends
    the call at once (deadlines_exceeded) instead of re-queueing work
    nobody will read."""
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=5, timeout_ms=2000,
              backoff_ms=1, deadline_ms=150)
    try:
        one = np.array([10], dtype=np.int64)
        g.node_types(one)  # warm up: pooled conn, negotiated v2
        native.fault_config("handler_stall:delay@400#1", 9)
        native.reset_counters()
        t0 = time.monotonic()
        t = g.node_types(one)
        elapsed = time.monotonic() - t0
        assert t[0] == -1  # degraded to default, not wedged
        assert elapsed < 1.5, "DEADLINE reply did not end the call"
        assert native.fault_injected()["handler_stall"] == 1
        ctr = native.counters()
        assert ctr["deadline_rejects"] == 1, ctr   # server side
        assert ctr["deadlines_exceeded"] == 1, ctr  # client side
        assert ctr["calls_failed"] == 1, ctr
        assert ctr["retries"] == 0, ctr  # no retry of dead work
    finally:
        native.fault_clear()
        g.close()


def test_accept_fault_drops_connection_and_client_retries(shard):
    """accept:err drops the freshly-accepted connection on the floor —
    the client sees a mid-exchange reset on a connection that dialed
    fine, and must recover through the ordinary retry path."""
    svc, reg = shard
    native.fault_config("accept:err@1.0#1", 11)
    native.reset_counters()
    g = Graph(mode="remote", registry=reg, retries=3, timeout_ms=2000,
              backoff_ms=1)
    try:
        t = g.node_types(np.array([10, 11], dtype=np.int64))
        np.testing.assert_array_equal(t, [0, 1])
        assert native.fault_injected()["accept"] == 1
        ctr = native.counters()
        assert ctr["retries"] == 1, ctr
        assert ctr["quarantines"] == 1, ctr
        assert ctr["failovers"] == 1, ctr
        assert ctr["dials_failed"] == 0, ctr  # the connect itself worked
    finally:
        g.close()


# ---------------------------------------------------------------------------
# determinism: the seed owns the failure sequence
# ---------------------------------------------------------------------------


def _failure_pattern(reg, seed, n=48):
    """Per-call success/failure pattern of n sequential single-id queries
    under send_frame:err@0.5 with zero retries. The in-process shard
    shares the injector, so the stream's draws interleave client
    request-sends and shard reply-sends — but on a single connection that
    interleaving is itself fixed, so the observable pattern is a pure
    function of the seed."""
    g = Graph(mode="remote", registry=reg, retries=0, timeout_ms=2000,
              quarantine_ms=1)
    try:
        one = np.array([10], dtype=np.int64)
        g.node_types(one)  # warm-up before the faults arm
        native.fault_config("send_frame:err@0.5", seed)
        return tuple(int(g.node_types(one)[0]) == 0 for _ in range(n))
    finally:
        native.fault_clear()
        g.close()


def test_same_seed_replays_identical_failure_sequence(shard):
    svc, reg = shard
    a1 = _failure_pattern(reg, seed=1234)
    a2 = _failure_pattern(reg, seed=1234)
    b = _failure_pattern(reg, seed=99)
    assert a1 == a2, "same seed must replay the same injected failures"
    assert a1 != b, "a different seed must explore a different sequence"
    assert any(a1) and not all(a1), "p=0.5 must mix successes and failures"


# ---------------------------------------------------------------------------
# async whole-step sampling (eg_remote_sample_async): a shard fault that
# lands mid-continuation must degrade exactly like the sync path — same
# counter arithmetic, same strict= contract — and the handle must still
# complete (a faulted op that never reaches kDone would wedge take())
# ---------------------------------------------------------------------------


METAPATH = [[0, 1], [0, 1]]
FANOUTS = [3, 2]


def test_async_fault_degrades_exactly_like_sync(shard):
    """Total send blackout during a 2-hop fan-out: the sync call and the
    async op run the SAME NbrPrep/chunk/Finish phases, so under an
    identical fault seed they must produce the identical degraded result
    and the identical op-level failure ledger."""
    svc, reg = shard
    ids = np.array([10, 12, 14, 16], dtype=np.int64)

    def run(async_mode):
        # fresh client per run: both start from an un-quarantined pool
        # and a cold neighbor cache, so the fault stream sees the same
        # call sequence (cache off => every hop goes to the wire)
        g = Graph(mode="remote", registry=reg, retries=0, timeout_ms=2000,
                  backoff_ms=1, neighbor_cache_mb=0)
        try:
            g.sample_fanout(ids, METAPATH, FANOUTS)  # warm connections
            native.fault_config("send_frame:err@1.0", 31)
            native.counters_reset()
            if async_mode:
                h = g.sample_fanout_async(ids, METAPATH, FANOUTS)
                assert h is not None, "async submit refused"
                out = h.take()
            else:
                out = g.sample_fanout(ids, METAPATH, FANOUTS)
            ctr = native.counters()
            native.fault_clear()
            return out, ctr
        finally:
            native.fault_clear()
            g.close()

    (s_ids, s_w, s_t), s_ctr = run(async_mode=False)
    (a_ids, a_w, a_t), a_ctr = run(async_mode=True)
    # identical degraded output (default-filled rows included)
    for a, b in zip(s_ids, a_ids):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(s_w, a_w):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # identical op-level failure arithmetic: every chunk failed in both
    assert s_ctr["rpc_errors"] >= 1
    assert a_ctr["rpc_errors"] == s_ctr["rpc_errors"], (s_ctr, a_ctr)
    assert a_ctr["calls_failed"] == s_ctr["calls_failed"], (s_ctr, a_ctr)
    # and the async ledger accounted for the op
    assert a_ctr["async_submits"] == 1
    assert s_ctr["async_submits"] == 0


def test_async_strict_raises_at_take_and_recovers(shard):
    """strict=1: a shard failure inside an async op must surface as the
    same RuntimeError the sync path raises — deferred to take(), the
    first point the caller touches the result — and the pending error is
    consumed so the next healthy call proceeds."""
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=0, timeout_ms=2000,
              backoff_ms=1, neighbor_cache_mb=0, strict=True)
    try:
        ids = np.array([10, 12], dtype=np.int64)
        g.sample_fanout(ids, METAPATH, FANOUTS)  # healthy: strict silent
        native.fault_config("send_frame:err@1.0", 33)
        h = g.sample_fanout_async(ids, METAPATH, FANOUTS)
        assert h is not None
        with pytest.raises(RuntimeError, match="shard"):
            h.take()
        native.fault_clear()
        # error consumed: a following healthy async op succeeds
        h2 = g.sample_fanout_async(ids, METAPATH, FANOUTS)
        out_ids, _, _ = h2.take()
        assert [len(x) for x in out_ids] == [2, 6, 12]
    finally:
        native.fault_clear()
        g.close()


def test_async_handle_completes_under_delay_fault(shard):
    """A delay fault stretches the continuation chain without failing
    it: poll() reports running, take() blocks until done, and the
    result is correct — the op is slow, not wrong."""
    svc, reg = shard
    g = Graph(mode="remote", registry=reg, retries=1, timeout_ms=2000,
              backoff_ms=1, neighbor_cache_mb=0)
    try:
        ids = np.array([10, 12], dtype=np.int64)
        g.sample_fanout(ids, METAPATH, FANOUTS)  # warm
        native.fault_config("send_frame:delay@60", 35)
        native.counters_reset()
        h = g.sample_fanout_async(ids, METAPATH, FANOUTS)
        assert h is not None
        out_ids, out_w, _ = h.take()
        assert [len(x) for x in out_ids] == [2, 6, 12]
        ctr = native.counters()
        assert ctr["retries"] == 0  # delay is not a failure
        assert ctr["async_continuations"] >= 1
    finally:
        native.fault_clear()
        g.close()
