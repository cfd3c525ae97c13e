"""Chaos soak: train over a live multi-shard TCP cluster while seeded
failpoints fire, plus a real shard SIGKILL + restart mid-run.

This is the capstone of the failpoint layer (_native/eg_fault, FAULTS.md):
the transport faults that production serves daily — refused dials, slow
links, mid-frame resets, a shard dying and coming back on a new port —
are injected deterministically into a real 2-shard cluster (each shard a
separate OS process, so the training process's injector touches ONLY the
client paths and the ledger arithmetic stays exact), and the run must:

  * complete, with every loss finite;
  * converge to a final loss within tolerance of the fault-free run
    (retry + backoff + quarantine + re-discovery absorb the chaos);
  * account for every injected fault in the exported failure counters.

Fault-sequence determinism (same seed => same injected-failure pattern)
is pinned per failpoint in test_fault_injection.py; here the seed makes
the soak reproducible in the aggregate. Counts still vary a little with
scheduling (retries draw more hits), so the ledger checks are exact
inequalities, not equalities.
"""

import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from euler_tpu.graph import native
from tests.fixture_graph import TOPOLOGY, write_fixture

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NUM_SHARDS = 2
NUM_PARTITIONS = 4
STEPS = 36
KILL_STEP = 12     # SIGKILL shard 1 before this step...
RESTART_STEP = 14  # ...and bring it back (new port) before this one
# client-path faults only: dial refusals, slow sends, mid-frame resets.
# Probabilities low enough that retries=8 makes per-call success ~certain
# once the cluster is up; the shard kill supplies the real failures.
FAULT_SPEC = "dial:err@0.2,send_frame:delay@3@0.3,recv_frame:err@0.15"
FAULT_SEED = 20260804


@pytest.fixture(autouse=True)
def _clean_faults():
    native.fault_clear()
    native.counters_reset()
    yield
    native.fault_clear()
    native.counters_reset()


def _launch_shard(idx: int, data: str, reg: str,
                  extra: list | None = None) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    return subprocess.Popen(
        [sys.executable, "-m", "euler_tpu.graph.service",
         "--data_dir", data, "--shard_idx", str(idx),
         "--shard_num", str(NUM_SHARDS), "--registry", reg,
         *(extra or [])],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
    )


def _wait_registered(idx: int, reg: str, timeout: float = 90.0) -> None:
    """Wait until shard idx has a registry entry that actually accepts
    connections. A SIGKILLed prior incarnation leaves its stale file
    behind — the dial probe is what rejects it, exactly like
    run_loop.build_graph's liveness filter."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for f in os.listdir(reg):
            if not f.startswith(f"{idx}#"):
                continue
            host, port = f.split("#", 1)[1].rsplit("_", 1)
            try:
                with socket.create_connection((host, int(port)), 1.0):
                    return
            except (OSError, ValueError):
                continue  # not up yet, or the entry's ".tmp" being written
        time.sleep(0.1)
    raise TimeoutError(f"shard {idx} never came up in {reg}")


def test_chaos_soak_trains_through_faults_and_shard_restart(tmp_path):
    import jax

    import euler_tpu
    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage

    data = str(tmp_path / "data")
    os.makedirs(data)
    write_fixture(data, num_partitions=NUM_PARTITIONS)
    reg = str(tmp_path / "reg")
    os.makedirs(reg)

    model = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=8, feature_idx=0, feature_dim=2, max_id=16,
    )
    opt = train_lib.get_optimizer("adam", 0.05)
    step = jax.jit(model.make_train_step(opt), donate_argnums=(0,))
    roots = np.array(sorted(TOPOLOGY), dtype=np.int64)

    def run(graph, hook=None):
        native.lib().eg_seed(1234)
        state = model.init_state(jax.random.PRNGKey(0), graph, roots, opt)
        losses = []
        for i in range(STEPS):
            if hook is not None:
                hook(i)
            batch = model.sample(graph, roots)
            state, loss, _ = step(state, batch)
            losses.append(float(loss))
        return losses

    procs = {}
    try:
        for s in range(NUM_SHARDS):
            procs[s] = _launch_shard(s, data, reg)
        for s in range(NUM_SHARDS):
            _wait_registered(s, reg)

        # ---- fault-free reference run ----
        g = euler_tpu.Graph(mode="remote", registry=reg, retries=8,
                            timeout_ms=2000, backoff_ms=2)
        assert g.num_shards == NUM_SHARDS
        clean = run(g)
        g.close()

        # ---- chaos run: seeded failpoints + shard kill/restart ----
        native.counters_reset()
        g = euler_tpu.Graph(
            mode="remote", registry=reg, retries=8, timeout_ms=2000,
            backoff_ms=2, rediscover_ms=300,
            fault=FAULT_SPEC, fault_seed=FAULT_SEED,
        )

        def chaos(i):
            if i == KILL_STEP:
                procs[1].send_signal(signal.SIGKILL)
                procs[1].wait()
            if i == RESTART_STEP:
                procs[1] = _launch_shard(1, data, reg)
                _wait_registered(1, reg)
                # let re-discovery learn the NEW port and route around
                # the stale entry before the tail of the run; id 13 lives
                # on shard 1 ((13 % 4) % 2 == 1), type 1 when reachable
                probe = np.array([13], dtype=np.int64)
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    if int(g.node_types(probe)[0]) == 1:
                        return
                    time.sleep(0.2)
                raise TimeoutError("restarted shard never rejoined")

        faulted = run(g, chaos)
        injected = native.fault_injected()
        counters = native.counters()
        g.close()

        # the run completed, every loss finite, and it actually trained
        assert all(np.isfinite(x) for x in clean + faulted)
        clean_final = float(np.mean(clean[-5:]))
        fault_final = float(np.mean(faulted[-5:]))
        assert fault_final < faulted[0], (faulted[0], fault_final)
        assert abs(fault_final - clean_final) < 0.4, (clean_final,
                                                      fault_final)

        # every configured failpoint demonstrably fired
        assert injected["dial"] > 0, injected
        assert injected["send_frame"] > 0, injected
        assert injected["recv_frame"] > 0, injected

        # ledger: the counters account for every injected fault. The
        # training process runs no service, so its dial/send/recv hooks
        # sit exclusively in ConnPool::Call — each injected dial fault is
        # a counted failed dial, each failing fault quarantines a replica
        # and is followed by a retry or a counted failed call. Real
        # failures from the shard kill only push the counters higher.
        failing = injected["dial"] + injected["recv_frame"]
        assert counters["dials_failed"] >= injected["dial"], (injected,
                                                              counters)
        assert counters["quarantines"] >= failing, (injected, counters)
        assert (counters["retries"] + counters["calls_failed"]
                >= failing), (injected, counters)
        # the kill/restart path was really exercised
        assert counters["failovers"] >= 1, counters
        assert counters["rediscoveries"] >= 1, counters
    finally:
        native.fault_clear()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for p in procs.values():
            p.wait()


def test_chaos_soak_async_pipeline_survives_shard_restart(tmp_path):
    """The sampler_depth=2 soak: the same SIGKILL + restart chaos, but
    every step's fan-out runs through the async completion queue with
    two steps in flight (model.sample_start / sample_finish — the split
    train.py uses for sampler_depth=2). The kill lands while a
    continuation chain is mid-flight, so this pins the property the sync
    soak can't reach: a shard dying BETWEEN hops of an already-submitted
    op degrades that op like the sync path and never wedges take()."""
    from collections import deque

    import jax

    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage

    data = str(tmp_path / "data")
    os.makedirs(data)
    write_fixture(data, num_partitions=NUM_PARTITIONS)
    reg = str(tmp_path / "reg")
    os.makedirs(reg)

    model = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=8, feature_idx=0, feature_dim=2, max_id=16,
    )
    opt = train_lib.get_optimizer("adam", 0.05)
    step = jax.jit(model.make_train_step(opt), donate_argnums=(0,))
    roots = np.array(sorted(TOPOLOGY), dtype=np.int64)
    DEPTH = 2

    procs = {}
    try:
        for s in range(NUM_SHARDS):
            procs[s] = _launch_shard(s, data, reg)
        for s in range(NUM_SHARDS):
            _wait_registered(s, reg)

        import euler_tpu

        native.counters_reset()
        # neighbor cache OFF: the fixture is tiny enough that the
        # init_state warm-up would cache every hop's lists and let all
        # async slices finish inline — wire-bound continuations are the
        # machinery under test, so force every hop onto the wire
        g = euler_tpu.Graph(
            mode="remote", registry=reg, retries=8, timeout_ms=2000,
            backoff_ms=2, rediscover_ms=300, neighbor_cache_mb=0,
            fault=FAULT_SPEC, fault_seed=FAULT_SEED,
        )

        def chaos(i):
            if i == KILL_STEP:
                procs[1].send_signal(signal.SIGKILL)
                procs[1].wait()
            if i == RESTART_STEP:
                procs[1] = _launch_shard(1, data, reg)
                _wait_registered(1, reg)
                probe = np.array([13], dtype=np.int64)
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    if int(g.node_types(probe)[0]) == 1:
                        return
                    time.sleep(0.2)
                raise TimeoutError("restarted shard never rejoined")

        native.lib().eg_seed(1234)
        state = model.init_state(jax.random.PRNGKey(0), g, roots, opt)
        losses = []
        inflight = deque()
        submitted = 0
        # depth-2 ring: chaos fires at SUBMIT time, so the kill hits
        # while the previous step's continuation chain is still running
        while len(losses) < STEPS:
            while submitted < STEPS and len(inflight) < DEPTH:
                chaos(submitted)
                inflight.append(model.sample_start(g, roots))
                submitted += 1
            batch = model.sample_finish(g, inflight.popleft())
            state, loss, _ = step(state, batch)
            losses.append(float(loss))
        counters = native.counters()
        injected = native.fault_injected()
        g.close()

        # completed through the chaos: every loss finite, net training
        assert all(np.isfinite(x) for x in losses)
        assert float(np.mean(losses[-5:])) < losses[0], losses
        # the steps really went through the completion queue
        assert counters["async_submits"] >= STEPS, counters
        assert counters["async_inflight_peak"] >= 1, counters
        # with the cache off every step's hop-0 slice is wire-bound,
        # so each submit re-enqueues at least one continuation
        assert counters["async_continuations"] >= STEPS, counters
        # chaos demonstrably fired and was absorbed by the same
        # retry/failover machinery as the sync soak
        assert injected["dial"] > 0 or injected["recv_frame"] > 0, injected
        assert counters["retries"] + counters["calls_failed"] >= 1, counters
    finally:
        native.fault_clear()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for p in procs.values():
            p.wait()


def test_chaos_soak_epoch_flips_race_async_faults_and_restart(tmp_path):
    """The snapshot-epoch capstone (FAULTS.md "Graph refresh"): a
    rolling delta refresh lands WHILE the depth-2 async ring has steps
    in flight and client-path faults fire, then a SIGKILL drops one
    shard's freshly-flipped epoch entirely. The restarted incarnation
    comes back at epoch 0 (a delta lives only in the epoch table of the
    process that merged it), refuses its first re-apply through a
    server-side `delta_load` failpoint, and applies it on retry — and
    the ledger accounts for every epoch, including the dropped one: the
    client completed three load_delta calls but the surviving processes
    can only show two flips; the difference IS the kill."""
    from collections import deque

    import jax

    import euler_tpu
    from euler_tpu import telemetry as T
    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage
    from tests.test_epoch import _minimal_new_nodes, _write_delta

    data = str(tmp_path / "data")
    os.makedirs(data)
    write_fixture(data, num_partitions=NUM_PARTITIONS)
    reg = str(tmp_path / "reg")
    os.makedirs(reg)
    dpath = _write_delta(str(tmp_path / "part.delta.1"),
                         _minimal_new_nodes())

    model = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=8, feature_idx=0, feature_dim=2, max_id=16,
    )
    opt = train_lib.get_optimizer("adam", 0.05)
    step = jax.jit(model.make_train_step(opt), donate_argnums=(0,))
    roots = np.array(sorted(TOPOLOGY), dtype=np.int64)
    DEPTH = 2
    FLIP0_STEP, FLIP1_STEP = 8, 10  # both < KILL_STEP: shard 1's flip
    # is merged, announced, observed — then DROPPED by the SIGKILL

    procs = {}
    applied_ok = 0
    try:
        for s in range(NUM_SHARDS):
            procs[s] = _launch_shard(s, data, reg)
        for s in range(NUM_SHARDS):
            _wait_registered(s, reg)

        native.counters_reset()
        g = euler_tpu.Graph(
            mode="remote", registry=reg, retries=8, timeout_ms=2000,
            backoff_ms=2, rediscover_ms=300, neighbor_cache_mb=0,
            fault=FAULT_SPEC, fault_seed=FAULT_SEED,
        )

        def load_clean(shard):
            # the control-plane call runs fault-free: a client-side
            # recv fault AFTER the server merged would retry the same
            # seq and be refused as stale — by design load_delta is
            # NOT idempotent, so the runbook (and this soak) keeps the
            # one-line control call off the chaotic path
            nonlocal applied_ok
            native.fault_clear()
            try:
                assert g.load_delta(dpath, shard=shard) == 1
                applied_ok += 1
            finally:
                native.fault_config(FAULT_SPEC, FAULT_SEED)

        def chaos(i):
            if i == FLIP0_STEP:
                load_clean(0)
            if i == FLIP1_STEP:
                load_clean(1)
            if i == KILL_STEP:
                procs[1].send_signal(signal.SIGKILL)
                procs[1].wait()
            if i == RESTART_STEP:
                # fresh incarnation: epoch 0 again, and its FIRST
                # delta load refused by a server-side failpoint
                procs[1] = _launch_shard(
                    1, data, reg,
                    extra=["--fault", "delta_load:err@1.0#1",
                           "--fault_seed", "3"],
                )
                _wait_registered(1, reg)
                probe = np.array([13], dtype=np.int64)
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    if int(g.node_types(probe)[0]) == 1:
                        break
                    time.sleep(0.2)
                else:
                    raise TimeoutError("restarted shard never rejoined")
                native.fault_clear()
                try:
                    with pytest.raises(RuntimeError):
                        g.load_delta(dpath, shard=1)  # failpoint fires
                    load_clean(1)  # limit #1 exhausted: re-apply lands
                finally:
                    native.fault_config(FAULT_SPEC, FAULT_SEED)

        native.lib().eg_seed(1234)
        state = model.init_state(jax.random.PRNGKey(0), g, roots, opt)
        losses = []
        inflight = deque()
        submitted = 0
        while len(losses) < STEPS:
            while submitted < STEPS and len(inflight) < DEPTH:
                chaos(submitted)
                inflight.append(model.sample_start(g, roots))
                submitted += 1
            batch = model.sample_finish(g, inflight.popleft())
            state, loss, _ = step(state, batch)
            losses.append(float(loss))
        counters = native.counters()
        injected = native.fault_injected()

        # survived and trained through flips + faults + kill
        assert all(np.isfinite(x) for x in losses)
        assert float(np.mean(losses[-5:])) < losses[0], losses
        assert counters["async_submits"] >= STEPS, counters
        assert injected["dial"] > 0 or injected["recv_frame"] > 0, injected

        # end state: both shards serve epoch 1, the client observed the
        # raises passively and bumped its cache generation for each
        assert applied_ok == 3  # shard 0, shard 1, shard 1 re-applied
        assert g.shard_epoch(0) == 1, g.shard_epoch(0)
        assert g.shard_epoch(1) == 1, g.shard_epoch(1)
        assert g.epoch() == 1
        assert g.cache_gen >= 2, g.cache_gen
        # the retargeted row serves post-delta data (14 lives on shard 0)
        nbr, _, _ = g.sample_neighbor(
            np.array([14], dtype=np.int64), [0], 2, default_node=-1
        )
        assert set(np.asarray(nbr).ravel()) == {16}, nbr

        # per-shard ledger over the STATS scrape: every SURVIVING
        # process shows exactly one flip (+ the restart's one refused
        # load), and every retired epoch drained. applied_ok == 3 vs
        # 1 + 1 scraped flips: the missing flip is the SIGKILLed
        # incarnation's — the dropped epoch, accounted for.
        deadline = time.monotonic() + 10.0
        scrapes = {}
        while time.monotonic() < deadline:
            scrapes = {s: T.scrape(g, s)["counters"]
                       for s in range(NUM_SHARDS)}
            if all(c["epoch_drains"] == c["epoch_flips"] == 1
                   for c in scrapes.values()):
                break
            g.sample_neighbor(np.array([14], dtype=np.int64), [0], 2)
            time.sleep(0.1)
        for s, c in scrapes.items():
            assert c["epoch_flips"] == 1, (s, c)
            assert c["epoch_drains"] == 1, (s, c)
        assert scrapes[0]["delta_loads_failed"] == 0, scrapes[0]
        assert scrapes[1]["delta_loads_failed"] == 1, scrapes[1]
        g.close()
    finally:
        native.fault_clear()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for p in procs.values():
            p.wait()
