"""Async whole-step sampling parity: eg_remote_sample_async vs the sync
path, on a reddit_heavytail-shaped fixture, plus the live input-stall
acceptance check.

Parity strategy: the async chain and sample_fanout run the SAME
NbrPrep/chunk/Finish phases against the same shards, so everything
deterministic must be BIT-identical — shapes, the root hop, default
fills, per-edge weights/types, neighbor-set membership. The draws
themselves go through server-side thread-local RNG (like the sync
path), so draw-for-draw equality across calls is not defined even
sync-vs-sync; there the contract is the reference's (SURVEY §4
sampler-distribution tests): empirical neighbor frequencies converge to
the same edge-weight distribution. Both halves are pinned here.

The acceptance test drives the sampler_depth=2 pipeline against a live
2-shard SUBPROCESS cluster and holds it to the counts that show it ran
ahead of its consumer (a stall share of a simulated step on this host's
clock is a timing of a shared CPU, not a property of the pipeline).
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from euler_tpu.graph import native
from euler_tpu.graph.graph import Graph
from euler_tpu.graph.service import GraphService

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NUM_SHARDS = 2
NUM_NODES = 400
METAPATH = [[0, 1], [0, 1]]
FANOUTS = [5, 3]


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """reddit_heavytail recipe at test scale (power-law out-degrees,
    preferential targets) behind 2 in-process shards + a local mirror
    for ground truth."""
    from euler_tpu.datasets import build_powerlaw

    data = str(tmp_path_factory.mktemp("async_parity_data"))
    build_powerlaw(data, num_nodes=NUM_NODES, num_edges=6000,
                   feature_dim=8, label_dim=3, alpha=1.8,
                   num_partitions=4, seed=23)
    reg = str(tmp_path_factory.mktemp("async_parity_reg"))
    services = [
        GraphService(data, s, NUM_SHARDS, registry=reg)
        for s in range(NUM_SHARDS)
    ]
    local = Graph(directory=data)
    remote = Graph(mode="remote", registry=reg)
    yield local, remote
    remote.close()
    local.close()
    for s in services:
        s.stop()


def _truth(local, ids, etypes):
    """{src: {dst: (weight, type)}} ground truth from the local ragged
    full-neighbor lists."""
    ids = np.asarray(ids, dtype=np.int64)
    nbr, w, t, counts = local.get_full_neighbor(ids, etypes)
    out = {}
    off = 0
    for i, src in enumerate(ids):
        c = int(counts[i])
        row = {}
        for d, ww, tt in zip(nbr[off:off + c], w[off:off + c],
                             t[off:off + c]):
            row[int(d)] = (float(ww), int(tt))
        out[int(src)] = row
        off += c
    return out


def _check_hops(local, roots, hop_ids, hop_w, hop_t, default):
    """Every sampled (src, dst, w, t) is bit-exact against the local
    graph's edge data; dead-end rows are default-filled with zero
    weight."""
    frontier = np.asarray(roots)
    for h in range(len(FANOUTS)):
        fan = FANOUTS[h]
        dst = np.asarray(hop_ids[h + 1]).reshape(len(frontier), fan)
        w = np.asarray(hop_w[h]).reshape(len(frontier), fan)
        t = np.asarray(hop_t[h]).reshape(len(frontier), fan)
        truth = _truth(
            local, np.unique(frontier[frontier >= 0]), METAPATH[h]
        )
        for i, src in enumerate(frontier):
            src = int(src)
            row = truth.get(src, {})
            for j in range(fan):
                d = int(dst[i, j])
                if src < 0 or not row:
                    # dead end (or propagated default): default fill
                    assert d == default, (h, src, d)
                    assert w[i, j] == 0.0, (h, src, w[i, j])
                    continue
                assert d in row, (h, src, d)
                tw, tt = row[d]
                assert w[i, j] == np.float32(tw), (h, src, d)
                assert t[i, j] == tt, (h, src, d)
        frontier = dst.reshape(-1)


def test_async_structurally_bit_exact_vs_sync(cluster):
    """Shapes, root hop, per-edge weight/type payloads, neighbor-set
    membership, and default fills: identical contract for sync and
    async outputs, element-for-element checkable against the local
    graph."""
    local, remote = cluster
    rng = np.random.default_rng(3)
    roots = rng.integers(0, NUM_NODES, 64).astype(np.int64)

    s_out = remote.sample_fanout(roots, METAPATH, FANOUTS, default_node=-1)
    h = remote.sample_fanout_async(roots, METAPATH, FANOUTS,
                                   default_node=-1)
    assert h is not None
    a_out = h.take()

    for out in (s_out, a_out):
        hop_ids, hop_w, hop_t = out
        assert [len(x) for x in hop_ids] == [64, 64 * 5, 64 * 5 * 3]
        assert [len(x) for x in hop_w] == [64 * 5, 64 * 5 * 3]
        np.testing.assert_array_equal(np.asarray(hop_ids[0]), roots)
        _check_hops(local, roots, hop_ids, hop_w, hop_t, default=-1)


def test_async_deterministic_subgraph_bit_identical(tmp_path):
    """On the deterministic slice of the draw — sources whose typed
    neighbor list has exactly one candidate (fixture nodes 11, 13, 14
    for edge type 0), and sources with none (node 15) — sync and async
    must agree BIT-FOR-BIT call after call: no RNG is consulted for
    forced rows, so this is the strongest parity the server-side
    thread-local RNG permits."""
    from tests.fixture_graph import write_fixture

    data = str(tmp_path / "tiny")
    os.makedirs(data)
    write_fixture(data, num_partitions=2)
    reg = str(tmp_path / "tiny_reg")
    os.makedirs(reg)
    services = [
        GraphService(data, s, NUM_SHARDS, registry=reg)
        for s in range(NUM_SHARDS)
    ]
    remote = Graph(mode="remote", registry=reg)
    try:
        ids = np.array([11, 13, 14, 15], dtype=np.int64)
        fan = 4
        # 11 -0-> {12}, 13 -0-> {10}, 14 -0-> {15}; 15 has no out-edges
        expect = np.repeat(
            np.array([12, 10, 15, -1], dtype=np.int64), fan
        ).reshape(len(ids), fan)
        s_ids, s_w, _ = remote.sample_neighbor(ids, [0], fan,
                                               default_node=-1)
        np.testing.assert_array_equal(
            np.asarray(s_ids).reshape(len(ids), fan), expect
        )
        assert np.all(np.asarray(s_w).reshape(len(ids), fan)[3] == 0.0)
        for _ in range(3):
            h = remote.sample_fanout_async(ids, [[0]], [fan],
                                           default_node=-1)
            assert h is not None
            a_ids, a_w, _ = h.take()
            np.testing.assert_array_equal(
                np.asarray(a_ids[1]).reshape(len(ids), fan), expect
            )
            np.testing.assert_array_equal(
                np.asarray(a_w[0]).reshape(len(ids), fan),
                np.asarray(s_w).reshape(len(ids), fan),
            )
    finally:
        remote.close()
        for s in services:
            s.stop()


def test_async_distribution_matches_sync(cluster):
    """Sampler-distribution parity (the reference's
    compact_weighted_collection_test.cc technique): over many draws from
    one hub, async empirical neighbor frequencies match the sync path's
    and the true edge-weight distribution."""
    local, remote = cluster
    truth = _truth(local, np.arange(NUM_NODES), [0, 1])
    hub = max(truth, key=lambda s: len(truth[s]))
    assert len(truth[hub]) >= 5
    total_w = sum(w for w, _ in truth[hub].values())
    ids = np.full(256, hub, dtype=np.int64)
    fan = 8
    n_draws = 256 * fan * 4

    def freqs(async_mode):
        counts: dict = {}
        for _ in range(4):
            if async_mode:
                h = remote.sample_fanout_async(ids, [[0, 1]], [fan])
                out, _, _ = h.take()
                drawn = np.asarray(out[1])
            else:
                out, _, _ = remote.sample_neighbor(ids, [0, 1], fan)
                drawn = np.asarray(out)
            for d in drawn.ravel():
                counts[int(d)] = counts.get(int(d), 0) + 1
        return {d: c / n_draws for d, c in counts.items()}

    f_sync = freqs(False)
    f_async = freqs(True)
    for d, (w, _) in truth[hub].items():
        expect = w / total_w
        assert f_sync.get(d, 0.0) == pytest.approx(expect, abs=0.03), d
        assert f_async.get(d, 0.0) == pytest.approx(expect, abs=0.03), d
        assert f_async.get(d, 0.0) == pytest.approx(
            f_sync.get(d, 0.0), abs=0.03
        ), d


def _launch_shard(idx, data, reg):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    return subprocess.Popen(
        [sys.executable, "-m", "euler_tpu.graph.service",
         "--data_dir", data, "--shard_idx", str(idx),
         "--shard_num", str(NUM_SHARDS), "--registry", reg],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
    )


def _wait_registered(idx, reg, timeout=90.0):
    import socket

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for f in os.listdir(reg):
            if f.startswith(f"{idx}#"):
                host, port = f.split("#", 1)[1].rsplit("_", 1)
                try:
                    with socket.create_connection((host, int(port)), 1.0):
                        return
                except OSError:
                    pass
        time.sleep(0.1)
    raise TimeoutError(f"shard {idx} never came up")


def test_acceptance_input_stall_under_threshold_live_cluster(tmp_path):
    """ROADMAP item 1 exit criterion on a live 2-shard SUBPROCESS
    cluster, as counts: with sampler_depth=2 every step's sampling is
    submitted through the completion queue, two steps are in flight at
    once, the hop chains advance on the dispatcher pool and not under a
    blocked caller, and at every dequeue but the last a later step is
    already submitted. (How small the consumer's stall then is against
    a device step is the chip's to say: `input.stall_ms` of a
    remote-fed cell, ROADMAP R6.)"""
    from euler_tpu.datasets import build_powerlaw
    from euler_tpu.parallel import pipeline
    from euler_tpu.telemetry import (
        phase_hists,
        set_telemetry,
        telemetry_json,
        telemetry_reset,
    )

    data = str(tmp_path / "data")
    os.makedirs(data)
    build_powerlaw(data, num_nodes=NUM_NODES, num_edges=6000,
                   feature_dim=8, label_dim=3, alpha=1.8,
                   num_partitions=4, seed=23)
    reg = str(tmp_path / "reg")
    os.makedirs(reg)
    procs = [_launch_shard(s, data, reg) for s in range(NUM_SHARDS)]
    try:
        for s in range(NUM_SHARDS):
            _wait_registered(s, reg)
        set_telemetry(True)
        g = Graph(mode="remote", registry=reg)
        try:
            rng = np.random.default_rng(5)
            batch, steps, depth = 64, 24, 2

            # calibrate: a device step the size of one sync sample, so
            # "hidden" is a real race, not a huge denominator
            t0 = time.perf_counter()
            for _ in range(3):
                roots = rng.integers(0, NUM_NODES, batch).astype(np.int64)
                g.sample_fanout(roots, METAPATH, FANOUTS)
            device_s = max(0.002, (time.perf_counter() - t0) / 3)

            def start_fn(step):
                roots = rng.integers(0, NUM_NODES, batch).astype(np.int64)
                return roots, g.sample_fanout_async(
                    roots, METAPATH, FANOUTS
                )

            def finish_fn(step, pending):
                roots, h = pending
                if h is None:
                    return g.sample_fanout(roots, METAPATH, FANOUTS)
                return h.take()

            native.reset_counters()
            first = True
            for _ in pipeline(start_fn, finish_fn, steps, depth=depth):
                if first:  # drop the pipeline-fill stall of step 0
                    telemetry_reset()
                    first = False
                time.sleep(device_s)  # simulated device compute

            stall = phase_hists().get("input_stall")
            assert stall and stall["count"] >= steps - 1, stall
            ctr = native.counters()
            assert ctr["async_submits"] == steps, ctr  # none fell back
            assert ctr["async_inflight_peak"] >= depth, ctr
            assert ctr["async_continuations"] >= steps, ctr
            # submits in flight at each of the consumer's dequeues (the
            # gauge's sum is over values, not µs): one or two, and none
            # only once the driver has finished its last step, when at
            # most the queue's depth+1 batches, the driver's one and the
            # consumer's one are still to be counted
            ahead = telemetry_json()["hist"]["prefetch_busy"]
            assert ahead["count"] == stall["count"], (ahead, stall)
            assert ahead["sum_us"] >= ahead["count"] - (depth + 3), ahead
        finally:
            g.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()
