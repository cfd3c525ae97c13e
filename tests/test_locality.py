"""Locality layer (ISSUE 9 / ROADMAP item 5): the degree-aware
partitioner + placement-map routing, TinyLFU-shaped cache admission, and
the client-side neighbor-list cache.

What is pinned here, mostly with EXACT arithmetic:

  * convert.py input validation — partitions < 1 and duplicate node_ids
    fail loudly instead of silently overwriting rows;
  * the greedy degree-descending placement respects its balance cap,
    places every node, and strictly beats hash partitioning's edge-cut
    on the hub-heavy fixture;
  * a corrupt / ambiguous / inconsistent placement artifact fails the
    shard start loudly — misrouting must never be silent;
  * TinyLFU admit/reject decisions against a hand-computed sketch
    state: the exact `cache_admit_rejects` ledger of a
    cold-candidate-vs-hot-victim sequence, stripe collisions derived by
    replicating the native key mix in Python;
  * exact neighbor-list cache counter arithmetic: promotion fires at
    the pinned sketch threshold, every later call is a local hit, and
    the heat fan-out ledger identity (ids_on_wire == requested -
    deduped - cache_hits) holds with the neighbor cache in the loop.
"""

import os

import numpy as np
import pytest

import euler_tpu
from euler_tpu import heat as H
from euler_tpu.graph import native
from euler_tpu.graph.convert import (
    convert_dicts,
    degree_placement,
    write_placement,
)
from euler_tpu.graph.graph import Graph
from euler_tpu.graph.service import GraphService
from tests.test_remote_dedup_cache import (
    NUM_PARTITIONS,
    NUM_SHARDS,
    PL_META,
    powerlaw_nodes,
)

M64 = (1 << 64) - 1


@pytest.fixture(autouse=True)
def _clean_state():
    native.reset_counters()
    H.heat_reset()
    yield
    native.reset_counters()
    H.heat_reset()


# ---------------------------------------------------------------------------
# convert.py input validation
# ---------------------------------------------------------------------------


def test_convert_rejects_partitions_below_one(tmp_path):
    for bad in (0, -3):
        with pytest.raises(ValueError, match="num_partitions"):
            convert_dicts(powerlaw_nodes(), PL_META,
                          str(tmp_path / "part"), num_partitions=bad)


def test_convert_rejects_duplicate_node_ids(tmp_path):
    nodes = powerlaw_nodes()
    nodes.append(dict(nodes[3]))  # second copy of node_id 3
    for placement in ("hash", "degree"):
        with pytest.raises(ValueError, match="duplicate node_id 3"):
            convert_dicts(nodes, PL_META, str(tmp_path / placement),
                          num_partitions=2, placement=placement)


def test_convert_rejects_unknown_placement(tmp_path):
    with pytest.raises(ValueError, match="placement"):
        convert_dicts(powerlaw_nodes(), PL_META, str(tmp_path / "part"),
                      num_partitions=2, placement="zoned")


# ---------------------------------------------------------------------------
# the degree-aware partitioner: balance + strict edge-cut win over hash
# ---------------------------------------------------------------------------


def test_degree_placement_balance_and_coverage():
    nodes = powerlaw_nodes()
    placed = degree_placement(nodes, NUM_PARTITIONS)
    assert set(placed) == {int(n["node_id"]) for n in nodes}
    assert all(0 <= p < NUM_PARTITIONS for p in placed.values())
    cap = -(-int(len(nodes) * 1.2) // NUM_PARTITIONS)
    loads = [0] * NUM_PARTITIONS
    for p in placed.values():
        loads[p] += 1
    assert max(loads) <= cap, loads


def test_degree_placement_beats_hash_edge_cut():
    """The partitioner's whole point, measured on the static graph: the
    fraction of directed edges whose endpoints land on different SHARDS
    (partition % NUM_SHARDS) must be strictly below hash partitioning's
    on the hub-heavy fixture."""
    nodes = powerlaw_nodes()
    placed = degree_placement(nodes, NUM_PARTITIONS)

    def edge_cut(shard_of):
        cross = total = 0
        for n in nodes:
            u = int(n["node_id"])
            for group in (n.get("neighbor") or {}).values():
                for dst in group:
                    total += 1
                    if shard_of(u) != shard_of(int(dst)):
                        cross += 1
        return cross / total

    hash_cut = edge_cut(lambda i: (i % NUM_PARTITIONS) % NUM_SHARDS)
    place_cut = edge_cut(lambda i: placed[i] % NUM_SHARDS)
    assert place_cut < hash_cut, (place_cut, hash_cut)


# ---------------------------------------------------------------------------
# corrupt / ambiguous placement artifacts fail the shard start loudly
# ---------------------------------------------------------------------------


@pytest.fixture()
def hash_data(tmp_path):
    data = str(tmp_path / "data")
    os.makedirs(data)
    convert_dicts(powerlaw_nodes(), PL_META, data + "/part",
                  num_partitions=NUM_PARTITIONS)
    return data


def test_service_rejects_garbage_placement_artifact(hash_data):
    with open(os.path.join(hash_data, "part.placement"), "wb") as f:
        f.write(b"JUNKJUNKJUNKJUNKJUNK")
    with pytest.raises(RuntimeError, match="magic"):
        GraphService(hash_data, 0, NUM_SHARDS)


def test_service_rejects_truncated_placement_artifact(hash_data):
    placed = {i: i % NUM_PARTITIONS for i in range(10)}
    path = os.path.join(hash_data, "part.placement")
    write_placement(path, placed, NUM_PARTITIONS)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:-7])  # cut mid-array: count now exceeds payload
    with pytest.raises(RuntimeError, match="placement"):
        GraphService(hash_data, 0, NUM_SHARDS)


def test_service_rejects_partition_count_mismatch(hash_data):
    # artifact claims 3 partitions, the dir holds NUM_PARTITIONS (4)
    placed = {i: i % 3 for i in range(10)}
    write_placement(os.path.join(hash_data, "part.placement"), placed, 3)
    with pytest.raises(RuntimeError, match="partitions"):
        GraphService(hash_data, 0, NUM_SHARDS)


def test_service_rejects_ambiguous_placement_artifacts(hash_data):
    placed = {i: i % NUM_PARTITIONS for i in range(10)}
    write_placement(os.path.join(hash_data, "a.placement"), placed,
                    NUM_PARTITIONS)
    write_placement(os.path.join(hash_data, "b.placement"), placed,
                    NUM_PARTITIONS)
    with pytest.raises(RuntimeError, match="multiple"):
        GraphService(hash_data, 0, NUM_SHARDS)


# ---------------------------------------------------------------------------
# TinyLFU admission: exact admit/reject ledger vs a hand-computed sketch
# ---------------------------------------------------------------------------


def _fnv_spec(fids, dims):
    """Python twin of FeatureCache::SpecHash (FNV-1a over fids+dims)."""
    h = 0xCBF29CE484222325
    for v in list(fids) + list(dims):
        for b in range(4):
            h ^= (v >> (8 * b)) & 0xFF
            h = (h * 0x100000001B3) & M64
    return h


def _mix(spec, nid):
    """Python twin of FeatureCache::Mix (splitmix64 finalizer)."""
    z = (spec ^ ((nid + 0x9E3779B97F4A7C15) & M64)) & M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & M64
    return (z ^ (z >> 31)) & M64


def _stripe_colliding_ids(spec, stripe, want, limit=100000):
    out = []
    for nid in range(limit):
        if _mix(spec, nid) % 16 == stripe:
            out.append(nid)
            if len(out) == want:
                return out
    raise AssertionError("not enough colliding ids")


@pytest.fixture(scope="module")
def lfu_cluster(tmp_path_factory):
    """Single-shard cluster over a wide id space (ids 0..2999), so
    stripe-colliding id sets exist for any spec."""
    from tests.fixture_graph import PL_META as BENCH_META
    from tests.fixture_graph import powerlaw_fixture_nodes

    data = str(tmp_path_factory.mktemp("lfu_data"))
    convert_dicts(powerlaw_fixture_nodes(3000, 6, 8), BENCH_META,
                  data + "/part", num_partitions=1)
    svc = GraphService(data, 0, 1)
    yield svc
    svc.stop()


def test_tinylfu_exact_admit_reject_ledger(lfu_cluster):
    """Drive ONE cache stripe to capacity with hot rows (sketch est 3
    each), then offer cold candidates. Hand-computed TinyLFU verdicts:
      * candidate est 1 vs victim est 3  -> reject
      * candidate est 2, 3 vs victim 3  -> reject (admission is STRICT:
        ties keep the resident row, which already paid its fetch)
      * candidate est 4 vs victim 3     -> admit (one victim evicted)
    cache_admit_rejects must equal exactly the rejects above, and the
    admitted row must hit on its next access."""
    # 1 MB budget / 16 stripes = 65536 B per stripe; a 512-float row
    # costs 512*4 + 96 = 2144 B, so exactly 30 rows fill a stripe
    g = Graph(mode="remote", shards=[lfu_cluster.address], retries=2,
              timeout_ms=5000, feature_cache_mb=1, neighbor_cache_mb=0)
    try:
        spec = _fnv_spec([0], [512])
        ids = _stripe_colliding_ids(spec, stripe=0, want=32)
        warm, x, y = ids[:30], ids[30], ids[31]
        euler_tpu.telemetry_reset()
        H.heat_reset()
        native.reset_counters()
        warm_arr = np.array(warm, dtype=np.int64)
        for _ in range(3):  # each call feeds every unique id once
            g.get_dense_feature(warm_arr, [0], [512])
        c = native.counters()
        assert c["cache_misses"] == 30, c   # cold fill
        assert c["cache_hits"] == 60, c     # calls 2-3 all hit
        assert c["cache_admit_rejects"] == 0, c
        # cold candidate X: est 1 < victim est 3 -> rejected, once
        g.get_dense_feature(np.array([x], dtype=np.int64), [0], [512])
        c = native.counters()
        assert c["cache_admit_rejects"] == 1, c
        # warming candidate Y: est 1, 2, 3 rejected (strict >), est 4
        # admitted; the 5th access is a hit served from the cache
        for _ in range(4):
            g.get_dense_feature(np.array([y], dtype=np.int64), [0], [512])
        c = native.counters()
        assert c["cache_admit_rejects"] == 4, c  # 1 (X) + 3 (Y)
        native.reset_counters()
        g.get_dense_feature(np.array([y], dtype=np.int64), [0], [512])
        c = native.counters()
        assert c["cache_hits"] == 1 and c["cache_misses"] == 0, c
    finally:
        g.close()


def test_fifo_policy_restores_unconditional_admission(lfu_cluster):
    """cache_policy=fifo: the same cold-candidate sequence admits every
    row (evicting hot victims) and never counts a rejection."""
    g = Graph(mode="remote", shards=[lfu_cluster.address], retries=2,
              timeout_ms=5000, feature_cache_mb=1, neighbor_cache_mb=0,
              cache_policy="fifo")
    try:
        spec = _fnv_spec([0], [512])
        ids = _stripe_colliding_ids(spec, stripe=0, want=31)
        euler_tpu.telemetry_reset()
        H.heat_reset()
        native.reset_counters()
        warm = np.array(ids[:30], dtype=np.int64)
        for _ in range(3):
            g.get_dense_feature(warm, [0], [512])
        g.get_dense_feature(np.array([ids[30]], dtype=np.int64), [0],
                            [512])
        c = native.counters()
        assert c["cache_admit_rejects"] == 0, c
        # the candidate displaced the FIFO head: re-requesting it hits
        native.reset_counters()
        g.get_dense_feature(np.array([ids[30]], dtype=np.int64), [0],
                            [512])
        assert native.counters()["cache_hits"] == 1
    finally:
        g.close()


def test_bad_cache_policy_rejected(lfu_cluster):
    with pytest.raises(RuntimeError, match="cache_policy"):
        Graph(mode="remote", shards=[lfu_cluster.address], retries=1,
              timeout_ms=2000, cache_policy="lru")


def test_cache_policy_rejected_on_local_mode(tmp_path):
    with pytest.raises(ValueError, match="remote"):
        Graph(directory=str(tmp_path), cache_policy="fifo")
    with pytest.raises(ValueError, match="remote"):
        Graph(directory=str(tmp_path), neighbor_cache_mb=8)
    with pytest.raises(ValueError, match="remote"):
        Graph(directory=str(tmp_path), placement=True)


# ---------------------------------------------------------------------------
# neighbor-list cache: exact promotion/hit arithmetic + ledger identity
# ---------------------------------------------------------------------------


@pytest.fixture()
def nbr_cluster(tmp_path):
    data = str(tmp_path / "data")
    os.makedirs(data)
    convert_dicts(powerlaw_nodes(), PL_META, data + "/part",
                  num_partitions=NUM_PARTITIONS)
    svcs = [GraphService(data, s, NUM_SHARDS) for s in range(NUM_SHARDS)]
    local = Graph(directory=data)
    yield local, svcs
    local.close()
    for s in svcs:
        s.stop()


def test_neighbor_cache_exact_promotion_arithmetic(nbr_cluster):
    """One hub sampled repeatedly: each call feeds the sketch once (one
    unique id), so the estimate after call k is exactly k. Promotion is
    pinned at est >= 8 (kNbrPromoteMinFreq): calls 1..8 miss (call 8
    fetches the full slice), calls 9..12 sample locally — so over 12
    calls nbr_cache_misses == 8 and nbr_cache_hits == 4, and the heat
    fan-out identity holds with the neighbor cache in the loop."""
    local, svcs = nbr_cluster
    g = Graph(mode="remote", shards=[s.address for s in svcs], retries=2,
              timeout_ms=5000)
    try:
        euler_tpu.telemetry_reset()
        H.heat_reset()
        native.reset_counters()
        ids = np.full(50, 0, dtype=np.int64)  # hub 0, duplicated
        for _ in range(12):
            g.sample_neighbor(ids, [0, 1], 4)
        c = native.counters()
        assert c["nbr_cache_misses"] == 8, c
        assert c["nbr_cache_hits"] == 4, c
        f = H.heat_json()["fanout"]["sample_neighbor"]
        assert f["ids_on_wire"] == (f["ids_requested"] - f["ids_deduped"]
                                    - f["cache_hits"]), f
        assert f["cache_hits"] == 4, f
    finally:
        g.close()


def test_neighbor_cache_hits_match_engine_distribution(nbr_cluster):
    """Locally-sampled draws (cache hits) must match the host engine's
    neighbor distribution — the sampler-distribution half of the
    acceptance criteria — and duplicate rows stay independent."""
    local, svcs = nbr_cluster
    g = Graph(mode="remote", shards=[s.address for s in svcs], retries=2,
              timeout_ms=5000)
    try:
        H.heat_reset()
        native.reset_counters()
        hub = 0
        ids = np.full(200, hub, dtype=np.int64)
        for _ in range(9):  # past the promotion point: draws now local
            g.sample_neighbor(ids, [0, 1], 4)
        assert native.counters()["nbr_cache_hits"] >= 1
        r_nbr, r_w, r_t = g.sample_neighbor(ids, [0, 1], 8)
        l_nbr, _, _ = local.sample_neighbor(ids, [0, 1], 8)
        r_nbr, l_nbr = np.asarray(r_nbr), np.asarray(l_nbr)
        distinct = {tuple(row) for row in r_nbr.tolist()}
        assert len(distinct) > 1, "duplicate rows shared one sample"
        values = np.unique(np.concatenate([r_nbr.ravel(), l_nbr.ravel()]))
        for v in values:
            rf = (r_nbr == v).mean()
            lf = (l_nbr == v).mean()
            assert abs(rf - lf) < 0.05, (v, rf, lf)
        # weights/types carried through the local draw match the
        # engine's vocabulary for this hub
        l_full = local.get_full_neighbor([hub], [0, 1])
        assert set(np.asarray(r_nbr).ravel()) <= set(
            np.asarray(l_full[0]).tolist()
        )
    finally:
        g.close()


def test_neighbor_cache_disabled_stays_on_wire(nbr_cluster):
    local, svcs = nbr_cluster
    g = Graph(mode="remote", shards=[s.address for s in svcs], retries=2,
              timeout_ms=5000, neighbor_cache_mb=0)
    try:
        H.heat_reset()
        native.reset_counters()
        ids = np.full(50, 0, dtype=np.int64)
        for _ in range(12):
            g.sample_neighbor(ids, [0, 1], 4)
        c = native.counters()
        assert c["nbr_cache_hits"] == 0, c
        assert c["nbr_cache_misses"] == 0, c  # disabled: never probed
    finally:
        g.close()
