"""Fused Pallas sampling kernels vs the host engine and the XLA path.

The kernel-executing tests here require a real TPU backend: they
exercise the ON-CORE PRNG's stream (statistical pinning against the
host engine) and the compiled kernels, which interpret mode cannot
attest. Run on the chip (the env var keeps conftest.py from forcing the
virtual CPU backend):

    EULER_TPU_TESTS_ON_TPU=1 python -m pytest tests/test_pallas_sampling.py -v

On one chip the single-device kernel tests run; on a host with >= 4
chips those skip (the direct route is single-device by design) and the
per-shard kernel tests at the end of the file run on a real mesh.

Everything BELOW the PRNG — layout, DMA addressing, rank/select across
registers, the chained kernel's data-dependent hop-2 DMAs, default/OOB
contracts — additionally runs on CPU in the default suite through
pallas' TPU interpret mode with injected uniforms, as EXACT-equality
tests: see tests/test_pallas_interpret.py.

The recorded on-chip runs are in PERF.md (Findings); the distribution
check mirrors tests/test_device_graph.py's statistical pinning of the
XLA path against the host engine.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from euler_tpu.graph import pallas_sampling

MAX_ID = 16  # fixture ids are 10..16 (tests/fixture_graph.py TOPOLOGY)

tpu_only = pytest.mark.skipif(
    not pallas_sampling.available(),
    reason="needs a single-device TPU backend (pallas kernel path)",
)
tpu_mesh = pytest.mark.skipif(
    not (pallas_sampling.sharded_available() and len(jax.devices()) >= 4),
    reason="needs a TPU backend with >= 4 devices (per-shard kernel path)",
)


# ---- activation guards (pure host logic, run everywhere) ----


def test_eligible_budgets():
    ps = pallas_sampling
    assert ps.eligible(5120, 10)            # PPI hop-2 draw
    assert ps.eligible(1, ps.MAX_COUNT)
    assert not ps.eligible(1, ps.MAX_COUNT + 1)
    assert not ps.eligible(204800, 10)      # [M, count] past the output cap
    assert not ps.eligible(ps.MAX_M + 1, 1)  # ids past the SMEM cap


def test_eligible2_budgets():
    ps = pallas_sampling
    assert ps.eligible2(512, 10, 10)            # the PPI recipe fanout
    assert ps.eligible2(1000, 4, 4, k1=4, k2=4)  # reddit recipe, wide slabs
    assert not ps.eligible2(512, ps.MAX_F1 + 1, 4)
    assert not ps.eligible2(512, 4, ps.MAX_COUNT + 1)
    assert ps.eligible2(10485, 10, 10)          # hop-2 output at its cap
    assert not ps.eligible2(10486, 10, 10)
    # both hop outputs are whole in VMEM at 512 B a row: m * (1 + f1) rows
    assert ps.eligible2(ps.MAX_M, 3, 8)
    assert not ps.eligible2(ps.MAX_M, 4, 8)
    # a hop-2 stage at the MINIMUM stage (8 rows) must fit its pick
    # budget — f1 * f2 * k2 <= 512 — else the kernel would fail VMEM
    # allocation at compile time instead of falling back
    assert ps.eligible2(128, 32, 16) and not ps.eligible2(128, 32, 17)
    assert ps.eligible2(128, 8, 16, k1=1, k2=4)
    assert not ps.eligible2(128, 16, 16, k1=1, k2=4)


def test_pack_adjacency_hbm_budget():
    small = {
        "nbr": np.zeros((100, 8), np.int32),
        "cum": np.ones((100, 8), np.float32),
    }
    assert pallas_sampling.pack_adjacency(small) is not None
    # past the budget (this slab packs to exactly 100 KiB) — refused;
    # at the default 2 GB cap that's the 10M-node-graph case
    assert (
        pallas_sampling.pack_adjacency(small, max_bytes=100 * 1024 - 1)
        is None
    )
    # W=200 packs as K=2 (test_packed_layout_k_boundaries); only
    # W > MAX_W refuses (test_packed_layout_refuses_past_max_width)


def test_packed_layout_refuses_past_max_width():
    """Wider than MAX_W keeps the XLA path (layout coverage for every
    supported K lives in test_packed_layout_k_boundaries)."""
    ps = pallas_sampling
    too_wide = {
        "nbr": np.zeros((4, ps.MAX_W + 1), np.int32),
        "cum": np.ones((4, ps.MAX_W + 1), np.float32),
    }
    assert ps.pack_adjacency(too_wide) is None


@pytest.mark.parametrize("w,k", [(129, 2), (200, 2), (300, 3), (512, 4)])
def test_packed_layout_k_boundaries(w, k):
    """Every K the kernel supports (up to MAX_W/128 = 4), including the
    one-past-a-register width 129: node-major [K nbr rows, K cum rows]
    blocks with exact pad semantics (pure host numpy, runs
    everywhere)."""
    ps = pallas_sampling
    rng = np.random.default_rng(w)
    n = 6
    nbr = rng.integers(0, n, (n, w)).astype(np.int32)
    cum = np.sort(rng.random((n, w)).astype(np.float32), axis=1)
    cum[:, -1] = 1.0
    packed = ps.pack_adjacency({"nbr": nbr, "cum": cum})
    assert packed is not None and packed.shape == (2 * k * n, ps.LANES)
    blk = packed.reshape(n, 2 * k, ps.LANES)
    got_nbr = blk[:, :k].reshape(n, k * ps.LANES)
    got_cum = blk[:, k:].reshape(n, k * ps.LANES).view(np.float32)
    np.testing.assert_array_equal(got_nbr[:, :w], nbr)
    np.testing.assert_array_equal(got_cum[:, :w], cum)
    assert (got_cum[:, w:] == 1.0).all()
    assert (got_nbr[:, w:] == n - 1).all()


def test_pack_bakes_unsampleable_rows_to_default():
    """Zero-weight (unsampleable) rows default-fill their neighbor lanes
    at pack time — the kernel's replacement for the host path's
    `sampleable` mask — while sampleable rows keep their ids (pure host
    numpy, runs everywhere)."""
    ps = pallas_sampling
    n, w = 6, 4
    nbr = np.arange(n * w, dtype=np.int32).reshape(n, w)
    cum = np.tile(np.linspace(0.25, 1.0, w, dtype=np.float32), (n, 1))
    ok = np.array([True, False, True, True, False, True])
    packed = ps.pack_adjacency({"nbr": nbr, "cum": cum, "sampleable": ok})
    blk = packed.reshape(n, 2, ps.LANES)
    for i in range(n):
        if ok[i]:
            np.testing.assert_array_equal(blk[i, 0, :w], nbr[i])
        else:
            assert (blk[i, 0] == n - 1).all()  # every lane -> default id


def test_force_env_still_requires_tpu_backend(monkeypatch):
    """EULER_TPU_PALLAS_SAMPLING=1 must not activate the kernel where its
    TPU-only primitives cannot run (this suite's backend is CPU)."""
    monkeypatch.setenv("EULER_TPU_PALLAS_SAMPLING", "1")
    if jax.default_backend() != "tpu":
        assert not pallas_sampling.available()
    monkeypatch.setenv("EULER_TPU_PALLAS_SAMPLING", "0")
    assert not pallas_sampling.available()


def test_force_env_parsed_strictly(monkeypatch):
    """Only 0/1/false/true (case-insensitive) are honored; anything else
    warns and counts as unset instead of silently force-enabling."""
    for raw, want in [
        ("1", True), ("true", True), ("TRUE", True),
        ("0", False), ("false", False), ("False", False), (" FALSE ", False),
    ]:
        monkeypatch.setenv("EULER_TPU_PALLAS_SAMPLING", raw)
        assert pallas_sampling._force_flag() is want, raw
    monkeypatch.delenv("EULER_TPU_PALLAS_SAMPLING", raising=False)
    assert pallas_sampling._force_flag() is None
    monkeypatch.setenv("EULER_TPU_PALLAS_SAMPLING", "")
    assert pallas_sampling._force_flag() is None
    for bad in ("off", "no", "yes", "2"):
        monkeypatch.setenv("EULER_TPU_PALLAS_SAMPLING", bad)
        with pytest.warns(UserWarning, match="not one of 0/1/false/true"):
            assert pallas_sampling._force_flag() is None


# ---- SPMD wiring (shard_map path; CPU-executable via draw_fn) ----

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices (CPU conftest mesh); on the single-chip "
    "TPU run these would test a 1-device mesh — vacuous for "
    "decorrelation, wrong for the divisibility fallback",
)


@multi_device
def test_force_env_multi_device_needs_kernel_mesh(monkeypatch):
    """Force=1 on a multi-device backend is honored only once a kernel
    mesh is registered: without one the direct route would run an
    unsharded pallas_call under pjit (silently wrong per-shard draws),
    so available() warns and stays False (code-review r4)."""
    from jax.sharding import Mesh

    from euler_tpu.graph import device as dg

    monkeypatch.setenv("EULER_TPU_PALLAS_SAMPLING", "1")
    monkeypatch.setattr(
        pallas_sampling, "_backend_ok", lambda require_single_device: True
    )
    assert dg.kernel_mesh() is None
    with pytest.warns(UserWarning, match="no kernel mesh"):
        assert not pallas_sampling.available()
    dg.set_kernel_mesh(Mesh(np.array(jax.devices()[:4]), ("data",)), "data")
    try:
        assert pallas_sampling.available()
    finally:
        dg.set_kernel_mesh(None)


def _xla_draw(adj_l, nodes_l, seed, count):
    """XLA stand-in with the kernel's exact call signature
    (adj, nodes, seed[2], count) — lets the shard_map wiring run on CPU
    meshes where the kernel's TPU primitives cannot."""
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed[0])
    nodes = jnp.asarray(nodes_l, jnp.int32)
    n_rows = adj_l["nbr"].shape[0]
    nodes = jnp.where(nodes < 0, n_rows - 1, jnp.minimum(nodes, n_rows - 1))
    cum = adj_l["cum"][nodes]
    u = jax.random.uniform(key, (*nodes.shape, count))
    idx = (u[..., None] >= cum[..., None, :]).sum(-1)
    idx = jnp.clip(idx, 0, adj_l["nbr"].shape[1] - 1)
    out = jnp.take_along_axis(adj_l["nbr"][nodes], idx, axis=-1)
    return jnp.where(
        adj_l["sampleable"][nodes][..., None], out, n_rows - 1
    )


@multi_device
def test_sharded_draw_wiring_distribution(graph, adj):
    """sample_neighbor_sharded on a 4-device mesh (XLA stand-in body):
    batch-sharded nodes, replicated adjacency, per-source draw
    frequencies match the host engine's weights — proving the shard_map
    specs and the reshape round-trip. (The module's graph/adj fixtures
    build on any backend; only the kernel-executing tests are
    TPU-gated.)"""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    g = graph
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    ids = np.arange(MAX_ID + 1)
    nodes = jnp.asarray(np.tile(ids, 4), jnp.int32)  # 68 rows -> 17/shard
    draws = 64

    f = jax.jit(
        lambda n, s: pallas_sampling.sample_neighbor_sharded(
            adj, n, s, draws, mesh, "data", draw_fn=_xla_draw
        )
    )
    out = np.concatenate(
        [np.asarray(f(nodes, jnp.asarray([c, c + 1]))) for c in range(16)],
        axis=1,
    )
    assert out.shape == (len(nodes), 16 * draws)
    nb, w, _, cnt = g.get_full_neighbor(ids, [0, 1])
    per_node = out.reshape(4, len(ids), -1).transpose(1, 0, 2).reshape(
        len(ids), -1
    )
    total = per_node.shape[1]
    off = 0
    for i, c in enumerate(cnt):
        c = int(c)
        nbrs, ws = nb[off:off + c], w[off:off + c]
        off += c
        if c == 0 or ws.sum() <= 0:
            assert (per_node[i] == MAX_ID + 1).all()
            continue
        expect = ws / ws.sum()
        for n_, p in zip(nbrs, expect):
            freq = (per_node[i] == n_).mean()
            assert abs(freq - p) < 6 * np.sqrt(p * (1 - p) / total) + 1e-3


@multi_device
def test_sharded_draw_decorrelates_shards(adj):
    """The same node replicated across the whole batch must NOT draw
    identical sequences on every shard — axis_index folds into the
    per-shard seed."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    nodes = jnp.full((64,), 10, jnp.int32)  # node with >1 neighbor
    out = np.asarray(
        pallas_sampling.sample_neighbor_sharded(
            adj, nodes, jnp.asarray([7, 8]), 32, mesh, "data",
            draw_fn=_xla_draw,
        )
    ).reshape(4, 16, 32)
    assert not (out[0] == out[1]).all()
    assert not (out[0] == out[2]).all()


@multi_device
def test_kernel_mesh_routing(adj, monkeypatch):
    """device.sample_neighbor routes through the sharded path when a
    kernel mesh is registered and the local draw is eligible, and falls
    back to the XLA chain when the batch does not divide the axis."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from euler_tpu.graph import device as dg

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    calls = []

    def fake_sharded(adj_, nodes, seed, count, mesh_, axis, draw_fn=None):
        calls.append((int(np.prod(nodes.shape)), count, axis))
        return jnp.zeros((*nodes.shape, count), jnp.int32)

    monkeypatch.setattr(
        pallas_sampling, "sample_neighbor_sharded", fake_sharded
    )
    dg.set_kernel_mesh(mesh, "data")
    try:
        out = dg.sample_neighbor(
            adj, jnp.zeros((8,), jnp.int32), jax.random.PRNGKey(0), 5
        )
        assert out.shape == (8, 5) and calls == [(8, 5, "data")]
        # 7 rows do not divide 4 shards -> XLA fallback, no sharded call
        out = dg.sample_neighbor(
            adj, jnp.zeros((7,), jnp.int32), jax.random.PRNGKey(0), 5
        )
        assert out.shape == (7, 5) and len(calls) == 1
    finally:
        dg.set_kernel_mesh(None)


def test_packed_consts_without_mesh_take_xla_chain_when_unavailable(
    monkeypatch,
):
    """Consts can carry a packed slab while available() is False (e.g.
    set_kernel_mesh(None) on a multi-device backend, or
    EULER_TPU_PALLAS_SAMPLING=0 set after packing): the direct-kernel
    branch must NOT fire — the unsharded pallas_call under pjit is the
    composition the module's SPMD note warns about (ADVICE r3)."""
    import jax.numpy as jnp

    from euler_tpu.graph import device as dg

    n, w = 4, 3
    nbr = np.tile(np.arange(1, w + 1, dtype=np.int32), (n, 1))
    cum = np.tile(
        np.array([0.25, 0.5, 1.0], np.float32), (n, 1)
    )
    adj = {
        "nbr": jnp.asarray(nbr),
        "cum": jnp.asarray(cum),
        "sampleable": jnp.ones((n,), bool),
        "packed": jnp.asarray(
            pallas_sampling.pack_adjacency({"nbr": nbr, "cum": cum})
        ),
    }
    kernel_calls = []
    monkeypatch.setattr(
        pallas_sampling,
        "sample_neighbor",
        lambda *a, **kw: kernel_calls.append(a) or None,
    )
    assert dg.kernel_mesh() is None
    monkeypatch.setattr(pallas_sampling, "available", lambda: False)
    out = dg.sample_neighbor(
        adj, jnp.zeros((5,), jnp.int32), jax.random.PRNGKey(0), 6
    )
    assert out.shape == (5, 6) and not kernel_calls  # XLA chain taken
    # converse: available() True routes the eligible draw to the kernel
    monkeypatch.setattr(pallas_sampling, "available", lambda: True)
    out = dg.sample_neighbor(
        adj, jnp.zeros((5,), jnp.int32), jax.random.PRNGKey(0), 6
    )
    assert kernel_calls and out is None  # the fake kernel was called


@multi_device
def test_kernel_mesh_scope_registers_and_restores(monkeypatch):
    """kernel_mesh_scope registers a multi-device mesh only where the
    per-shard kernel can run, CLEARS a stale registration otherwise, and
    always restores what it found — so nothing an outer or earlier
    caller left can route this block's draws."""
    from jax.sharding import Mesh

    from euler_tpu.graph import device as dg

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    stale = Mesh(np.array(jax.devices()[:2]), ("data",))
    dg.set_kernel_mesh(stale, "data")
    try:
        monkeypatch.setattr(
            pallas_sampling, "sharded_available", lambda: False
        )
        with dg.kernel_mesh_scope(mesh):  # no kernel to shard: cleared
            assert dg.kernel_mesh() is None
        assert dg.kernel_mesh() == (stale, "data")
        monkeypatch.setattr(pallas_sampling, "sharded_available", lambda: True)
        with dg.kernel_mesh_scope(mesh):
            assert dg.kernel_mesh() == (mesh, "data")
            one = Mesh(np.array(jax.devices()[:1]), ("data",))
            with dg.kernel_mesh_scope(one):  # one device: direct call
                assert dg.kernel_mesh() is None
            assert dg.kernel_mesh() == (mesh, "data")
        assert dg.kernel_mesh() == (stale, "data")
    finally:
        dg.set_kernel_mesh(None)


@multi_device
def test_trainer_entry_points_scope_their_mesh(monkeypatch):
    """train()/evaluate()/save_embedding() called directly (the
    examples, the benchmark) register their mesh like run_loop.main does —
    a multi-chip run no longer takes the XLA chain for want of a
    registration — and default the mesh to every device."""
    from euler_tpu import train as train_lib
    from euler_tpu.graph import device as dg

    monkeypatch.setattr(pallas_sampling, "sharded_available", lambda: True)
    for fn in (train_lib.train, train_lib.evaluate, train_lib.save_embedding):
        assert fn.__wrapped__  # all three are scoped

    @train_lib._kernel_mesh_scoped
    def probe(model, mesh=None):
        return dg.kernel_mesh(), mesh

    registered, mesh = probe("m")
    assert mesh.size == len(jax.devices()) and registered == (mesh, "data")
    assert dg.kernel_mesh() is None


def test_draw_route_is_logged_once_with_its_reason(adj, caplog):
    """Which path a draw took, and why, is said at trace time: a slab
    that was never packed or a fanout the chained kernel cannot take is
    visible in the log, not silent."""
    import logging

    import jax.numpy as jnp

    from euler_tpu.graph import device as dg

    plain = {k: v for k, v in adj.items() if k != "packed"}
    roots = jnp.zeros((6,), jnp.int32)
    dg._log_route.cache_clear()
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        dg.sample_fanout([plain] * 3, roots, jax.random.PRNGKey(0), [2, 2, 2])
        dg.sample_fanout([plain] * 3, roots, jax.random.PRNGKey(1), [2, 2, 2])
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("draw path")]
    assert lines == [
        "draw path: fanout 6x2x2x2 -> per-hop draws (3 hops (the chained "
        "kernel fuses 2))",
        "draw path: neighbor draw 6x2 -> XLA draw chain (adjacency has no "
        "packed slab)",
        "draw path: neighbor draw 12x2 -> XLA draw chain (adjacency has no "
        "packed slab)",
        "draw path: neighbor draw 24x2 -> XLA draw chain (adjacency has no "
        "packed slab)",
    ]
    if jax.default_backend() != "tpu":
        # a packed slab with no kernel to run it says so too
        caplog.clear()
        with caplog.at_level(logging.INFO, logger="euler_tpu"):
            dg.sample_neighbor(adj, roots, jax.random.PRNGKey(0), 3)
        (line,) = [r.getMessage() for r in caplog.records]
        assert "XLA draw chain (backend cpu" in line
        assert "no kernel mesh registered" in line


# ---- kernel tests (single-device TPU only) ----


@pytest.fixture(scope="module")
def graph(tmp_path_factory):
    import euler_tpu
    from tests.fixture_graph import write_fixture

    d = tmp_path_factory.mktemp("pallas_graph")
    write_fixture(str(d))
    return euler_tpu.Graph(directory=str(d))


@pytest.fixture(scope="module")
def adj(graph):
    from euler_tpu.graph import device as dg

    a = dg.build_adjacency(graph, [0, 1], MAX_ID)
    packed = pallas_sampling.pack_adjacency(a)
    assert packed is not None
    a["packed"] = packed
    return jax.device_put({k: jax.numpy.asarray(v) for k, v in a.items()})


@tpu_only
def test_packed_layout_roundtrip(adj):
    packed = np.asarray(adj["packed"])
    nbr = np.asarray(adj["nbr"])
    cum = np.asarray(adj["cum"])
    n, w = nbr.shape
    assert packed.shape == (2 * n, pallas_sampling.LANES)
    # unsampleable rows bake the default-node fill into the slab
    ok = np.asarray(adj["sampleable"]).astype(bool)
    np.testing.assert_array_equal(
        packed[0::2, :w], np.where(ok[:, None], nbr, n - 1)
    )
    np.testing.assert_array_equal(
        packed[1::2, :w].view(np.float32), cum
    )
    # pad lanes: unreachable (cum=1.0) and default-id filled
    assert (packed[1::2, w:].view(np.float32) == 1.0).all()
    assert (packed[0::2, w:] == n - 1).all()


@tpu_only
def test_shapes_and_default_fill(adj, graph):
    import jax.numpy as jnp

    from euler_tpu.graph import device as dg

    default = int(adj["nbr"].shape[0] - 1)
    # the default row must draw itself; real nodes must draw in-graph
    nodes = jnp.asarray([0, 1, default], jnp.int32)
    out = jax.jit(
        lambda n, k: dg.sample_neighbor(adj, n, k, 7)
    )(nodes, jax.random.PRNGKey(0))
    assert out.shape == (3, 7)
    assert (np.asarray(out[2]) == default).all()
    assert (np.asarray(out[:2]) <= default).all()


@tpu_only
def test_oob_ids_and_empty_input(adj):
    """Out-of-range ids must clamp to the default row (the XLA path's
    OOB-gather behavior) — in the kernel they are raw DMA offsets — and
    an empty node set must return an empty array, not start unawaited
    prologue DMAs."""
    import jax.numpy as jnp

    from euler_tpu.graph import device as dg

    default = int(adj["nbr"].shape[0] - 1)
    nodes = jnp.asarray([default + 1, default + 1000, -3], jnp.int32)
    out = jax.jit(
        lambda n, k: dg.sample_neighbor(adj, n, k, 5)
    )(nodes, jax.random.PRNGKey(1))
    # rows past the slab AND negative ids both land on the default row
    # (build_adjacency's "unknown ids sample the default node" contract;
    # the XLA path's numpy-style wrap sends -1 there too)
    assert (np.asarray(out) == default).all()

    empty = jax.jit(
        lambda n, k: dg.sample_neighbor(adj, n, k, 5)
    )(jnp.zeros((0,), jnp.int32), jax.random.PRNGKey(2))
    assert empty.shape == (0, 5)


@tpu_only
def test_distribution_matches_host_engine(adj, graph):
    """Empirical draw frequencies ≈ the host engine's normalized edge
    weights for every fixture node (the same gate the XLA path passes in
    tests/test_device_graph.py)."""
    import jax.numpy as jnp

    from euler_tpu.graph import device as dg

    ids = np.arange(MAX_ID + 1)
    nb, w, _, cnt = graph.get_full_neighbor(ids, [0, 1])
    per_call, calls = 128, 32          # kernel caps count at MAX_COUNT;
    draws = per_call * calls           # accumulate over folded keys
    f = jax.jit(lambda n, k: dg.sample_neighbor(adj, n, k, per_call))
    key = jax.random.PRNGKey(7)
    out = np.concatenate(
        [
            np.asarray(f(jnp.asarray(ids, jnp.int32),
                         jax.random.fold_in(key, c)))
            for c in range(calls)
        ],
        axis=1,
    )
    off = 0
    for i, c in enumerate(cnt):
        c = int(c)
        nbrs, ws = nb[off:off + c], w[off:off + c]
        off += c
        if c == 0 or ws.sum() <= 0:
            assert (out[i] == MAX_ID + 1).all()
            continue
        expect = ws / ws.sum()
        for n_, p in zip(nbrs, expect):
            freq = (out[i] == n_).mean()
            assert abs(freq - p) < 6 * np.sqrt(p * (1 - p) / draws) + 1e-3


@tpu_only
def test_wide_slab_draws_cross_register_boundary():
    """A W=200 (K=2) slab whose mass sits at slots 5 and 150 — one in
    each 128-lane register — must draw exactly those neighbors at their
    weights, proving the rank sum and the per-register select compose
    across the boundary."""
    import jax.numpy as jnp

    ps = pallas_sampling
    n, w = 8, 200
    nbr = np.tile(np.arange(w, dtype=np.int32), (n, 1)) + 1000
    cum = np.zeros((n, w), np.float32)
    cum[:, 5:150] = 0.3
    cum[:, 150:] = 1.0
    adj = {
        "nbr": jnp.asarray(nbr),
        "cum": jnp.asarray(cum),
        "sampleable": jnp.ones((n,), bool),
        "packed": jnp.asarray(
            ps.pack_adjacency({"nbr": nbr, "cum": cum})
        ),
    }
    draws = 128
    out = np.concatenate(
        [
            np.asarray(
                ps.sample_neighbor(
                    adj, jnp.arange(n, dtype=jnp.int32),
                    jnp.int32(seed), draws,
                )
            )
            for seed in range(16)
        ],
        axis=1,
    )
    vals, counts = np.unique(out, return_counts=True)
    assert set(vals) == {1005, 1150}, vals
    p150 = counts[vals == 1150][0] / out.size
    assert abs(p150 - 0.7) < 6 * np.sqrt(0.7 * 0.3 / out.size) + 1e-3


@tpu_only
def test_sharded_kernel_executes_on_hardware(adj, graph):
    """The REAL kernel inside shard_map on the chip (a 1-device mesh —
    the single-chip environment's honest version of the SPMD path; the
    wiring across >1 shard is pinned by the CPU tests above). Draw
    frequencies must match the host engine like the direct-call test."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    ids = np.arange(MAX_ID + 1)
    nodes = jnp.asarray(ids, jnp.int32)
    per_call, calls = 128, 16
    f = jax.jit(
        lambda n, s: pallas_sampling.sample_neighbor_sharded(
            adj, n, s, per_call, mesh, "data"
        )
    )
    out = np.concatenate(
        [np.asarray(f(nodes, jnp.asarray([c, c + 9]))) for c in range(calls)],
        axis=1,
    )
    nb, w, _, cnt = graph.get_full_neighbor(ids, [0, 1])
    total = per_call * calls
    off = 0
    for i, c in enumerate(cnt):
        c = int(c)
        nbrs, ws = nb[off:off + c], w[off:off + c]
        off += c
        if c == 0 or ws.sum() <= 0:
            assert (out[i] == MAX_ID + 1).all()
            continue
        expect = ws / ws.sum()
        for n_, p in zip(nbrs, expect):
            freq = (out[i] == n_).mean()
            assert abs(freq - p) < 6 * np.sqrt(p * (1 - p) / total) + 1e-3


@tpu_only
def test_fanout_routes_through_kernel_and_trains(adj, graph):
    """sample_fanout picks up the packed slab, and a device-sampling
    GraphSAGE step using it still descends."""
    import jax.numpy as jnp
    import optax

    from euler_tpu.graph import device as dg
    from euler_tpu.models import SupervisedGraphSage

    roots = jnp.asarray(graph.sample_node(8, -1), jnp.int32)
    hops = jax.jit(
        lambda r, k: dg.sample_fanout([adj, adj], r, k, [3, 2])
    )(roots, jax.random.PRNGKey(3))
    assert [h.shape[0] for h in hops] == [8, 24, 48]

    model = SupervisedGraphSage(
        label_idx=0, label_dim=4, metapath=[[0, 1]] * 2, fanouts=[3, 2],
        dim=16, feature_idx=0, feature_dim=2, max_id=MAX_ID,
        device_features=True, device_sampling=True,
    )
    opt = optax.adam(0.05)
    state = model.init_state(
        jax.random.PRNGKey(0), graph, graph.sample_node(8, -1), opt
    )
    assert any(
        "packed" in a for a in state["consts"]["adj"].values()
    ), "available() TPU run must pack the slabs"
    step = jax.jit(model.make_train_step(opt), donate_argnums=(0,))
    losses = []
    for i in range(30):
        batch = model.device_sample_batch(graph.sample_node(8, -1))
        state, loss, _ = step(state, batch)
        losses.append(float(loss))
    assert np.mean(losses[-10:]) < np.mean(losses[:10])


@tpu_only
def test_chained_fanout_distribution_matches_host_engine(adj, graph):
    """sample_fanout2 on the chip: hop-1 marginals match the host
    engine's normalized weights, and hop-2 draws grouped by their
    ACTUAL hop-1 source match that source's distribution — the
    conditional check the chained kernel's data-dependent DMAs must
    get right (reference: two chained CompactNode::SampleNeighbor
    rounds, euler/core/compact_node.cc:42-101)."""
    import jax.numpy as jnp

    ids = np.arange(MAX_ID + 1)
    nb, w, _, cnt = graph.get_full_neighbor(ids, [0, 1])
    weights = {}
    off = 0
    for i, c in enumerate(cnt):
        c = int(c)
        nbrs, ws = nb[off:off + c], w[off:off + c]
        off += c
        if c and ws.sum() > 0:
            weights[i] = dict(zip(nbrs, ws / ws.sum()))
    f1, f2, calls = 16, 16, 24
    f = jax.jit(
        lambda r, s: pallas_sampling.sample_fanout2(
            adj, adj, r, s, f1, f2
        )
    )
    roots = jnp.asarray(ids, jnp.int32)
    h1_all, pairs = [], []          # pairs: (hop-2 source id, drawn id)
    for c in range(calls):
        h1, h2 = f(roots, jnp.asarray([c, 5 * c + 1]))
        h1, h2 = np.asarray(h1), np.asarray(h2)
        h1_all.append(h1)
        pairs.append(
            np.stack(
                [np.repeat(h1.reshape(-1), f2), h2.reshape(-1)], axis=1
            )
        )
    h1_all = np.concatenate(h1_all, axis=1)     # [n_ids, calls*f1]
    total1 = h1_all.shape[1]
    for i in range(len(ids)):
        if i not in weights:
            assert (h1_all[i] == MAX_ID + 1).all()
            continue
        for n_, p in weights[i].items():
            freq = (h1_all[i] == n_).mean()
            assert abs(freq - p) < 6 * np.sqrt(p * (1 - p) / total1) + 1e-3
    pairs = np.concatenate(pairs, axis=0)
    for i, dist in weights.items():
        drawn = pairs[pairs[:, 0] == i][:, 1]
        if len(drawn) < 512:        # too few hop-1 visits to pin
            continue
        for n_, p in dist.items():
            freq = (drawn == n_).mean()
            assert abs(freq - p) < 6 * np.sqrt(p * (1 - p) / len(drawn)) + 2e-3
    # every hop-2 row whose source is the default node stays default
    dflt = pairs[pairs[:, 0] == MAX_ID + 1][:, 1]
    assert len(dflt) and (dflt == MAX_ID + 1).all()


@tpu_only
def test_chained_sharded_kernel_executes_on_hardware(adj, graph):
    """The chained kernel inside shard_map on the chip (1-device mesh,
    like test_sharded_kernel_executes_on_hardware): shapes, in-graph
    picks, and hop-1 marginals for one well-connected node."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    roots = jnp.full((32,), 10, jnp.int32)
    f = jax.jit(
        lambda r, s: pallas_sampling.sample_fanout2_sharded(
            adj, adj, r, s, 8, 4, mesh, "data"
        )
    )
    h1, h2 = f(roots, jnp.asarray([3, 11]))
    assert h1.shape == (32, 8) and h2.shape == (256, 4)
    assert (np.asarray(h1) <= MAX_ID + 1).all()
    assert (np.asarray(h2) <= MAX_ID + 1).all()
    nb, w, _, cnt = graph.get_full_neighbor(np.array([10]), [0, 1])
    expect = dict(zip(nb[: int(cnt[0])], w[: int(cnt[0])]))
    total = sum(expect.values())
    draws = np.concatenate(
        [np.asarray(f(roots, jnp.asarray([c, c]))[0]).reshape(-1)
         for c in range(8)]
    )
    for n_, ww in expect.items():
        p = ww / total
        freq = (draws == n_).mean()
        assert abs(freq - p) < 6 * np.sqrt(p * (1 - p) / len(draws)) + 1e-3


def _synthetic_adj(n, w):
    """[n, w] slab whose node i draws uniformly among ids (i+1..i+w) % n;
    the last row is the default row (draws itself)."""
    import jax.numpy as jnp

    nbr = (np.arange(n)[:, None] + 1 + np.arange(w)[None, :]) % (n - 1)
    nbr = nbr.astype(np.int32)
    cum = np.tile(
        (np.arange(1, w + 1, dtype=np.float32) / w)[None, :], (n, 1)
    )
    cum[:, -1] = 1.0
    nbr[-1] = n - 1
    return {
        "nbr": jnp.asarray(nbr),
        "cum": jnp.asarray(cum),
        "sampleable": jnp.ones((n,), bool),
        "packed": jnp.asarray(
            pallas_sampling.pack_adjacency({"nbr": nbr, "cum": cum})
        ),
    }


@tpu_only
@pytest.mark.parametrize("m,count,w", [
    (pallas_sampling.MAX_M, 32, 16),      # MAX_M rows at MAX_OUT_ELEMS
    (8192, pallas_sampling.MAX_COUNT, 16),  # MAX_COUNT at MAX_OUT_ELEMS
    (pallas_sampling.MAX_M, 1, 16),       # a walk step: one lane per row
    (8192, 32, pallas_sampling.MAX_W),    # widest slab (K=4)
])
def test_eligible_corners_compile(m, count, w):
    """Every corner eligible() admits must be a shape Mosaic accepts:
    the bound is a promise that routing to the kernel compiles."""
    import jax.numpy as jnp

    ps = pallas_sampling
    assert ps.eligible(m, count)
    n = 2048
    adj = _synthetic_adj(n, w)
    nodes = jnp.asarray(np.arange(m) % n, jnp.int32)
    out = np.asarray(
        jax.jit(lambda a, x: ps.sample_neighbor(a, x, jnp.int32(3), count))(
            adj, nodes
        )
    )
    assert out.shape == (m, count)
    assert out.min() >= 0 and out.max() <= n - 1
    assert (out[np.asarray(nodes) == n - 1] == n - 1).all()


@tpu_only
@pytest.mark.parametrize("m,f1,f2,w1,w2", [
    (512, 10, 10, 32, 32),      # the PPI recipe
    (1000, 4, 4, 60, 60),       # the Reddit recipe (synthetic slab width)
    (1000, 4, 4, 512, 512),     # the Reddit recipe over the widest slabs
    (10485, 10, 10, 16, 16),    # hop-2 output at MAX_OUT_ELEMS
    (pallas_sampling.MAX_M, 3, 8, 16, 16),  # MAX_M roots at MAX_OUT_ROWS2
    (26214, 4, 10, 16, 16),     # MAX_OUT_ELEMS and MAX_OUT_ROWS2 at once
    (2048, 32, 16, 16, 16),     # MAX_F1 and stage picks at MAX_OUT_ELEMS
    (2048, 4, 128, 16, 16),     # MAX_COUNT draws in hop 2
    (1024, 8, 16, 16, 512),     # stage picks at the bound over K2 = 4
    (1024, 32, 4, 512, 512),    # a 256-row hop-2 stage over K = 4 slabs
])
def test_eligible2_corners_compile(m, f1, f2, w1, w2):
    """Same promise for the chained kernel, at the recipe shapes and at
    every budget eligible2() checks."""
    import jax.numpy as jnp

    ps = pallas_sampling
    k1, k2 = -(-w1 // ps.LANES), -(-w2 // ps.LANES)
    assert ps.eligible2(m, f1, f2, k1, k2)
    n = 2048
    a1, a2 = _synthetic_adj(n, w1), _synthetic_adj(n, w2)
    roots = jnp.asarray(np.arange(m) % n, jnp.int32)
    h1, h2 = jax.jit(
        lambda x, y, r: ps.sample_fanout2(
            x, y, r, jnp.asarray([5, 7]), f1, f2
        )
    )(a1, a2, roots)
    h1, h2 = np.asarray(h1), np.asarray(h2)
    assert h1.shape == (m, f1) and h2.shape == (m * f1, f2)
    for h in (h1, h2):
        assert h.min() >= 0 and h.max() <= n - 1
    # node i draws among (i+1..i+w) % (n-1): hop 2 really followed hop 1
    src = h1.reshape(-1)[:, None]
    delta = (h2 - src) % (n - 1)
    real = (src != n - 1)[:, 0]
    assert ((delta[real] >= 1) & (delta[real] <= w2)).all()
    assert (h2[~real] == n - 1).all()


@tpu_only
@pytest.mark.parametrize("feature_dim,stored", [(602, 640), (50, 128)])
def test_feature_table_stays_row_major(tmp_path, feature_dim, stored):
    """The guard against the whole-table copy coming back (PERF.md
    section 6, PR 28): in the compiled, donated train step the feature
    table's entry layout is row-major and nothing but parameters (the
    entry's, and those of the fusions that gather from it) has the
    table's shape. At the logical widths the runtime's default layout is
    column-major: 602 cost a 5.35 GB transpose a step, 50 a strided
    gather."""
    import re

    import optax

    import euler_tpu
    from euler_tpu.models import SupervisedGraphSage
    from tests.fixture_graph import write_fixture

    write_fixture(str(tmp_path), num_partitions=2)
    graph = euler_tpu.Graph(directory=str(tmp_path))
    n = 200_000
    model = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[4, 4], dim=64, feature_idx=0, feature_dim=feature_dim,
        max_id=n - 2, device_features=True,
    )
    opt = optax.adam(0.01)
    roots = np.resize(np.arange(10, 17), 1000)
    try:
        state = model.init_state(jax.random.PRNGKey(0), graph, roots, opt)
        batch = model.sample(graph, roots)
    finally:
        graph.close()
    assert state["consts"]["features"].shape == (n, stored)
    text = (
        jax.jit(model.make_train_step(opt), donate_argnums=(0,))
        .lower(state, batch).compile().as_text()
    )
    shape = re.escape("f32[%d,%d]" % (n, stored))
    made = re.findall(
        r"^\s*(?:ROOT )?%\S+ = " + shape + r"\{([\d,]+)[^}]*\} (\S+?)\(",
        text, re.MULTILINE,
    )
    assert made, "the feature table is not in the compiled step"
    assert {op for _, op in made} == {"parameter"}, made
    assert {layout for layout, _ in made} == {"1,0"}, made
    assert "[%d,%d]" % (n, feature_dim) not in text


# ---- per-shard kernel on a real mesh (TPU host with >= 4 chips) ----


def _assert_matches_host_weights(out, graph, ids, tol=1e-3):
    nb, w, _, cnt = graph.get_full_neighbor(ids, [0, 1])
    total = out.shape[1]
    off = 0
    for i, c in enumerate(cnt):
        c = int(c)
        nbrs, ws = nb[off:off + c], w[off:off + c]
        off += c
        if c == 0 or ws.sum() <= 0:
            assert (out[i] == MAX_ID + 1).all()
            continue
        for n_, p in zip(nbrs, ws / ws.sum()):
            freq = (out[i] == n_).mean()
            assert abs(freq - p) < 6 * np.sqrt(p * (1 - p) / total) + tol


@tpu_mesh
def test_sharded_kernel_on_a_real_mesh(adj, graph):
    """The REAL kernel inside shard_map over four chips: every shard's
    draws match the host engine's weights, and shards draw different
    sequences for the same node (axis_index folded into the seed)."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    ids = np.arange(MAX_ID + 1)
    nodes = jnp.asarray(np.tile(ids, 4), jnp.int32)     # 17 rows per shard
    per_call, calls = 128, 16
    f = jax.jit(
        lambda n, s: pallas_sampling.sample_neighbor_sharded(
            adj, n, s, per_call, mesh, "data"
        )
    )
    out = np.concatenate(
        [np.asarray(f(nodes, jnp.asarray([c, c + 9]))) for c in range(calls)],
        axis=1,
    )
    per_shard = out.reshape(4, len(ids), -1)
    for shard in per_shard:
        _assert_matches_host_weights(shard, graph, ids)
    busy = int(np.argmax([len(set(r.tolist())) for r in per_shard[0]]))
    assert not (per_shard[0][busy] == per_shard[1][busy]).all()
    assert not (per_shard[0][busy] == per_shard[2][busy]).all()


@tpu_mesh
def test_chained_sharded_kernel_on_a_real_mesh(adj, graph):
    """sample_fanout2 per shard over four chips — through
    device.sample_fanout's own routing under a registered kernel mesh,
    on the (data, model) mesh layout run_loop builds."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from euler_tpu.graph import device as dg

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    ids = np.arange(MAX_ID + 1)
    roots = jnp.asarray(np.tile(ids, 2), jnp.int32)     # 17 roots per shard
    f1, f2, calls = 16, 16, 24
    with dg.kernel_mesh_scope(mesh):
        assert dg.kernel_mesh() == (mesh, "data")
        f = jax.jit(
            lambda r, k: dg.sample_fanout([adj, adj], r, k, [f1, f2])
        )
        assert "tpu_custom_call" in f.lower(
            roots, jax.random.PRNGKey(0)
        ).as_text()
        h1s = []
        for c in range(calls):
            r, h1, h2 = f(roots, jax.random.PRNGKey(c))
            assert h1.shape == (len(roots) * f1,)
            assert h2.shape == (len(roots) * f1 * f2,)
            assert int(jnp.max(h2)) <= MAX_ID + 1
            h1s.append(np.asarray(h1).reshape(len(roots), f1))
    h1_all = np.concatenate(h1s, axis=1).reshape(2, len(ids), -1)
    for shard in h1_all:
        _assert_matches_host_weights(shard, graph, ids)
