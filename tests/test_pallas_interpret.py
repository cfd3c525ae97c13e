"""CPU execution of the Pallas sampling kernels via TPU interpret mode.

EULER_TPU_PALLAS_INTERPRET=1 routes pallas_call through pallas' TPU
interpreter (emulated DMAs/semaphores/SMEM on CPU), which executes the
REAL kernel bodies — the same programs the chip compiles — so layout,
DMA addressing, the cross-register rank/select, the chained hop-2
data-dependent DMAs, and the default/OOB contracts are all validated in
the default suite instead of waiting for hardware. The emulated core
PRNG returns zeros, so these tests inject uniforms (the kernels' ``u``
arguments), which upgrades the distributional TPU tests to EXACT ones:
identical uniforms must reproduce the XLA path's picks bit-for-bit
against the numpy reference below. What interpret mode cannot attest —
the real PRNG stream and performance — stays with the TPU-gated tests
in test_pallas_sampling.py and the bench.

Reference semantics: CompactNode::SampleNeighbor
(euler/core/compact_node.cc:42-101), first slot whose cumulative weight
exceeds u, default node for unsampleable/unknown rows.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from euler_tpu.graph import device as dg
from euler_tpu.graph import pallas_sampling as ps


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("EULER_TPU_PALLAS_INTERPRET", "1")


def ref_pick(adj, nodes, u):
    """The XLA chain's pick semantics in plain numpy float32 — the
    oracle both kernels must match exactly for identical uniforms."""
    nbr = np.asarray(adj["nbr"])
    cum = np.asarray(adj["cum"])
    ok = np.asarray(adj["sampleable"]).astype(bool)
    n = nbr.shape[0]
    default = n - 1
    nodes = np.asarray(nodes)
    nodes = np.where(nodes < 0, default, np.minimum(nodes, default))
    u = np.asarray(u, np.float32)
    idx = (u[..., None] >= cum[nodes][..., None, :]).sum(-1)
    idx = np.clip(idx, 0, nbr.shape[1] - 1)
    out = np.take_along_axis(nbr[nodes], idx, axis=-1)
    return np.where(ok[nodes][..., None], out, default)


def make_adj(n, w, seed, unsampleable=()):
    """Random packed adjacency over n rows (row n-1 = default row,
    self-looped like build_adjacency's output)."""
    rng = np.random.default_rng(seed)
    nbr = rng.integers(0, n, (n, w)).astype(np.int32)
    cum = np.sort(rng.random((n, w)).astype(np.float32), axis=1)
    cum[:, -1] = 1.0
    ok = np.ones(n, bool)
    for i in unsampleable:
        ok[i] = False
        cum[i] = 1.0
    nbr[n - 1] = n - 1  # default row draws itself
    adj = {"nbr": nbr, "cum": cum, "sampleable": ok}
    packed = ps.pack_adjacency(adj)
    assert packed is not None
    adj["packed"] = packed
    return {k: jnp.asarray(v) for k, v in adj.items()}


def test_single_hop_exact_vs_reference(monkeypatch):
    """Multi-stage single-hop kernel (stage size forced to 8 so the
    double-buffered pipeline + tail padding run) with OOB ids and an
    unsampleable row — picks must equal the numpy oracle exactly."""
    monkeypatch.setattr(ps, "_MAX_R", 8)
    adj = make_adj(24, 7, seed=0, unsampleable=(3,))
    rng = np.random.default_rng(1)
    nodes = np.array(
        [0, 1, 3, 23, 22, -4, 30, 5, 6, 7, 8, 9, 10, 11, 2, 12, 13, 14],
        np.int32,
    )  # 18 ids -> 3 stages of 8 with padding
    u = rng.random((len(nodes), 5), dtype=np.float32)
    out = ps.sample_neighbor(
        adj, jnp.asarray(nodes), jnp.asarray([11, 13], jnp.int32), 5, u=u
    )
    np.testing.assert_array_equal(np.asarray(out), ref_pick(adj, nodes, u))


@pytest.mark.parametrize("w", [7, 200], ids=["k1", "k2"])
def test_walk_step_rows_run_as_a_loop_exact_vs_reference(monkeypatch, w):
    """A walk step is one draw a row: the kernel then issues and awaits
    a stage's row copies in a loop on the core instead of unrolling them
    (512 rows a stage at real size). Three stages of 8 with padding, OOB
    ids and an unsampleable row: picks equal the numpy oracle exactly,
    and a five-step chain of such draws walks the oracle's path."""
    monkeypatch.setattr(ps, "_MAX_R", 8)
    adj = make_adj(24, w, seed=4, unsampleable=(3,))
    rng = np.random.default_rng(5)
    nodes = np.array(
        [0, 1, 3, 23, 22, -4, 30, 5, 6, 7, 8, 9, 10, 11, 2, 12, 13, 14],
        np.int32,
    )
    seed = jnp.asarray([11, 13], jnp.int32)
    cur, want = nodes, nodes
    for _ in range(5):
        u = rng.random((len(nodes), 1), dtype=np.float32)
        cur = np.asarray(ps.sample_neighbor(
            adj, jnp.asarray(cur), seed, 1, u=u))[:, 0]
        want = ref_pick(adj, want, u)[:, 0]
        np.testing.assert_array_equal(cur, want)
    # the loop is in the traced kernel: eight copy starts in the
    # prologue, eight in the look-ahead, eight waits, whatever the
    # stage's rows
    text = str(jax.make_jaxpr(lambda n: ps.sample_neighbor(
        adj, n, seed, 1))(jnp.asarray(nodes)))
    assert text.count("dma_start") == 16 and text.count("dma_wait") == 8
    monkeypatch.setattr(ps, "_MAX_R", 64)
    big = jnp.asarray(np.resize(nodes, 200))
    text = str(jax.make_jaxpr(lambda n: ps.sample_neighbor(
        adj, n, seed, 1))(big))
    assert text.count("dma_start") == 16 and text.count("dma_wait") == 8
    text = str(jax.make_jaxpr(lambda n: ps.sample_neighbor(
        adj, n, seed, 2))(big))
    rows = 64 // (1 if w == 7 else 2)      # a stage's rows, unrolled
    assert text.count("dma_start") == 2 * rows
    assert text.count("dma_wait") == rows


def test_single_hop_wide_slab_cross_register(monkeypatch):
    """K=2 slab (W=200): uniforms aimed at lanes on both sides of the
    128-lane register boundary must pick exactly the oracle's lanes."""
    adj = make_adj(10, 200, seed=2)
    nodes = np.arange(10, dtype=np.int32)
    # target low lanes, the boundary neighborhood, and high lanes
    cum = np.asarray(adj["cum"])
    u = np.stack(
        [cum[nodes, 3] - 1e-4, cum[nodes, 126] - 1e-4,
         cum[nodes, 128] - 1e-4, cum[nodes, 190] - 1e-4,
         np.full(10, 0.999, np.float32)],
        axis=1,
    ).astype(np.float32)
    out = ps.sample_neighbor(
        adj, jnp.asarray(nodes), jnp.asarray([5, 6], jnp.int32), 5, u=u
    )
    np.testing.assert_array_equal(np.asarray(out), ref_pick(adj, nodes, u))


def test_chained_two_hop_exact_vs_reference(monkeypatch):
    """The chained kernel's two hops — including the VMEM->SMEM pick
    copy and the data-dependent hop-2 DMAs, across multiple pipelined
    stages — must equal two oracle rounds exactly (heterogeneous
    adjacencies, OOB roots, unsampleable rows on both hops)."""
    monkeypatch.setattr(ps, "_MAX_R", 8)
    adj1 = make_adj(24, 6, seed=3, unsampleable=(5,))
    adj2 = make_adj(24, 9, seed=4, unsampleable=(7,))
    rng = np.random.default_rng(5)
    roots = np.array(
        [0, 5, 7, 23, -1, 40, 1, 2, 3, 4, 6, 8, 9, 10, 11, 12, 13, 14],
        np.int32,
    )  # 18 roots -> 3 stages of 8
    f1, f2 = 3, 2
    u1 = rng.random((len(roots), f1), dtype=np.float32)
    u2 = rng.random((len(roots) * f1, f2), dtype=np.float32)
    h1, h2 = ps.sample_fanout2(
        adj1, adj2, jnp.asarray(roots), jnp.asarray([21, 22], jnp.int32),
        f1, f2, u1=u1, u2=u2,
    )
    want1 = ref_pick(adj1, roots, u1)
    np.testing.assert_array_equal(np.asarray(h1), want1)
    want2 = ref_pick(adj2, want1.reshape(-1), u2)
    np.testing.assert_array_equal(np.asarray(h2), want2)


def test_chained_wide_slabs(monkeypatch):
    """K1=2 x K2=2 chained draw, single stage — the widest packed form
    both hops support together."""
    adj1 = make_adj(8, 160, seed=6)
    adj2 = make_adj(8, 140, seed=7)
    rng = np.random.default_rng(8)
    roots = np.arange(8, dtype=np.int32)
    u1 = rng.random((8, 2), dtype=np.float32)
    u2 = rng.random((16, 3), dtype=np.float32)
    h1, h2 = ps.sample_fanout2(
        adj1, adj2, jnp.asarray(roots), jnp.asarray([1, 2], jnp.int32),
        2, 3, u1=u1, u2=u2,
    )
    want1 = ref_pick(adj1, roots, u1)
    np.testing.assert_array_equal(np.asarray(h1), want1)
    np.testing.assert_array_equal(
        np.asarray(h2), ref_pick(adj2, want1.reshape(-1), u2)
    )


def test_chained_dma_race_detector_clean(monkeypatch):
    """The interpreter's DMA race detector must stay silent across the
    chained kernel's pipelined stages (double-buffered hop-1 rows,
    one-stage-behind hop-2 processing, single SMEM pick buffer)."""
    monkeypatch.setenv("EULER_TPU_PALLAS_INTERPRET", "races")
    monkeypatch.setattr(ps, "_MAX_R", 8)
    adj = make_adj(16, 5, seed=9)
    rng = np.random.default_rng(10)
    roots = np.arange(16, dtype=np.int32)
    u1 = rng.random((16, 2), dtype=np.float32)
    u2 = rng.random((32, 2), dtype=np.float32)
    h1, h2 = ps.sample_fanout2(
        adj, adj, jnp.asarray(roots), jnp.asarray([3, 4], jnp.int32),
        2, 2, u1=u1, u2=u2,
    )
    want1 = ref_pick(adj, roots, u1)
    np.testing.assert_array_equal(np.asarray(h1), want1)
    np.testing.assert_array_equal(
        np.asarray(h2), ref_pick(adj, want1.reshape(-1), u2)
    )


def test_empty_and_mismatched_inputs():
    adj = make_adj(8, 4, seed=11)
    h1, h2 = ps.sample_fanout2(
        adj, adj, jnp.zeros((0,), jnp.int32), jnp.asarray([1, 2]), 3, 2
    )
    assert h1.shape == (0, 3) and h2.shape == (0, 2)
    other = make_adj(9, 4, seed=12)
    with pytest.raises(ValueError, match="one id space"):
        ps.sample_fanout2(
            adj, other, jnp.zeros((4,), jnp.int32), jnp.asarray([1, 2]),
            2, 2,
        )
    with pytest.raises(ValueError, match="both u1 and u2"):
        ps.sample_fanout2(
            adj, adj, jnp.zeros((4,), jnp.int32), jnp.asarray([1, 2]),
            2, 2, u1=np.zeros((4, 2), np.float32),
        )


# ---- routing (no interpretation — fakes record the call) ----


def test_sample_fanout_routes_two_hop_to_chained_kernel(monkeypatch):
    monkeypatch.delenv("EULER_TPU_PALLAS_INTERPRET", raising=False)
    adj = make_adj(12, 4, seed=13)
    calls = []

    def fake(a1, a2, roots, seed, f1, f2):
        calls.append((int(roots.shape[0]), f1, f2))
        return (
            jnp.zeros((roots.shape[0], f1), jnp.int32),
            jnp.zeros((roots.shape[0] * f1, f2), jnp.int32),
        )

    monkeypatch.setattr(ps, "sample_fanout2", fake)
    monkeypatch.setattr(ps, "available", lambda: True)
    # the non-chained fallback loop would route its single-hop draws to
    # the kernel too (available() is forced True) — stub it to keep the
    # fallback XLA-executable on this CPU backend
    monkeypatch.setattr(
        ps,
        "sample_neighbor",
        lambda adj, nodes, seed, count, u=None: jnp.zeros(
            (*np.shape(nodes), count), jnp.int32
        ),
    )
    out = dg.sample_fanout(
        [adj, adj], jnp.arange(6, dtype=jnp.int32), jax.random.PRNGKey(0),
        [3, 2],
    )
    assert calls == [(6, 3, 2)]
    assert [int(np.prod(o.shape)) for o in out] == [6, 18, 36]
    # NOT two hops -> per-hop loop, chained kernel untouched
    dg.sample_fanout(
        [adj, adj, adj], jnp.arange(6, dtype=jnp.int32),
        jax.random.PRNGKey(0), [2, 2, 2],
    )
    assert len(calls) == 1
    # unpacked adjacency -> per-hop loop
    bare = {k: v for k, v in adj.items() if k != "packed"}
    dg.sample_fanout(
        [bare, bare], jnp.arange(6, dtype=jnp.int32),
        jax.random.PRNGKey(0), [3, 2],
    )
    assert len(calls) == 1


def test_sample_fanout_routes_through_mesh_when_registered(monkeypatch):
    if len(jax.devices()) < 4:
        pytest.skip("needs the CPU conftest mesh")
    from jax.sharding import Mesh

    monkeypatch.delenv("EULER_TPU_PALLAS_INTERPRET", raising=False)
    adj = make_adj(12, 4, seed=14)
    calls = []

    def fake_sharded(a1, a2, roots, seed, f1, f2, mesh, axis,
                     draw_fn=None):
        calls.append((int(roots.shape[0]), f1, f2, axis))
        return (
            jnp.zeros((roots.shape[0], f1), jnp.int32),
            jnp.zeros((roots.shape[0] * f1, f2), jnp.int32),
        )

    monkeypatch.setattr(ps, "sample_fanout2_sharded", fake_sharded)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    dg.set_kernel_mesh(mesh, "data")
    try:
        out = dg.sample_fanout(
            [adj, adj], jnp.arange(8, dtype=jnp.int32),
            jax.random.PRNGKey(0), [3, 2],
        )
        assert calls == [(8, 3, 2, "data")]
        assert [int(np.prod(o.shape)) for o in out] == [8, 24, 48]
        # indivisible batch -> per-hop loop (which divides per draw or
        # falls back itself); the chained sharded route must not fire
        dg.sample_fanout(
            [adj, adj], jnp.arange(7, dtype=jnp.int32),
            jax.random.PRNGKey(0), [3, 2],
        )
        assert len(calls) == 1
    finally:
        dg.set_kernel_mesh(None)


def test_chained_sharded_wiring_cpu_mesh():
    """sample_fanout2_sharded's shard_map wiring on the CPU mesh with an
    XLA-executable draw_fn: per-shard seeds decorrelate and shapes
    reassemble (the kernel itself cannot run per-shard on CPU)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs the CPU conftest mesh")
    from jax.sharding import Mesh

    adj = make_adj(12, 4, seed=15)
    seeds = []

    def draw_fn(a1, a2, roots, seed, f1, f2):
        # XLA stand-in: reference-pick via the XLA chain, seed recorded
        # through a shape trick (seed affects nothing here)
        return (
            jnp.broadcast_to(seed[0], (roots.shape[0], f1)).astype(
                jnp.int32
            ),
            jnp.zeros((roots.shape[0] * f1, f2), jnp.int32),
        )

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    h1, h2 = ps.sample_fanout2_sharded(
        adj, adj, jnp.arange(8, dtype=jnp.int32),
        jnp.asarray([5, 6], jnp.int32), 3, 2, mesh, "data",
        draw_fn=draw_fn,
    )
    assert h1.shape == (8, 3) and h2.shape == (24, 2)
    # 4 shards x 2 rows: each shard's folded seed differs
    per_shard = np.asarray(h1).reshape(4, 2, 3)
    assert len({int(s[0, 0]) for s in per_shard}) == 4


def test_interpret_params_parsing(monkeypatch):
    monkeypatch.delenv("EULER_TPU_PALLAS_INTERPRET", raising=False)
    assert ps.interpret_params() is False
    monkeypatch.setenv("EULER_TPU_PALLAS_INTERPRET", "0")
    assert ps.interpret_params() is False
    monkeypatch.setenv("EULER_TPU_PALLAS_INTERPRET", "1")
    p = ps.interpret_params()
    assert p is not False and not p.detect_races
    monkeypatch.setenv("EULER_TPU_PALLAS_INTERPRET", "races")
    assert ps.interpret_params().detect_races
