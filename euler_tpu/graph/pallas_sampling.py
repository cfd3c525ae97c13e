"""Fused weighted-neighbor draw as a Pallas TPU kernel.

The XLA device-sampling path (device.py sample_neighbor) lowers to a
chain of ~6 small ops per hop (row gathers, RNG, compare-sum, pick) and
is latency-bound at GNN batch dims: measured on a v5e chip, the two-hop
PPI fanout (512x10 + 5120x10 draws) costs 0.72 ms/step of the 1.27 ms
train step while the MXU math is ~free (see PERF.md step anatomy). This
kernel fuses the whole per-hop draw into ONE program: the source nodes'
slab rows stream HBM->VMEM through a double-buffered row-DMA pipeline,
the on-core PRNG draws the uniforms, and the compare-sum pick happens on
the rows while the next batch of rows is in flight. Same fanout measured
at 0.24 ms/step — 3x over the XLA chain.

Layout: ``pack_adjacency`` stores each node as 2K adjacent rows of one
``[2KN, 128]`` array — its K neighbor-id rows then its K
cumulative-weight rows (bitcast to int32), K = ceil(W / 128) — so ONE
2K-row DMA fetches the whole node and every row stays aligned to the
(1, 128) HBM tiling that scattered-row slices require (a ``[N, 2K*128]``
array would tile (8, 128) and break scattered-row DMA). Pad slots hold
cum=1.0, which ``idx = #(u >= cum)`` can never select while u < 1 (the
last real slot is pinned to 1.0 at build time), and the VPU compares
each 128-lane row in one op anyway, so the pad is free compute-wise.
Graphs whose slab width exceeds MAX_W = 512 keep the XLA path (cap with
``build_adjacency(..., max_degree=512)`` to opt in — the same
truncate-to-heaviest semantics the reference applies to heavy-tailed
graphs).

Draw semantics are identical to device.sample_neighbor — first slot
whose cumulative weight exceeds u, default node for unsampleable rows
(baked into the slab: their neighbor lanes are default-filled at pack
time, so the kernel needs no mask gather; reference
CompactNode::SampleNeighbor, euler/core/compact_node.cc:42-101) — but
from the core PRNG's stream rather than threefry, so
sequences differ for the same seed while distributions match
(statistically pinned against the host engine in
tests/test_pallas_sampling.py, TPU-only).

SPMD note: pallas_call does not partition under pjit, so the kernel is
called directly only on a single-device TPU (``available()``); on a
mesh it runs per shard inside shard_map once the mesh is registered
(device.kernel_mesh_scope — run_loop.main and train.train do).
EULER_TPU_PALLAS_SAMPLING=0 forces the XLA chain. Which path a draw
took, and why, is logged at trace time (device.py).

Chained two-hop variant: ``sample_fanout2`` fuses BOTH fanout hops into
one program — each stage of root rows draws its hop-1 picks, async-
copies them VMEM->SMEM so they can address HBM, and issues the
data-dependent hop-2 row DMAs, which complete behind the NEXT stage's
hop-1 compute (hop-2 processing runs one stage behind hop-1). This
removes the second kernel dispatch and the hop-1 -> HBM -> hop-2
round-trip of the per-hop path. Folding the FEATURE gather in as well
was evaluated and rejected: a per-row DMA gather of the [B*f1*f2]-row
feature matrix costs ~40 ns of issue per row (~2 ms at PPI dims) vs
~0.49 ms for XLA's gather — see PERF.md.

CPU validation: EULER_TPU_PALLAS_INTERPRET=1 routes every pallas_call
through pallas' TPU interpret mode (emulated DMAs/semaphores on CPU;
=races additionally turns on its DMA race detector). The emulated core
PRNG returns zeros, so interpret-mode tests inject precomputed uniforms
(the ``u``/``u1``/``u2`` arguments) — which also makes them EXACT:
identical uniforms must reproduce the XLA path's picks bit-for-bit
(tests/test_pallas_interpret.py). Hardware runs never inject.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from euler_tpu.telemetry import setup_spanned

LANES = 128
# Kernel budgets. Each is a promise that a shape eligible()/eligible2()
# admits is one Mosaic compiles — checked on the chip at every corner by
# tests/test_pallas_sampling.py::test_eligible*_corners_compile. They
# are EMPIRICAL (TPU v5e, libtpu 0.0.34, PR 21; PERF.md "Kernel
# budgets"): where XLA places a kernel's whole-array VMEM outputs, and
# what else is in VMEM beside them, is not a simple function of their
# bytes. What the chip runs did establish: (1) the outputs are tiled
# (8, 128), 512 B a row whatever the draw count, and 129 MB of them
# exceed a v5e's 128 MB; (2) a stage's draw holds its `count` [rows, 1]
# pick columns live until the final concat, each lane-sparse (one vreg
# per 8 rows), so rows*count — not output bytes — is what fills VMEM
# with spills: a 512-row stage ran out of VMEM at 32 draws and, after a
# 341 s compile, at 128.
MAX_COUNT = 128  # larger per-node draw counts keep the XLA path: the
# count loop is unrolled in the kernel; every model draw (fanouts,
# walks, negatives) is far below this
MAX_OUT_ELEMS = 1 << 20  # [M, count] output cap: bigger draws keep the
# XLA path. Compiled at 2x this (32768 x 64), refused at 4x (32768 x 128)
MAX_M = 1 << 15  # source-node cap: ids ride scalar prefetch (SMEM, far
# smaller than VMEM — 128 KB of ids at this cap), so M needs its own
# bound even when M*count fits the output budget (e.g. count=1 walks)
MAX_W = 4 * LANES  # widest slab the kernel handles (K = ceil(W/128)
# row-pairs per node, compare-sum unrolled over K); wider keeps XLA
MAX_PACKED_BYTES = 2 << 30  # pack_adjacency opt-out: the packed slab is
# always a K*128-lane multiple (1 KB/node per K), a (K*128)/W inflation
# over nbr+cum that it is ADDED to; beyond this budget the kernel is not
# worth the HBM
_MAX_R = 512  # rows per pipeline stage (2 DMA semaphores regardless)
_MAX_STAGE_PICKS = 8192  # rows x count one single-hop stage may draw
# (fact 2); counts <= 16 keep the full 512-row stage
# The chained kernel holds both hops' code and scratch at once and is
# tighter. Refused on the chip: a 512-row hop-2 stage (f1 = 64), a hop-2
# stage of 64 rows x 128 draws, 256 rows x 16 draws over K = 4 slabs,
# 262144 hop-2 rows (129 MB), and hop-2 outputs of 1.19M elements and up
# at several shapes (1.05M compiled at every shape tried).
MAX_F1 = 32  # hop-2 stages are rows*f1 source rows, rows >= 8
_MAX_STAGE_ROWS2 = 256  # rows x f1 of one hop-2 stage
_MAX_STAGE_PICKS2 = 4096  # rows x f1 x f2 x K2 of one hop-2 stage
MAX_OUT_ROWS2 = 1 << 17  # m x (1 + f1), the rows of both hop outputs:
# 64 MB of VMEM at this cap (fact 1)


def _backend_ok(require_single_device: bool) -> bool:
    import jax

    if jax.default_backend() != "tpu":
        return False
    if require_single_device and len(jax.devices()) != 1:
        return False
    # Not caught: on a TPU backend a Pallas import or API error is a
    # broken installation, and swallowing it would route every draw to
    # the XLA chain without a word.
    from jax.experimental import pallas  # noqa: F401
    from jax.experimental.pallas import tpu  # noqa: F401

    return True


def _force_flag():
    """Strictly parsed EULER_TPU_PALLAS_SAMPLING: True ("1"/"true"),
    False ("0"/"false"), or None (unset/empty). Anything else —
    "off", "no", "False " with a space — warns once and counts as
    unset rather than silently force-enabling the kernel."""
    raw = os.environ.get("EULER_TPU_PALLAS_SAMPLING")
    if raw is None or raw == "":
        return None
    v = raw.strip().lower()
    if v in ("1", "true"):
        return True
    if v in ("0", "false"):
        return False
    import warnings

    warnings.warn(
        f"EULER_TPU_PALLAS_SAMPLING={raw!r} is not one of 0/1/false/true"
        " (case-insensitive); ignoring it",
        stacklevel=3,
    )
    return None


def available() -> bool:
    """True when the kernel should be called DIRECTLY: TPU backend, one
    device (see SPMD note above), not overridden by env.
    EULER_TPU_PALLAS_SAMPLING=1 skips the single-device heuristic —
    but only once a kernel mesh is registered (device.kernel_mesh_scope,
    which run_loop.main and train.train enter): on a multi-device
    backend with NO mesh registered the flag warns and still returns
    False, because the direct (non-shard_map) route would run an
    unsharded pallas_call under pjit — silently wrong per-shard draws.
    Experts composing their own shard_map call
    pallas_sampling.sample_neighbor directly, which never consults this
    gate. The flag still requires a TPU backend — the kernel's
    primitives exist nowhere else; =0 forces the XLA path. On a TPU
    backend a Pallas that does not import raises (_backend_ok)."""
    force = _force_flag()
    if force is not None:
        if not force:
            return False
        ok = _backend_ok(require_single_device=False)
        if ok:
            import jax

            from euler_tpu.graph import device as _dg

            if len(jax.devices()) > 1 and _dg.kernel_mesh() is None:
                import warnings

                warnings.warn(
                    "EULER_TPU_PALLAS_SAMPLING=1 with "
                    f"{len(jax.devices())} devices but no kernel mesh:"
                    " pallas_call does not partition under pjit, so the"
                    " force flag is ignored (XLA path) — run inside"
                    " device.kernel_mesh_scope(mesh), as run_loop.main"
                    " and train.train do, to wire the kernel per-shard",
                    stacklevel=2,
                )
                return False
        return ok
    return _backend_ok(require_single_device=True)


def sharded_available() -> bool:
    """True when the kernel can run PER-SHARD inside shard_map on this
    backend: TPU, any device count. This is the mesh-path activation
    check (device.kernel_mesh_scope consults it);
    available() stays the single-device auto-activation check —
    pallas_call does not partition under plain pjit."""
    if _force_flag() is False:
        return False
    return _backend_ok(require_single_device=False)


def interpret_params():
    """False (compile for real) unless EULER_TPU_PALLAS_INTERPRET opts
    this process into pallas' TPU interpret mode: "1" emulates the
    kernels on CPU, "races" also enables the emulator's DMA race
    detector. Test-only — interpretation is orders of magnitude slower
    than both the compiled kernel and the XLA chain, so nothing
    auto-activates it; available() is unaffected (the interpret knob
    changes how an explicit kernel call executes, not routing). Refused
    on a TPU backend, where it would quietly run the emulator in place
    of the compiled kernel."""
    raw = os.environ.get("EULER_TPU_PALLAS_INTERPRET")
    if raw not in ("1", "races"):
        return False
    import jax
    from jax.experimental.pallas import tpu as pltpu

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"EULER_TPU_PALLAS_INTERPRET={raw} on a TPU backend: the "
            "kernels compile for real here — unset it (interpret mode "
            "is the CPU tests' emulator)"
        )

    return pltpu.InterpretParams(detect_races=(raw == "races"))


def eligible(m: int, count: int) -> bool:
    """True when a draw of ``m`` source nodes x ``count`` fits the
    kernel's on-core budgets (ids in scalar prefetch / SMEM, [M, count]
    output whole in VMEM — see the budget notes at the top); callers
    fall back to the XLA chain otherwise."""
    return (
        count <= MAX_COUNT
        and m <= MAX_M
        and m * count <= MAX_OUT_ELEMS
    )


@setup_spanned("setup_pack")
def pack_adjacency(adj: dict, max_bytes: int = MAX_PACKED_BYTES):
    """[2KN, 128] int32, K = ceil(W/128): node i occupies rows
    2K*i..2K*i+2K-1 — its K neighbor-id rows (pad: default id) then its
    K cumulative-weight rows bitcast to int32 (pad: 1.0). Returns None
    (caller keeps the XLA path) when the slab is wider than MAX_W, or
    when the packed copy — which is KEPT ALONGSIDE nbr/cum (the fallback
    paths still need them) at a fixed K KB/node regardless of real
    degree — would exceed ``max_bytes`` of HBM."""
    nbr = np.asarray(adj["nbr"])
    cum = np.asarray(adj["cum"])
    n_rows, w = nbr.shape
    k = (w + LANES - 1) // LANES
    if w > MAX_W or 2 * k * n_rows * LANES * 4 > max_bytes:
        return None
    nbr_p = np.full((n_rows, k * LANES), n_rows - 1, np.int32)
    nbr_p[:, :w] = nbr
    # unsampleable rows (zero total weight — their cum is a neutral
    # all-1.0, see build_adjacency) draw the DEFAULT node on the host
    # path via the `sampleable` mask; the packed slab is kernel-only, so
    # bake that in by default-filling their neighbor lanes — the kernel
    # then needs no separate mask gather at draw time
    sampleable = np.asarray(
        adj.get("sampleable", np.ones(n_rows, bool))
    ).astype(bool)
    nbr_p[~sampleable] = n_rows - 1
    cum_p = np.ones((n_rows, k * LANES), np.float32)
    cum_p[:, :w] = cum
    packed = np.empty((2 * k * n_rows, LANES), np.int32)
    # node-major: [nbr_0..nbr_{K-1}, cum_0..cum_{K-1}] per node
    packed.reshape(n_rows, 2 * k, LANES)[:, :k] = nbr_p.reshape(
        n_rows, k, LANES
    )
    packed.reshape(n_rows, 2 * k, LANES)[:, k:] = cum_p.view(
        np.int32
    ).reshape(n_rows, k, LANES)
    return packed


def _prng_uniform(rows):
    """[rows, 1] 24-bit mantissa-exact uniform in [0, 1) from the core
    PRNG (seeded once per kernel via pltpu.prng_seed)."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    bits = pltpu.bitcast(pltpu.prng_random_bits((rows, 1)), jnp.uint32)
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (
        1.0 / (1 << 24)
    )


def _stage_draw(slab_block, rows, k, count, next_u):
    """[rows, count] int32 picks from one stage's slab rows (VMEM value,
    [2k*rows, 128], node-major K nbr rows then K cum rows per node).
    ``next_u(c)`` yields the [rows, 1] uniform for draw column c — the
    core PRNG on hardware, an injected-uniform read under interpret
    mode. Shared by the single-hop kernel and both hops of the chained
    kernel, so the draw semantics cannot drift between them."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    both = slab_block.reshape(rows, 2 * k, LANES)
    nbrs = [both[:, j, :] for j in range(k)]               # k x [rows, 128]
    cums = [
        pltpu.bitcast(both[:, k + j, :], jnp.float32) for j in range(k)
    ]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    cols = []
    for c in range(count):
        u = next_u(c)
        # rank over the whole (sorted) K*128-lane cumulative row
        idx = jnp.sum((u >= cums[0]).astype(jnp.int32), axis=1,
                      keepdims=True)
        for j in range(1, k):
            idx = idx + jnp.sum(
                (u >= cums[j]).astype(jnp.int32), axis=1, keepdims=True
            )
        idx = jnp.minimum(idx, k * LANES - 1)
        # select lane idx from the concatenated nbr rows: exactly one
        # register's local lane matches (out-of-register locals match
        # no lane and contribute 0)
        val = jnp.sum(
            jnp.where(lanes == idx, nbrs[0], 0), axis=1, keepdims=True
        )
        for j in range(1, k):
            val = val + jnp.sum(
                jnp.where(lanes == idx - j * LANES, nbrs[j], 0),
                axis=1, keepdims=True,
            )
        cols.append(val)
    # unsampleable/default rows already hold the default id in every
    # neighbor lane (pack_adjacency), so the draw needs no mask here
    return jnp.concatenate(cols, axis=1)


def _kernel(ids_ref, seed_ref, pk_hbm, *rest,
            rows, count, num_iters, k, with_u):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if with_u:
        u_ref, out_ref, pk_s, sem = rest
    else:
        u_ref, (out_ref, pk_s, sem) = None, rest

    # both words seed the core PRNG: 62 bits of caller entropy (a lone
    # int31 word collides across long runs)
    pltpu.prng_seed(seed_ref[0], seed_ref[1])

    def dma(slot, r, row):
        # one copy moves the node's whole 2K-row block (K nbr rows + K
        # cum rows); every copy is the same size, so a single per-slot
        # semaphore counts them all
        return pltpu.make_async_copy(
            pk_hbm.at[pl.ds(row * 2 * k, 2 * k), :],
            pk_s.at[slot, pl.ds(2 * k * r, 2 * k), :],
            sem.at[slot],
        )

    def each_row(copy):
        """``copy(r)`` for every row of a stage. A walk step (one draw a
        row) runs the rows as a loop on the core: unrolled here, a
        512-row stage is 1,536 DMA descriptors to trace and lower, 10 to
        35 s of Python a kernel, and a walk holds ``walk_len`` kernels
        (PERF.md section 6, PR 35). The fan-out draws keep the unrolled
        stage they were measured with."""
        if count == 1:
            def eight(g, _):    # rows is a power of two, 8 at the least
                for j in range(8):
                    copy(g * 8 + j)
                return 0

            jax.lax.fori_loop(0, rows // 8, eight, 0)
        else:
            for r in range(rows):
                copy(r)

    def issue(slot, it):
        base = it * rows
        each_row(lambda r: dma(slot, r, ids_ref[base + r]).start())

    def wait(slot, it):
        base = it * rows
        each_row(lambda r: dma(slot, r, ids_ref[base + r]).wait())

    issue(0, 0)

    def body(it, _):
        slot = jax.lax.rem(it, 2)

        @pl.when(it + 1 < num_iters)
        def _():
            issue(jax.lax.rem(it + 1, 2), it + 1)

        wait(slot, it)
        if with_u:
            def next_u(c):
                return u_ref[pl.ds(it * rows, rows), c:c + 1]
        else:
            def next_u(c):
                return _prng_uniform(rows)
        out_ref[pl.ds(it * rows, rows), :] = _stage_draw(
            pk_s[slot], rows, k, count, next_u
        )
        return 0

    jax.lax.fori_loop(0, num_iters, body, 0)


def _two_word_seed(seed):
    import jax.numpy as jnp

    seed = jnp.atleast_1d(jnp.asarray(seed)).astype(jnp.int32)
    if seed.shape[0] < 2:
        seed = jnp.concatenate([seed, jnp.zeros(1, jnp.int32)])
    return seed[:2]


def sample_neighbor(adj: dict, nodes, seed, count: int, u=None):
    """[len(nodes), count] int32 weighted draws via the fused kernel.

    ``adj`` must carry the "packed" slab (models add it through
    base.Model.add_sampling_consts when available()); ``seed`` is one or
    two traced int32 words (two preferred — both are fed to the core
    PRNG; callers with a PRNG key derive them via jax.random.randint).
    A scalar/1-word seed is zero-extended.

    ``u`` (test-only, [len(nodes), count] float32 in [0, 1)): injected
    uniforms replacing the core PRNG's — interpret-mode tests use them
    to pin the kernel's picks EXACTLY to the XLA chain's semantics,
    since the emulated PRNG returns zeros."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed = adj["packed"]
    n_rows = adj["nbr"].shape[0]
    k = packed.shape[0] // (2 * n_rows)  # ceil(W / 128) row-pairs/node
    nodes = jnp.asarray(nodes, jnp.int32)
    shape = nodes.shape
    flat = nodes.reshape(-1)
    m = flat.shape[0]
    if m == 0:  # the kernel's prologue DMA needs >= 1 real row
        return jnp.zeros((*shape, count), jnp.int32)
    # ids become raw DMA offsets in the kernel — clamp so unknown ids
    # (negative or past the slab) land on the DEFAULT row (n_rows-1)
    # instead of reading out of bounds; device.sample_neighbor's XLA
    # path applies the identical mapping, keeping build_adjacency's
    # "unknown ids sample the default node" contract on both paths
    flat = jnp.where(flat < 0, n_rows - 1, jnp.minimum(flat, n_rows - 1))
    # power-of-two stage size (sublane-aligned dynamic slices), floored
    # at 8, scaled down by K to keep the 2-slot scratch K-independent
    # and by count to keep the stage's live picks in budget
    max_r = max(1, min(_MAX_R // k, _MAX_STAGE_PICKS // count))
    max_r = max(8, 1 << (max_r.bit_length() - 1))
    rows = max_r if m >= max_r else max(8, 1 << (m - 1).bit_length())
    mp = ((m + rows - 1) // rows) * rows
    ids = jnp.pad(flat, (0, mp - m))
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),           # packed slab (HBM)
    ]
    args = [ids, _two_word_seed(seed), packed]
    if u is not None:
        u = jnp.pad(
            jnp.asarray(u, jnp.float32).reshape(m, count),
            ((0, mp - m), (0, 0)),
        )
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        args.append(u)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # ids, seed
        grid=(1,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, 2 * k * rows, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, rows=rows, count=count, num_iters=mp // rows, k=k,
            with_u=u is not None,
        ),
        out_shape=jax.ShapeDtypeStruct((mp, count), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret_params(),
    )(*args)
    return out[:m].reshape(*shape, count)


def eligible2(m: int, f1: int, f2: int, k1: int = 1, k2: int = 1) -> bool:
    """True when a chained two-hop fanout of ``m`` roots x f1 x f2 over
    K1/K2-row-pair slabs fits the fused kernel's budgets (notes at the
    top): root ids in scalar prefetch (SMEM), both hop outputs whole in
    VMEM, and a hop-2 stage that still fits at the MINIMUM stage size of
    8 rows — without that a wide fanout would pass and then fail VMEM
    allocation at compile time instead of falling back. Callers fall
    back to the per-hop path (which may still use the single-hop
    kernel) otherwise."""
    return (
        f1 <= MAX_F1
        and f2 <= MAX_COUNT
        and m <= MAX_M
        and m * f1 * f2 <= MAX_OUT_ELEMS
        and m * (1 + f1) <= MAX_OUT_ROWS2
        and 8 * f1 * f2 * k2 <= _MAX_STAGE_PICKS2
        and k1 <= MAX_W // LANES
        and k2 <= MAX_W // LANES
    )


def _fanout2_kernel(ids_ref, seed_ref, pk1_hbm, pk2_hbm, *rest,
                    rows, f1, f2, num_iters, k1, k2, with_u):
    """Both fanout hops in one program. Per stage of ``rows`` roots:
    hop-1 slab rows stream in (double-buffered, like _kernel), the f1
    picks are drawn and written to out1, then async-copied VMEM->SMEM so
    they can address HBM, and the rows*f1 data-dependent hop-2 row DMAs
    are issued. Hop-2 processing runs ONE STAGE BEHIND hop-1: stage
    it's hop-2 rows arrive while stage it+1's hop-1 draw computes, so
    the dependent DMA latency hides behind compute instead of
    serializing after it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if with_u:
        u1_ref, u2_ref, out1_ref, out2_ref, pk1_s, pk2_s, picks_v, \
            picks_s, sem1, sem2, semp = rest
    else:
        u1_ref = u2_ref = None
        (out1_ref, out2_ref, pk1_s, pk2_s, picks_v, picks_s, sem1, sem2,
         semp) = rest

    pltpu.prng_seed(seed_ref[0], seed_ref[1])
    rows2 = rows * f1

    def dma1(slot, r, row):
        return pltpu.make_async_copy(
            pk1_hbm.at[pl.ds(row * 2 * k1, 2 * k1), :],
            pk1_s.at[slot, pl.ds(2 * k1 * r, 2 * k1), :],
            sem1.at[slot],
        )

    def dma2(slot, j, row):
        return pltpu.make_async_copy(
            pk2_hbm.at[pl.ds(row * 2 * k2, 2 * k2), :],
            pk2_s.at[slot, pl.ds(2 * k2 * j, 2 * k2), :],
            sem2.at[slot],
        )

    def issue1(slot, it):
        base = it * rows
        for r in range(rows):
            dma1(slot, r, ids_ref[base + r]).start()

    def wait1(slot, it):
        base = it * rows
        for r in range(rows):
            dma1(slot, r, ids_ref[base + r]).wait()

    def issue2(slot):
        # picks_s holds THIS stage's picks (copied just before): they
        # are in-slab ids (< pk2's row count — sample_fanout2 asserts
        # both slabs share it), so no clamp is needed for the DMA
        for j in range(rows2):
            r, c = divmod(j, f1)
            dma2(slot, j, picks_s[r, c]).start()

    def wait2(slot):
        # semaphore waits count BYTES, not descriptors: picks_s has
        # moved on to the next stage by now, so re-deriving the issued
        # src rows is impossible — wait on same-shaped descriptors
        # (src row 0) instead, which decrements the same per-slot
        # semaphore by the same per-copy size
        for j in range(rows2):
            dma2(slot, j, 0).wait()

    def next_u1(it):
        if with_u:
            return lambda c: u1_ref[pl.ds(it * rows, rows), c:c + 1]
        return lambda c: _prng_uniform(rows)

    def next_u2(stage):
        if with_u:
            return lambda c: u2_ref[pl.ds(stage * rows2, rows2), c:c + 1]
        return lambda c: _prng_uniform(rows2)

    def process_hop2(slot, stage):
        wait2(slot)
        out2_ref[pl.ds(stage * rows2, rows2), :] = _stage_draw(
            pk2_s[slot], rows2, k2, f2, next_u2(stage)
        )

    issue1(0, 0)

    def body(it, _):
        slot = jax.lax.rem(it, 2)

        @pl.when(it + 1 < num_iters)
        def _():
            issue1(jax.lax.rem(it + 1, 2), it + 1)

        wait1(slot, it)
        picks = _stage_draw(pk1_s[slot], rows, k1, f1, next_u1(it))
        out1_ref[pl.ds(it * rows, rows), :] = picks
        # VMEM->SMEM so the picks can address HBM. Mosaic requires DMA
        # slices lane-aligned to the (·, 128) tiling, so the copy source
        # is a full-width scratch (picks lane-padded with zeros), not an
        # f1-wide slice of out1 — hardware rejects the narrow slice
        # (interpret mode does not model the tiling constraint).
        picks_v[:, :] = jnp.concatenate(
            [picks, jnp.zeros((rows, LANES - f1), jnp.int32)], axis=1
        ) if f1 < LANES else picks
        cp = pltpu.make_async_copy(picks_v, picks_s, semp)
        cp.start()
        cp.wait()
        issue2(slot)

        # NOTE on uniform ORDER vs the per-hop path: with the core PRNG
        # (hardware), hop-2 uniforms for stage it-1 are drawn after
        # hop-1 uniforms for stages <= it — a different position in the
        # one PRNG stream than two sequential kernels would use. That
        # changes sequences, not distributions (same independent
        # stream), exactly like the kernel-vs-threefry difference the
        # module docstring records. Injected-uniform runs are
        # position-exact by construction.
        @pl.when(it > 0)
        def _():
            process_hop2(jax.lax.rem(it + 1, 2), it - 1)

        return 0

    jax.lax.fori_loop(0, num_iters, body, 0)
    process_hop2(
        jax.lax.rem(num_iters - 1, 2), num_iters - 1
    )


def sample_fanout2(adj1: dict, adj2: dict, roots, seed, f1: int, f2: int,
                   u1=None, u2=None):
    """(hop1 [m, f1], hop2 [m*f1, f2]) int32 draws with BOTH hops fused
    into one kernel program (see _fanout2_kernel). ``adj1``/``adj2`` may
    be the same dict (homogeneous fanout) or differ (metapath); both
    must carry "packed" slabs over the same id space. ``u1``/``u2`` are
    the test-only injected uniforms (see sample_neighbor).

    Reference semantics: two chained CompactNode::SampleNeighbor rounds
    (euler/core/compact_node.cc:42-101) — identical per-hop draw
    distribution to device.sample_fanout's per-hop path."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_rows = adj1["nbr"].shape[0]
    if adj2["nbr"].shape[0] != n_rows:
        raise ValueError(
            "sample_fanout2 needs both adjacencies over one id space: "
            f"{n_rows} vs {adj2['nbr'].shape[0]} rows"
        )
    pk1, pk2 = adj1["packed"], adj2["packed"]
    k1 = pk1.shape[0] // (2 * n_rows)
    k2 = pk2.shape[0] // (2 * n_rows)
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    m = roots.shape[0]
    if m == 0:
        return (
            jnp.zeros((0, f1), jnp.int32),
            jnp.zeros((0, f2), jnp.int32),
        )
    # same unknown-id contract as sample_neighbor: clamp to the default
    # row rather than DMA out of bounds
    roots = jnp.where(
        roots < 0, n_rows - 1, jnp.minimum(roots, n_rows - 1)
    )
    # stage size: power-of-two (sublane-aligned out1 slices), sized so
    # the hop-2 stage stays inside its row and pick budgets (eligible2
    # guarantees 8 rows do) and the full-lane-width pick buffers (R x
    # 128 ids in VMEM scratch and SMEM — full width because the
    # VMEM->SMEM DMA must be 128-lane aligned) stay <= 8 KB, i.e. R <= 16
    r_max = max(1, min(
        _MAX_R // k1,
        _MAX_STAGE_ROWS2 // f1,
        _MAX_STAGE_PICKS2 // (f1 * f2 * k2),
        16,
    ))
    r_max = max(8, 1 << (r_max.bit_length() - 1))
    rows = r_max if m >= r_max else max(8, 1 << (m - 1).bit_length())
    mp = ((m + rows - 1) // rows) * rows
    ids = jnp.pad(roots, (0, mp - m), constant_values=n_rows - 1)
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),           # hop-1 slab (HBM)
        pl.BlockSpec(memory_space=pl.ANY),           # hop-2 slab (HBM)
    ]
    args = [ids, _two_word_seed(seed), pk1, pk2]
    with_u = u1 is not None
    if (u1 is None) != (u2 is None):
        raise ValueError("inject both u1 and u2 or neither")
    if with_u:
        u1 = jnp.pad(
            jnp.asarray(u1, jnp.float32).reshape(m, f1),
            ((0, mp - m), (0, 0)),
        )
        u2 = jnp.pad(
            jnp.asarray(u2, jnp.float32).reshape(m * f1, f2),
            ((0, (mp - m) * f1), (0, 0)),
        )
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ]
        args += [u1, u2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # root ids, seed
        grid=(1,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 2 * k1 * rows, LANES), jnp.int32),
            pltpu.VMEM((2, 2 * k2 * rows * f1, LANES), jnp.int32),
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.SMEM((rows, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out1, out2 = pl.pallas_call(
        functools.partial(
            _fanout2_kernel, rows=rows, f1=f1, f2=f2,
            num_iters=mp // rows, k1=k1, k2=k2, with_u=with_u,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((mp, f1), jnp.int32),
            jax.ShapeDtypeStruct((mp * f1, f2), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret_params(),
    )(*args)
    return out1[:m], out2[:m * f1]


def sample_fanout2_sharded(
    adj1: dict, adj2: dict, roots, seed, f1: int, f2: int, mesh,
    axis: str = "data", draw_fn=None,
):
    """sample_fanout2 under SPMD: shard_map over ``mesh``'s ``axis``
    with roots batch-sharded, both (packed) adjacencies replicated, and
    per-shard seeds decorrelated via axis_index — the same wiring as
    sample_neighbor_sharded (see its docstring for why plain pjit
    cannot express this). ``roots`` length must divide the axis size;
    device.sample_fanout checks before routing here. ``draw_fn``
    defaults to sample_fanout2; tests inject an XLA-executable stand-in
    to exercise the wiring on CPU meshes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if draw_fn is None:
        draw_fn = sample_fanout2
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    seed = _two_word_seed(seed)

    def body(adj1_l, adj2_l, roots_l, seed_l):
        ai = jax.lax.axis_index(axis).astype(jnp.int32)
        s = seed_l + (ai + 1) * jnp.int32(0x9E3779B1 - (1 << 32))
        return draw_fn(adj1_l, adj2_l, roots_l, s, f1, f2)

    out1, out2 = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(), adj1),
            jax.tree.map(lambda _: P(), adj2),
            P(axis),
            P(),
        ),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )(adj1, adj2, roots, seed)
    return out1, out2


def sample_neighbor_sharded(
    adj: dict, nodes, seed, count: int, mesh, axis: str = "data",
    draw_fn=None,
):
    """The kernel draw under SPMD: shard_map over ``mesh``'s ``axis``
    with nodes batch-sharded and the (packed) adjacency replicated, so
    each device runs ONE fused pallas_call on its local rows — the
    composition plain pjit cannot express (pallas_call does not
    partition). Per-shard seeds are decorrelated by folding in
    axis_index, otherwise every shard would replay the same core-PRNG
    stream against different rows.

    ``nodes`` is flattened; its length must divide the axis size
    (callers check — device.sample_neighbor falls back to the XLA chain
    otherwise). ``draw_fn(adj, nodes, seed, count)`` defaults to the
    kernel; tests inject an XLA-executable stand-in to exercise this
    wiring on CPU meshes where the kernel's TPU primitives cannot run.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if draw_fn is None:
        draw_fn = sample_neighbor
    nodes = jnp.asarray(nodes, jnp.int32)
    shape = nodes.shape
    flat = nodes.reshape(-1)
    seed = _two_word_seed(seed)

    def body(adj_l, nodes_l, seed_l):
        ai = jax.lax.axis_index(axis).astype(jnp.int32)
        # distinct per-shard words (golden-ratio odd constant; int32
        # wraparound is fine — determinism is all that matters)
        s = seed_l + (ai + 1) * jnp.int32(0x9E3779B1 - (1 << 32))
        return draw_fn(adj_l, nodes_l, s, count)

    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(), adj),
            P(axis),
            P(),
        ),
        out_specs=P(axis),
        check_vma=False,
    )(adj, flat, seed)
    return out.reshape(*shape, count)
