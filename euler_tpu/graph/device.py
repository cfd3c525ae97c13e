"""Device-resident graph sampling: adjacency in HBM, fanout inside the
jitted step.

The reference's hot loop is host-side per-draw binary search
(reference euler/core/compact_node.cc:42-101 SampleNeighbor, called
batch x prod(fanouts) times per step through the TF AsyncOpKernels). On
TPU the roles invert: a single chip runs the whole GraphSAGE train step in
~0.1 ms, so any host-side sampling — however fast — dominates the step.
For graphs that fit in HBM (hundreds of millions of edges at int32), the
TPU-native design uploads the adjacency ONCE and samples on device:

- ``build_adjacency`` exports a padded-CSR slab per edge-type set from the
  host engine: ``nbr [N+2, W] int32`` neighbor ids and ``cum [N+2, W]
  float32`` normalized cumulative weights per row (CompactNode's
  cumulative layout, vectorized). Row max_id+1 is the default node
  (degree 0), so chained hops through padding stay padding — the same
  convention as the host path.
- ``sample_neighbor`` draws weighted neighbors with replacement inside
  jit: gather the row, one uniform per draw, and an index =
  sum(u >= cum) comparison — the vectorized equivalent of the binary
  search, exact same distribution (statistically verified against the
  host engine in tests/test_device_graph.py).
- ``build_node_sampler`` / ``sample_node`` are the weighted global node
  sampler (roots, negatives): one Walker alias table per node type, as
  the reference's CompactGraph::BuildGlobalSampler builds
  (compact_graph.cc:74-104), and one alias draw a node — an integer
  slot, a uniform, three gathers, no search.

Everything returned is a dict of numpy arrays meant to live in
``state["consts"]`` — replicated (or sharded) over the mesh, aliased
across steps by donation, free after the one-time upload. Export works
against local AND remote graphs: adjacency rides get_full_neighbor and
the samplers ride node_weights/node_types, all of which scatter per
shard in remote mode — so device-sampling training composes with a
sharded TCP-registry cluster (tests/test_remote.py).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu import devprof
from euler_tpu.telemetry import setup_spanned

log = logging.getLogger("euler_tpu")


@setup_spanned("setup_adjacency")
def _fetch_flat_csr(graph, edge_types, max_id: int, chunk: int,
                    sorted: bool = False):
    """Chunked full-neighbor export shared by the slab and alias
    builders: (counts [N+2] int64, nbr_flat int64, w_flat float32
    contiguous, offsets [N+3] int64 with offsets[-1] == len(nbr_flat)).
    Row max_id+1 (the default row) is always empty here; builders add
    their own default semantics."""
    n_rows = max_id + 2
    et = list(edge_types)
    counts_all = np.zeros(n_rows, dtype=np.int64)
    nbr_parts: list[np.ndarray] = []
    w_parts: list[np.ndarray] = []
    for lo in range(0, max_id + 1, chunk):
        ids = np.arange(lo, min(lo + chunk, max_id + 1), dtype=np.int64)
        nbr, w, _, counts = graph.get_full_neighbor(ids, et, sorted=sorted)
        counts_all[lo:lo + len(ids)] = counts
        nbr_parts.append(nbr)
        w_parts.append(w)
    nbr_flat = (
        np.concatenate(nbr_parts) if nbr_parts else np.zeros(0, np.int64)
    )
    w_flat = np.ascontiguousarray(
        np.concatenate(w_parts) if w_parts else np.zeros(0), np.float32
    )
    offsets = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(counts_all, out=offsets[1:])
    return counts_all, nbr_flat, w_flat, offsets


@setup_spanned("setup_adjacency")
def build_adjacency(
    graph,
    edge_types,
    max_id: int,
    max_degree: int | None = None,
    chunk: int = 65536,
    sorted: bool = False,
    _prefetched=None,
) -> dict:
    """Export the adjacency restricted to ``edge_types`` as device slabs.

    Returns {"nbr": [N+2, W] int32, "cum": [N+2, W] float32,
    "deg": [N+2] int32} with N = max_id + 1; W = observed max degree (or
    ``max_degree`` cap — rows beyond it are truncated to their W heaviest
    neighbors and renormalized, with a warning). ``deg`` is the in-slab
    neighbor count (min(true degree, W)) — the full-neighborhood models
    mask padding slots with it. Unknown ids and the default row sample
    the default node (max_id + 1).
    """
    n_rows = max_id + 2
    default = max_id + 1
    counts_all, nbr_flat, w_flat, offsets = (
        _prefetched
        if _prefetched is not None
        else _fetch_flat_csr(graph, edge_types, max_id, chunk, sorted=sorted)
    )

    W = int(counts_all.max()) if len(counts_all) else 0
    truncated = np.zeros(0, dtype=np.int64)
    if max_degree is not None and W > max_degree:
        W = max_degree
        truncated = np.flatnonzero(counts_all > W)
    W = max(W, 1)
    slab_bytes = n_rows * W * 8  # nbr int32 + cum float32
    budget = device_memory_bytes()
    if slab_bytes > budget:
        raise ValueError(
            f"build_adjacency: the padded slab of edge types "
            f"{list(edge_types)} is {n_rows} rows x {W} (the graph's "
            f"largest degree{'' if max_degree is None else ', capped'}): "
            f"{slab_bytes / 1e9:.1f} GB of nbr and cum, over the "
            f"{budget / 1e9:.1f} GB of the device it is built for; sample "
            "from exact flat-CSR alias tables (--alias_sampling true, 12 B "
            "an edge) or cap the width (--max_degree)"
        )

    # vectorized scatter into the padded slabs (no per-row Python loop:
    # real graphs have hundreds of thousands of rows)
    rows = np.repeat(np.arange(n_rows), counts_all)
    cols = np.arange(len(nbr_flat)) - np.repeat(offsets[:-1], counts_all)
    keep = cols < W  # drop overflow entries; heavy-tail fix-up below
    nbr_out = np.full((n_rows, W), default, dtype=np.int32)
    cum_out = np.ones((n_rows, W), dtype=np.float32)
    nbr_out[rows[keep], cols[keep]] = nbr_flat[keep]
    # per-row normalized cumulative weights from one flat cumsum
    csum = np.cumsum(w_flat, dtype=np.float64)
    csum_z = np.concatenate([[0.0], csum])
    row_base = csum_z[np.repeat(offsets[:-1], counts_all)]
    row_total = (csum_z[offsets[1:]] - csum_z[offsets[:-1]])[rows]
    with np.errstate(invalid="ignore", divide="ignore"):
        cum_flat = (csum_z[1:] - row_base) / row_total
    cum_out[rows[keep], cols[keep]] = cum_flat[keep]
    # guard float drift: the last real slot must be exactly 1 so u < 1
    # always lands in-row
    has = counts_all > 0
    cum_out[np.flatnonzero(has),
            np.minimum(counts_all[has], W) - 1] = 1.0
    # rows whose weights sum to 0 are UNSAMPLEABLE (host sampling fills
    # the default node) but their neighbors still EXIST (host
    # GetFullNeighbor returns them, and the full-neighborhood GCN
    # aggregates them) — so keep nbr/deg intact, neutralize the nan cum,
    # and record unsampleability separately for sample_neighbor
    zero_w = np.flatnonzero(
        has & (csum_z[offsets[1:]] - csum_z[offsets[:-1]] <= 0)
    )
    sampleable = np.ones(n_rows, dtype=bool)
    if len(zero_w):
        cum_out[zero_w] = 1.0
        sampleable[zero_w] = False

    # rows beyond the cap: redo exactly (keep the heaviest W neighbors)
    for i in truncated:
        nb = nbr_flat[offsets[i]:offsets[i + 1]]
        wt = w_flat[offsets[i]:offsets[i + 1]]
        sel = np.argsort(wt)[::-1][:W]
        if sorted:  # keep the heaviest W but preserve the id order
            sel = np.sort(sel)
        nb, wt = nb[sel], wt[sel]
        total = wt.sum()
        if total <= 0:
            continue
        nbr_out[i, :W] = nb
        c = np.cumsum(wt) / total
        c[-1] = 1.0
        cum_out[i, :W] = c
    if len(truncated):
        import warnings

        warnings.warn(
            f"build_adjacency: {len(truncated)} rows exceeded "
            f"max_degree={W}; truncated to their heaviest neighbors "
            "(renormalized)"
        )
    deg = np.minimum(counts_all, W).astype(np.int32)
    # sorted=True rows are id-ordered (padding = default = largest id, so
    # whole rows sort ascending) — the precondition for
    # biased_random_walk's searchsorted membership test. Not recorded in
    # the dict: consts pytrees are traced through jit, where a flag leaf
    # could not be branch-checked anyway; callers keep sorted slabs under
    # distinct consts keys (Model.adj_key(et, sorted=True)).
    # "truncated_rows" is HOST-side metadata (a plain int, popped by
    # Model.add_sampling_consts before the dict reaches jit): biased
    # walks on a truncated slab are measurably distorted (PERF.md walk
    # study) and callers must be able to detect the condition.
    return {
        "nbr": nbr_out,
        "cum": cum_out,
        "deg": deg,
        "sampleable": sampleable,
        "truncated_rows": int(len(truncated)),
    }


def device_memory_bytes() -> int:
    """What one device of the default backend holds: the limit its
    ``memory_stats()`` reports (a TPU's HBM), else the host's physical
    memory, where the CPU backend keeps its arrays."""
    try:
        limit = int((jax.devices()[0].memory_stats() or {}).get(
            "bytes_limit", 0))
    except Exception:  # noqa: BLE001 - a backend without memory_stats
        limit = 0
    if limit > 0:
        return limit
    return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")


def _build_alias_rows(offsets: np.ndarray, w_flat: np.ndarray):
    """(prob [E] float32, alias [E] int32, ROW-LOCAL slots) Walker alias
    tables over the CSR rows ``offsets`` ([R+1] int64) of ``w_flat``, by
    the native builder (eg_build_alias_csr: scaled in float64, OpenMP
    over rows). A slot nothing was paired with keeps prob 1 and itself
    as alias, and so does every slot of a zero-total row."""
    import ctypes

    from euler_tpu.graph import native

    e = len(w_flat)
    prob = np.ones(e, dtype=np.float32)
    alias_local = np.zeros(e, dtype=np.int32)
    if e:
        offsets = np.ascontiguousarray(offsets, np.int64)
        w_flat = np.ascontiguousarray(w_flat, np.float32)
        native.lib().eg_build_alias_csr(
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ctypes.c_int64(len(offsets) - 1),
            w_flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            prob.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            alias_local.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
    return prob, alias_local


@setup_spanned("setup_adjacency")
def build_alias_adjacency(
    graph,
    edge_types,
    max_id: int,
    chunk: int = 65536,
    sorted: bool = False,
    _prefetched=None,
) -> dict:
    """Export the adjacency restricted to ``edge_types`` as device-side
    EXACT sampling structures: flat-CSR Walker alias tables, O(1) per
    draw with NO max_degree truncation — the heavy-tail alternative to
    build_adjacency's padded slab, whose width is the max observed
    degree (unbuildable on power-law graphs where hubs reach tens of
    thousands of neighbors; reference semantics being preserved:
    CompactNode::SampleNeighbor draws exactly over ALL neighbors,
    euler/core/compact_node.cc:42-101).

    Returns {"off": [N+2] int32 row starts, "deg": [N+2] int32,
    "nbr": [E] int32, "alias": [E] int32 (GLOBAL ids, prebaked so the
    draw needs no second row-local hop), "prob": [E] float32,
    "sampleable": [N+2] bool, "bisect_steps": [ceil(log2(max_degree))]
    int8 zeros — a SHAPE-carried static (array shapes survive jit
    tracing where an int leaf would be traced) that lets the rejection
    walk's membership bisection stop at the max ROW width instead of
    log2(E) iterations} with N = max_id + 1 and E = total edges.
    Memory is O(E) — 12 bytes/edge vs the slab's O(N * max_degree) —
    e.g. ~1.4 GB for a 114M-edge Reddit-scale graph. The alias build
    itself runs in native code (eg_build_alias_csr, OpenMP over rows).
    Unknown ids and the default row sample the default node, exactly
    like build_adjacency.

    ``sorted=True`` exports id-sorted CSR rows — the precondition for
    alias_biased_random_walk's parent-membership bisection (the alias
    draw itself is order-independent, so sorted tables sample the same
    distribution)."""
    default = max_id + 1
    counts_all, nbr_flat, w_flat, offsets = (
        _prefetched
        if _prefetched is not None
        else _fetch_flat_csr(graph, edge_types, max_id, chunk, sorted=sorted)
    )
    e = len(nbr_flat)
    if e >= 1 << 31:
        raise ValueError(
            f"alias adjacency needs int32 slots: {e} edges; shard the "
            "graph first"
        )
    prob, alias_local = _build_alias_rows(offsets, w_flat)
    row_base = np.repeat(offsets[:-1], counts_all)
    alias_ids = (
        nbr_flat[row_base + alias_local].astype(np.int32)
        if e else np.zeros(0, np.int32)
    )
    # zero-total rows are UNSAMPLEABLE (host engine fills the default
    # node); the native build already made their tables uniform, the
    # mask keeps the contract
    csum_z = np.concatenate(
        [[0.0], np.cumsum(w_flat, dtype=np.float64)]
    )
    sums = csum_z[offsets[1:]] - csum_z[offsets[:-1]]
    sampleable = (counts_all > 0) & (sums > 0)
    sampleable[default] = False
    max_deg = int(counts_all.max()) if len(counts_all) else 0
    tables = {
        "off": offsets[:-1].astype(np.int32),
        "deg": counts_all.astype(np.int32),
        "nbr": nbr_flat.astype(np.int32),
        "alias": alias_ids,
        "prob": prob,
        "sampleable": sampleable,
        "bisect_steps": np.zeros(max(max_deg.bit_length(), 1), np.int8),
    }
    nbytes = sum(a.nbytes for a in tables.values())
    devprof.record_alias_tables(e, max_deg, nbytes)
    log.info(
        "alias tables for edge types %s: %d edges, longest row %d, "
        "%.3f GB", list(edge_types), e, max_deg, nbytes / 1e9,
    )
    return tables


def _alias_sample_neighbor(adj: dict, nodes, key, count: int):
    """Exact CSR-alias draw: j ~ U[0, deg), keep nbr[off+j] with
    prob[off+j] else alias[off+j]. Same distribution as the padded-slab
    compare-sum draw but over the FULL neighbor list — no truncation —
    at O(1) ops and 4 gathers per draw."""
    n_rows = adj["off"].shape[0]
    default = n_rows - 1
    # tolerate plain-numpy consts (tests build them host-side; traced
    # callers pass device arrays already)
    offs, degs, probs, nbrs, aliases, ok_rows = (
        jnp.asarray(adj[k])
        for k in ("off", "deg", "prob", "nbr", "alias", "sampleable")
    )
    nodes = jnp.asarray(nodes, dtype=jnp.int32)
    nodes = jnp.where(nodes < 0, default, jnp.minimum(nodes, default))
    deg = degs[nodes]                              # [M]
    off = offs[nodes]
    k1, k2 = jax.random.split(key)
    u1 = jax.random.uniform(k1, (*nodes.shape, count))
    u2 = jax.random.uniform(k2, (*nodes.shape, count))
    j = jnp.minimum(
        (u1 * deg[..., None]).astype(jnp.int32),
        jnp.maximum(deg[..., None] - 1, 0),
    )
    e = probs.shape[0]
    if e == 0:  # no edges of these types at all: everything defaults
        return jnp.full((*nodes.shape, count), default, jnp.int32)
    # empty rows at the CSR's end have off == E; their draws are masked
    # to the default below, so clamping the slot only prevents the OOB
    slot = jnp.minimum(off[..., None] + j, e - 1)
    pick = jnp.where(u2 < probs[slot], nbrs[slot], aliases[slot])
    ok = ok_rows[nodes] & (deg > 0)
    return jnp.where(ok[..., None], pick, default)


SEG = 1 << 16  # two-level draw segment size (the typed negative sampler,
# build_typed_node_sampler / sample_node_with_src): device arrays are float32
# (jax x32), so a SINGLE cumulative over ~16M comparably-weighted nodes
# collides at float32 resolution (spacing near 1.0 is 2^-24) and tail
# nodes silently get probability 0. Normalizing the cumulative WITHIN
# 2^16-node segments keeps adjacent steps >= ~2^-16 (always
# representable), and the segment-level cumulative only needs one value
# per 65536 nodes — resolution holds to ~2^36 nodes. Adjacency rows
# never hit this: W stays small.


def _segment_cum(weights: np.ndarray, seg: int | None = None):
    """(seg_cum [S] f32, within [M] f32): float64 host cumsum split into
    ceil(M/seg) segments — seg_cum is the normalized cumulative over
    segment totals, within is the cumulative normalized inside each
    segment, last entry of every segment pinned to exactly 1.0 so u < 1
    always lands in-segment. All weights must be > 0 (filtered by the
    callers), so every segment total is positive."""
    if seg is None:
        seg = SEG  # module attr read at call time: tests shrink it
    w = weights.astype(np.float64)
    m = len(w)
    starts = np.arange(0, m, seg)
    seg_tot = np.add.reduceat(w, starts)
    seg_cum = np.cumsum(seg_tot)
    seg_cum /= seg_cum[-1]
    seg_cum[-1] = 1.0
    cum = np.cumsum(w)
    base = np.concatenate([[0.0], np.cumsum(seg_tot)])
    seg_idx = np.arange(m) // seg
    within = (cum - base[seg_idx]) / seg_tot[seg_idx]
    within[np.minimum(starts + seg, m) - 1] = 1.0  # pin segment ends
    return seg_cum.astype(np.float32), within.astype(np.float32)


def _bisect_first_ge(cum, lo, hi, u, steps: int):
    """Vectorized first index in [lo, hi) with cum[idx] >= u (the
    fixed-depth binary search shared by the two-level draws; lo/hi/u are
    broadcast-compatible int32/float arrays)."""
    M = max(int(cum.shape[0]), 1)
    for _ in range(steps):
        active = lo < hi
        # lo + (hi - lo)//2, NOT (lo + hi)//2: int32 lo+hi wraps
        # negative for rows near the end of a >2^30-entry table (a size
        # build_alias_adjacency permits), silently corrupting the search
        mid = lo + (hi - lo) // 2
        go_right = cum[jnp.clip(mid, 0, M - 1)] < u
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return jnp.clip(lo, 0, M - 1)


def _export_node_arrays(graph, max_id: int, need_types: bool,
                        chunk: int = 1 << 20):
    """Chunked node_weights (+ node_types) export over [0, max_id]: keeps
    each remote-mode RPC reply bounded (weights/types work in remote mode
    too — one kNodeWeight/kNodeType scatter per shard per chunk), and
    costs local mode nothing."""
    w_parts, t_parts = [], []
    for lo in range(0, max_id + 1, chunk):
        ids = np.arange(lo, min(lo + chunk, max_id + 1), dtype=np.int64)
        w_parts.append(graph.node_weights(ids))
        if need_types:
            t_parts.append(graph.node_types(ids))
    weights = (
        np.concatenate(w_parts) if w_parts else np.zeros(0, np.float32)
    )
    types = (
        (np.concatenate(t_parts) if t_parts else np.zeros(0, np.int32))
        if need_types
        else None
    )
    return weights, types


@setup_spanned("setup_adjacency")
def build_node_sampler(graph, node_type: int = -1, max_id: int = 0) -> dict:
    """Weighted global node sampler for one node type (-1 = all types:
    with-replacement draws over all weights give the marginal of the
    reference's pick-a-type-then-a-node, compact_graph.cc:32-56).

    Returns one Walker alias table, as the reference builds one per node
    type (CompactGraph::BuildGlobalSampler, compact_graph.cc:74-104):
    {"ids": [M] int32, "prob": [M] float32, "alias": [M] int32 (slots
    of this table)} over the matching nodes of weight > 0, sorted by id
    for determinism. Three one-dimensional tables, 12 B a node; stacked
    as [M, 3] the minor dimension would pad to 128 lanes on the chip.
    Each prob is its own threshold in [0, 1], so nothing accumulates
    and P(node) = w / total holds at any M < 2^31 (a float32 cumulative
    over ~16M comparable nodes collides: see SEG). Works against local
    AND remote graphs (node_weights/node_types scatter per shard since
    round 3).
    """
    ids = np.arange(max_id + 1, dtype=np.int64)
    weights, types = _export_node_arrays(graph, max_id, node_type != -1)
    if node_type != -1:
        mask = types == node_type
        ids, weights = ids[mask], weights[mask]
    keep = weights > 0
    ids, weights = ids[keep], weights[keep]
    m = len(ids)
    if m == 0:
        raise ValueError(f"no nodes of type {node_type} with weight > 0")
    if m >= 1 << 31:
        raise ValueError(
            f"node sampler needs int32 slots: {m} nodes; shard the graph "
            "first"
        )
    prob, alias = _build_alias_rows(np.array([0, m], np.int64), weights)
    return {"ids": ids.astype(np.int32), "prob": prob, "alias": alias}


# ---- jit-side sampling ----


def sample_node(sampler: dict, key, count: int):
    """[count] int32 nodes drawn weight-proportionally on device: one
    alias draw each (the reference's SampleNode over its
    FastWeightedCollection). An INTEGER slot i ~ U[0, M) — above 2^24
    entries floor(u * M) of a float32 uniform skips slots — then keep i
    with prob[i], else take alias[i]: three gathers of ``count``
    elements, whatever the weights."""
    # tolerate plain-numpy tables (tests build them host-side; traced
    # callers pass device arrays already)
    ids, prob, alias = (
        jnp.asarray(sampler[k]) for k in ("ids", "prob", "alias")
    )
    m = int(ids.shape[0])
    _log_route(f"sample_node {count}", "alias draw", f"{m} entries")
    k1, k2 = jax.random.split(key)
    i = jax.random.randint(k1, (count,), 0, m)
    u = jax.random.uniform(k2, (count,))
    return ids[jnp.where(u < prob[i], i, alias[i])]


_KERNEL_MESH = None  # (Mesh, data_axis) set by set_kernel_mesh


def set_kernel_mesh(mesh, axis: str = "data") -> None:
    """Route eligible packed-slab draws through the Pallas kernel PER
    SHARD of ``mesh`` (shard_map over ``axis``) — the SPMD composition
    plain pjit cannot express. Call with None to clear. Callers normally
    go through kernel_mesh_scope."""
    global _KERNEL_MESH
    _KERNEL_MESH = None if mesh is None else (mesh, axis)


def kernel_mesh():
    return _KERNEL_MESH


@contextlib.contextmanager
def kernel_mesh_scope(mesh, axis: str = "data"):
    """Register ``mesh`` for per-shard kernel draws while the block runs
    and restore the previous registration after. Only a multi-device
    TPU mesh registers (a single device calls the kernel directly;
    other backends have no kernel), anything else CLEARS — so a mesh
    left by an outer or earlier caller can never route this block's
    draws. run_loop.main and train.train/evaluate/save_embedding wrap
    their work in it: init_state (the pack decision) and the first call
    of each jitted step (the routing decision) must both happen
    inside."""
    from euler_tpu.graph import pallas_sampling

    global _KERNEL_MESH
    prev = _KERNEL_MESH
    use = (
        mesh is not None
        and mesh.size > 1
        and pallas_sampling.sharded_available()
    )
    set_kernel_mesh(mesh if use else None, axis)
    try:
        yield
    finally:
        _KERNEL_MESH = prev


@functools.lru_cache(maxsize=256)
def _log_route(draw: str, path: str, why: str) -> None:
    """One line per distinct draw shape and outcome: which path a draw
    took and why. Called while tracing, so a jitted step says once
    whether it holds the chained kernel, the per-hop kernel, the XLA
    chain or the alias draw — a shape that misses a kernel budget or a
    slab that was never packed is visible, not silent."""
    log.info("draw path: %s -> %s (%s)", draw, path, why)


def no_kernel_why() -> str:
    """Why pallas_sampling.available() said no, for the route log."""
    from euler_tpu.graph import pallas_sampling

    if pallas_sampling._force_flag() is False:
        return "EULER_TPU_PALLAS_SAMPLING=0"
    return (
        f"backend {jax.default_backend()}, {jax.device_count()} "
        "device(s), no kernel mesh registered"
    )


def sample_neighbor(adj: dict, nodes, key, count: int):
    """[len(nodes), count] int32 weighted neighbor draws (replacement).

    Exact CompactNode semantics: per draw, pick the first slot whose
    cumulative weight exceeds u. Nodes with no matching neighbors (and
    the default row) yield the default node.

    When the adjacency carries a "packed" slab (added by
    base.Model.add_sampling_consts on a TPU backend), the draw runs as
    one fused Pallas kernel instead of this op chain — same
    distribution, ~3x faster at bench dims (graph/pallas_sampling.py).
    On a single device the kernel is called directly; under a mesh
    registered via set_kernel_mesh it runs per-shard through shard_map.

    Alias adjacencies (build_alias_adjacency — flat-CSR alias tables,
    exact over the full neighbor list, the heavy-tail form) dispatch on
    their "off" key to the O(1) alias draw instead of the slab chain.
    """
    from euler_tpu.graph import pallas_sampling

    m = int(np.prod(jnp.shape(nodes)))
    draw = f"neighbor draw {m}x{count}"
    if "off" in adj:
        _log_route(draw, "alias draw", "flat-CSR alias adjacency")
        return _alias_sample_neighbor(adj, nodes, key, count)

    why = "adjacency has no packed slab"
    if "packed" in adj:
        # kernel seed, shared by both routes: two independent int31
        # words -> 62 bits of the key's entropy reach the core PRNG (a
        # single int31 seed would birthday-collide across long runs,
        # replaying identical on-core streams)
        def kernel_seed():
            return jax.random.randint(
                key, (2,), 0, jnp.iinfo(jnp.int32).max
            )

        if _KERNEL_MESH is not None:
            mesh, axis = _KERNEL_MESH
            n_sh = mesh.shape[axis]
            if m == 0 or m % n_sh:
                why = f"{m} rows do not divide {n_sh} '{axis}' shards"
            elif not pallas_sampling.eligible(m // n_sh, count):
                why = (
                    f"per-shard draw {m // n_sh}x{count} exceeds the "
                    "kernel budgets (pallas_sampling.eligible)"
                )
            else:
                _log_route(draw, "per-hop Pallas kernel",
                           f"per shard over {n_sh} '{axis}' shards")
                return pallas_sampling.sample_neighbor_sharded(
                    adj, nodes, kernel_seed(), count, mesh, axis
                )
        elif not pallas_sampling.eligible(m, count):
            why = "draw exceeds the kernel budgets (pallas_sampling.eligible)"
        elif not pallas_sampling.available():
            # available() (single-device unless force-flagged) guards
            # consts that carry a packed slab from a multi-device build:
            # outside a kernel mesh the unsharded pallas_call under pjit
            # would be the exact composition the module warns about
            why = no_kernel_why()
        else:
            _log_route(draw, "per-hop Pallas kernel", "single device")
            return pallas_sampling.sample_neighbor(
                adj, nodes, kernel_seed(), count
            )
    _log_route(draw, "XLA draw chain", why)
    nodes = jnp.asarray(nodes, dtype=jnp.int32)
    # unknown ids sample the default node: negatives and past-the-slab
    # ids map to the default row on BOTH paths (the kernel clamps the
    # same way; a bare numpy-style wrap would send -2 to a real row)
    n_rows = adj["nbr"].shape[0]
    nodes = jnp.where(nodes < 0, n_rows - 1, jnp.minimum(nodes, n_rows - 1))
    cum = adj["cum"][nodes]                       # [M, W]
    u = jax.random.uniform(key, (*nodes.shape, count))
    # index = #thresholds strictly below u  (u < cum[0] -> 0, ...)
    idx = (u[..., None] >= cum[..., None, :]).sum(-1)
    idx = jnp.clip(idx, 0, adj["nbr"].shape[1] - 1)
    out = jnp.take_along_axis(adj["nbr"][nodes], idx, axis=-1)
    # rows with zero total weight have neighbors but no sampling mass:
    # the host engine fills the default node there
    default = adj["nbr"].shape[0] - 1
    return jnp.where(adj["sampleable"][nodes][..., None], out, default)


def random_walk(adj, roots, key, walk_len: int):
    """[len(roots), walk_len+1] int32 walks sampled on device (column 0 =
    start). Uniform-or-weighted per-step draws — the p=q=1 fast path of
    the reference's biased walk (euler/client/graph.cc:196-199); the
    biased p/q merge stays host-side. Dead ends chain into the default
    row and stay there, like the host walk's default_node fill.

    ``adj`` is one adjacency dict (homogeneous walk) or a per-step list
    of walk_len dicts (heterogeneous metapath walk, the LsHNE pattern)."""
    adjs = adj if isinstance(adj, (list, tuple)) else [adj] * walk_len
    if len(adjs) != walk_len:
        raise ValueError(
            f"metapath walk needs {walk_len} per-step adjacencies, "
            f"got {len(adjs)}"
        )
    cur = jnp.asarray(roots, dtype=jnp.int32).reshape(-1)
    cols = [cur]
    for i in range(walk_len):
        cur = sample_neighbor(
            adjs[i], cur, jax.random.fold_in(key, i), 1
        )[:, 0]
        cols.append(cur)
    return jnp.stack(cols, axis=1)


def biased_random_walk(adj, roots, key, walk_len: int, p: float, q: float):
    """[len(roots), walk_len+1] int32 node2vec-biased walks on device
    (reference euler/client/graph.cc:120-151 BuildWeights: candidate
    weights scaled by 1/p when the candidate IS the parent [d_tx=0], 1
    when the candidate is a neighbor of the parent [d_tx=1], 1/q
    otherwise [d_tx=2], then a weighted draw over the rescaled row).

    ``adj`` MUST be built with build_adjacency(..., sorted=True): the
    d_tx=1 membership test is a per-row binary search of the current
    node's candidates in the parent's id-sorted neighbor row. Step 0 has
    no parent and takes the plain weighted draw, exactly like the host
    walk. Dead ends chain into the default row and stay there.

    With max_degree truncation the parent's slab row holds only its
    heaviest W neighbors, so a dropped real neighbor classifies as
    d_tx=2 (1/q) instead of d_tx=1 — a bias distortion on top of the
    truncated sampling support. MEASURED (PERF.md walk-distortion
    study): on a heavy-tail graph, hub-parent steps sit at mean total
    variation 0.35 from the exact distribution even at W=512 — so when
    p/q matter, either size W to the observed max degree or keep the
    walk on the host path (exact reference semantics).
    """
    nbr, cum = adj["nbr"], adj["cum"]
    deg, sampleable = adj["deg"], adj["sampleable"]
    default = nbr.shape[0] - 1
    W = nbr.shape[1]
    cur = jnp.asarray(roots, dtype=jnp.int32).reshape(-1)
    parent = jnp.full_like(cur, default)
    prow = None  # parent's neighbor row = previous step's cand gather
    cols = [cur]
    slot = jnp.arange(W)
    for step in range(walk_len):
        cand = nbr[cur]                                    # [M, W]
        c = cum[cur]
        # per-slot weights from the normalized cumulative row; padding
        # and unsampleable rows zero out
        w = jnp.concatenate([c[:, :1], c[:, 1:] - c[:, :-1]], axis=1)
        w = w * (slot[None, :] < deg[cur][:, None])
        w = w * sampleable[cur][:, None]
        if prow is not None:
            # d_tx: parent-row membership via binary search (rows
            # sorted); step 0 skips this — no parent, and a uniform 1/q
            # would cancel in the normalization anyway
            pos = jax.vmap(
                lambda row, cds: jnp.searchsorted(row, cds)
            )(prow, cand)
            hit = jnp.take_along_axis(
                prow, jnp.clip(pos, 0, W - 1), axis=1
            ) == cand
            in_parent_nbr = hit & (pos < deg[parent][:, None])
            is_parent = cand == parent[:, None]
            # d_tx=1 wins over d_tx=0 when the parent has a self-loop:
            # the reference merge's equality branch runs before its
            # candidate<parent check (euler/client/graph.cc:126-140),
            # so a candidate that IS the parent AND appears in the
            # parent's neighbor list keeps weight w, not w/p
            scale = jnp.where(
                in_parent_nbr, 1.0,
                jnp.where(is_parent, 1.0 / p, 1.0 / q),
            )
            w = w * scale
        cw = jnp.cumsum(w, axis=1)
        total = cw[:, -1:]
        cw = cw / jnp.maximum(total, 1e-30)
        u = jax.random.uniform(
            jax.random.fold_in(key, step), (cur.shape[0], 1)
        )
        idx = jnp.clip((u >= cw).sum(-1), 0, W - 1)
        nxt = jnp.take_along_axis(cand, idx[:, None], axis=1)[:, 0]
        nxt = jnp.where(total[:, 0] > 0, nxt, default)
        # next step's parent is this step's node; its neighbor row is
        # exactly this step's cand gather — no second HBM gather.
        # (Dead-ended walkers land on the default row whose weights are
        # all zero, so their scale is irrelevant.)
        parent, cur, prow = cur, nxt, cand
        cols.append(cur)
    return jnp.stack(cols, axis=1)


DEFAULT_WALK_TRIALS = 64  # rejection-walk proposal budget per step: the
# worst realistic node2vec grid point (p or q = 1/4 -> envelope M = 4,
# acceptance >= 1/16 even when every candidate is d_tx=2) leaves
# (1 - 1/16)^64 ~ 1.6% of steps falling back to the unbiased first
# draw; typical p/q near 1 accept on the first or second proposal.


def _alias_biased_step(adj, cur, parent, key, p: float, q: float,
                       trials: int):
    """One EXACT node2vec-biased transition over full neighbor lists:
    propose from the current node's alias row (unbiased, O(1)), accept
    with probability s(d_tx)/M where s is the reference's d_tx scale
    (1 if the candidate is a parent neighbor — which wins on parent
    self-loops, matching the reference merge's branch order,
    euler/client/graph.cc:126-140 — else 1/p for the parent itself,
    else 1/q) and M = max(1/p, 1, 1/q). Accepted draws are distributed
    exactly ∝ w(y)*s(y); exhausting ``trials`` proposals falls back to
    the first (unbiased) draw. The d_tx membership test is a fixed-depth
    bisection of each candidate in the parent's id-sorted CSR row —
    ``adj`` MUST come from build_alias_adjacency(..., sorted=True).

    Returns [len(cur)] int32 next nodes (dead ends -> default row)."""
    offs, degs, probs, nbrs, aliases, ok_rows = (
        jnp.asarray(adj[k])
        for k in ("off", "deg", "prob", "nbr", "alias", "sampleable")
    )
    n_rows = offs.shape[0]
    default = n_rows - 1
    e = int(probs.shape[0])
    b = cur.shape[0]
    if e == 0:
        return jnp.full((b,), default, jnp.int32)
    k1, k2, k3 = jax.random.split(key, 3)
    deg = degs[cur]
    off = offs[cur]
    u1 = jax.random.uniform(k1, (b, trials))
    u2 = jax.random.uniform(k2, (b, trials))
    j = jnp.minimum(
        (u1 * deg[:, None]).astype(jnp.int32),
        jnp.maximum(deg[:, None] - 1, 0),
    )
    slot = jnp.minimum(off[:, None] + j, e - 1)
    cand = jnp.where(u2 < probs[slot], nbrs[slot], aliases[slot])
    # membership of each candidate in the parent's id-sorted CSR row:
    # first flat index in [plo, phi) with nbrs[idx] >= cand. Depth
    # covers the largest possible row (deg <= E), converged lanes
    # no-op; a lo that converged to phi (or ran past a last-row phi==E)
    # can never satisfy the equality check below.
    plo = jnp.broadcast_to(offs[parent][:, None], (b, trials))
    phi = jnp.broadcast_to(
        (offs[parent] + degs[parent])[:, None], (b, trials)
    )
    # bisection depth: the max ROW width bound when the builder recorded
    # it (shape-carried static — log2(58k)=16 vs log2(114M)=27 on the
    # heavy-tail flagship), else the always-safe log2(E)
    steps = (
        int(adj["bisect_steps"].shape[0])
        if "bisect_steps" in adj
        else max(e.bit_length(), 1)
    )
    pos = _bisect_first_ge(nbrs, plo, phi, cand, steps)
    hit = (nbrs[jnp.clip(pos, 0, e - 1)] == cand) & (pos < phi)
    is_par = cand == parent[:, None]
    s = jnp.where(hit, 1.0, jnp.where(is_par, 1.0 / p, 1.0 / q))
    m = max(1.0 / p, 1.0, 1.0 / q)
    accept = jax.random.uniform(k3, (b, trials)) < s / m
    # first accepted proposal; none accepted -> index 0, the first
    # (unbiased) draw — the bounded-retry fallback
    first = jnp.argmax(accept, axis=1)
    pick = jnp.take_along_axis(cand, first[:, None], axis=1)[:, 0]
    ok = ok_rows[cur] & (deg > 0)
    return jnp.where(ok, pick, default)


def alias_biased_random_walk(adj, roots, key, walk_len: int, p: float,
                             q: float, trials: int | None = None):
    """[len(roots), walk_len+1] int32 node2vec-biased walks sampled on
    device EXACTLY over the FULL neighbor lists — the heavy-tail form of
    biased_random_walk. Where the padded-slab walk must truncate hub
    rows (measured mean TVD 0.35 from the exact distribution at W=512,
    PERF.md walk study), this draws proposals from the flat-CSR alias
    tables (no truncation, O(E) memory) and rejection-corrects them to
    the reference's d_tx-scaled distribution
    (euler/client/graph.cc:120-151): P(accept y) = s(y)/M, leaving
    accepted candidates ∝ w(y)*s(y) exactly.

    ``adj`` MUST be built with build_alias_adjacency(..., sorted=True)
    (the membership bisection needs id-sorted rows). Step 0 has no
    parent and takes the plain alias draw, exactly like the host walk;
    dead ends chain into the default row and stay there. ``trials``
    bounds the per-step proposal budget (default DEFAULT_WALK_TRIALS);
    an exhausted step falls back to its first unbiased draw, a <~2%
    event at the worst realistic p/q (see DEFAULT_WALK_TRIALS)."""
    if trials is None:
        trials = DEFAULT_WALK_TRIALS
    n_rows = adj["off"].shape[0]
    default = n_rows - 1
    cur = jnp.asarray(roots, dtype=jnp.int32).reshape(-1)
    cur = jnp.where(cur < 0, default, jnp.minimum(cur, default))
    parent = jnp.full_like(cur, default)
    cols = [cur]
    for step in range(walk_len):
        k = jax.random.fold_in(key, step)
        if step == 0:
            # no parent: plain exact alias draw (the host walk's first
            # hop is the same unbiased draw)
            nxt = _alias_sample_neighbor(adj, cur, k, 1)[:, 0]
        else:
            nxt = _alias_biased_step(adj, cur, parent, k, p, q, trials)
        parent, cur = cur, nxt
        cols.append(cur)
    return jnp.stack(cols, axis=1)


@setup_spanned("setup_adjacency")
def build_typed_node_sampler(graph, num_types: int, max_id: int) -> dict:
    """Per-node-type weighted samplers packed into one flat layout for the
    device sample_node_with_src (reference sample_node_with_src semantics:
    each source draws negatives from ITS node type's global sampler,
    tf_euler euler_ops/sample_ops.py:39-67).

    Returns {"ids": [M] int32 (nodes sorted by type), "cum": [M] float32
    (cumulative weights normalized within SEG-node sub-segments of each
    type: the two-level cumulative layout of _segment_cum, so a single
    type beyond ~16M nodes keeps exact float32 draws; the untyped
    build_node_sampler is an alias table instead), "off": [T+1]
    int32 type offsets into ids, "seg_cum": [G] float32 (per-type
    normalized cumulative over sub-segment totals), "tseg_off": [T+1]
    int32 type offsets into seg_cum, "types": [N+2] int32 node-type
    lookup (-1 for unknown/default)}.
    """
    all_ids = np.arange(max_id + 1, dtype=np.int64)
    weights, types = _export_node_arrays(graph, max_id, need_types=True)
    type_table = np.full(max_id + 2, -1, dtype=np.int32)
    type_table[: max_id + 1] = types

    ids_out: list[np.ndarray] = []
    cum_out: list[np.ndarray] = []
    seg_out: list[np.ndarray] = []
    off = [0]
    tseg_off = [0]
    empty_types = []
    for t in range(num_types):
        mask = (types == t) & (weights > 0)
        tids = all_ids[mask]
        tw = weights[mask]
        if len(tids):
            seg_cum, within = _segment_cum(tw)
        else:
            seg_cum, within = np.zeros(0, np.float32), np.zeros(0, np.float32)
            if (types == t).any():
                empty_types.append(t)
        ids_out.append(tids)
        cum_out.append(within)
        seg_out.append(seg_cum)
        off.append(off[-1] + len(tids))
        tseg_off.append(tseg_off[-1] + len(seg_cum))
    if empty_types:
        import warnings

        warnings.warn(
            f"build_typed_node_sampler: node types {empty_types} exist "
            "but have no weight>0 nodes; sources of these types will "
            "draw the default (zero-feature) node as negatives — give "
            "those nodes sampling weight or use host-side negatives"
        )
    ids_cat = (
        np.concatenate(ids_out) if off[-1] else np.zeros(0, np.int64)
    )
    cum_cat = (
        np.concatenate(cum_out) if off[-1] else np.zeros(0, np.float32)
    )
    seg_cat = (
        np.concatenate(seg_out) if tseg_off[-1] else np.zeros(0, np.float32)
    )
    return {
        "ids": ids_cat.astype(np.int32),
        "cum": cum_cat.astype(np.float32),
        "off": np.asarray(off, dtype=np.int32),
        "seg_cum": seg_cat,
        "tseg_off": np.asarray(tseg_off, dtype=np.int32),
        "types": type_table,
    }


def sample_node_with_src(tsampler: dict, src, key, count: int):
    """[len(src), count] int32 negatives: each source draws from its own
    node type's weighted sampler (device analog of the native
    eg_sample_node_with_src). Sources of unknown/default type fall back
    to type 0's segment. Two fixed-depth vectorized bisections per draw
    (the two-level layout of build_typed_node_sampler): u1 picks a SEG
    sub-segment within the type, u2 a node within the sub-segment —
    float32-exact past the ~16M-nodes-per-type cliff."""
    src = jnp.asarray(src, dtype=jnp.int32).reshape(-1)
    t = tsampler["types"][src]
    # clamp out-of-range types into the sampler's range (mirrors the
    # TypedDense tower clamping): unknown (<0) falls to type 0, types
    # beyond the configured count to the last segment — never the
    # accidental empty-segment path, which would silently train against
    # all-default (zero-feature) negatives
    num_types = tsampler["off"].shape[0] - 1
    t = jnp.clip(t, 0, num_types - 1)
    shape = (src.shape[0], count)
    node_lo = tsampler["off"][t][:, None].astype(jnp.int32)
    node_hi = tsampler["off"][t + 1][:, None].astype(jnp.int32)
    empty = jnp.broadcast_to(node_hi <= node_lo, shape)
    k1, k2 = jax.random.split(key)
    # level 1: sub-segment within the type's seg_cum span
    g_lo = jnp.broadcast_to(
        tsampler["tseg_off"][t][:, None].astype(jnp.int32), shape
    )
    g_hi = jnp.broadcast_to(
        tsampler["tseg_off"][t + 1][:, None].astype(jnp.int32), shape
    )
    G = max(int(tsampler["seg_cum"].shape[0]), 1)
    g = _bisect_first_ge(
        tsampler["seg_cum"], g_lo, g_hi,
        jax.random.uniform(k1, shape), max(G.bit_length(), 1),
    )
    # level 2: node within sub-segment g (sub-segments of a type are
    # SEG-aligned from the type's node offset)
    j = g - tsampler["tseg_off"][t][:, None]
    lo = (node_lo + j * SEG).astype(jnp.int32)
    hi = jnp.minimum(lo + SEG, node_hi).astype(jnp.int32)
    M = max(int(tsampler["cum"].shape[0]), 1)
    idx = _bisect_first_ge(
        tsampler["cum"], lo, hi, jax.random.uniform(k2, shape),
        max(min(M, SEG).bit_length(), 1),
    )
    out = tsampler["ids"][idx]
    default = tsampler["types"].shape[0] - 1
    return jnp.where(empty, default, out)


@functools.lru_cache(maxsize=64)
def _log_expand_route(sizes: tuple, ranked: tuple) -> None:
    """One line per distinct expansion shape, said while tracing (as the
    draw paths say theirs): the padded slots every hop of the full-
    neighbourhood expansion works on, and each hop whose cap can bind
    (``ranked``: ``(hop, cap, slots)``), whose mask reads the rank. A
    line that names no hop so has no rank in its masks."""
    binds = "".join(
        f"; hop {h}: cap {cap} < {slots} slots, ranked"
        for h, cap, slots in ranked
    )
    log.info(
        "expand path: full neighbourhood %s slots (sort dedup, XLA%s)",
        " -> ".join(map(str, sizes)), binds,
    )


@jax.named_scope("expand")
def multi_hop_neighbor(adjs, roots, node_caps):
    """Full-neighbor multi-hop expansion with per-hop dedup, inside jit
    (device analog of ops.get_multi_hop_neighbor; deterministic — no
    sampling, no RNG).

    Per hop: gather every current node's full slab row, dedup the
    neighbor ids with a sort-based dense-rank (jnp.unique's size=
    truncation leaves inverse indices unspecified, so rank is computed
    explicitly), and emit the same padded COO the host path produces —
    {"nodes": [cap] (default-padded, sorted like np.unique),
    "src"/"dst": [C*W] indices into the current/next hop arrays ("src" is
    repeat(arange(C), W): a numpy constant of the static shapes, by which
    nn/sparse_aggregators.py knows the list for regular and sums its rows
    in place of a segment sum), "mask": [C*W] 1.0 on real edges, "w":
    alias of mask (the sparse aggregators use binary adjacency)} — and
    beside it "ids": [C*W] int32, every slot's own neighbour id (the
    default id on a padded slot), which is ``nodes[dst]`` on every slot
    whose rank fits under the cap: a reader of the slots' ids takes them
    from here and leaves the set, the sort and the rank dead where
    nothing else reads them (models/gcn.py ``_slot_rows``).

    Whether a hop's cap can bind is decided from the static shapes: with
    ``cap >= C*W`` no rank reaches the cap (a hop has at most C*W unique
    ids), so the mask is ``id != default`` with no rank term and
    "overflow" is a constant 0; only where ``cap < C*W`` does the mask
    read the rank and the overflow count it. The route log says which
    hops are ranked (``expand path: ...``).

    Divergences from the host path, both graceful where the host raises:
    rows beyond the slab's max_degree were already truncated to their
    heaviest neighbors at build_adjacency time, and a hop with more than
    node_caps[h] unique neighbors drops the largest-id overflow nodes
    (their edges are masked out) instead of raising — caps must be sized
    generously, exactly like the host's max_nodes_per_hop. The drop is
    counted, not silent: each hop carries "overflow", the number of its
    unique neighbors that found no room under the cap (0 where the cap
    holds), and "edges", the mask's sum (the true edges that entered).
    """
    cur = jnp.asarray(roots, dtype=jnp.int32).reshape(-1)
    sizes = [cur.shape[0]]
    ranked = []
    hops = []
    for h, (adj, cap) in enumerate(zip(adjs, node_caps), 1):
        default = adj["nbr"].shape[0] - 1
        W = adj["nbr"].shape[1]
        C = cur.shape[0]
        nbrs = adj["nbr"][cur]                            # [C, W]
        valid = jnp.arange(W)[None, :] < adj["deg"][cur][:, None]
        flat = jnp.where(valid, nbrs, default).reshape(-1)  # [C*W]
        # sort-based dedup: dense rank of each flat entry among the
        # sorted unique ids. The default node is the largest id, so
        # padding entries sort last and never displace real nodes.
        order = jnp.argsort(flat)
        s = flat[order]
        first = jnp.concatenate(
            [jnp.ones(1, dtype=bool), s[1:] != s[:-1]]
        )
        rank_sorted = jnp.cumsum(first) - 1               # [C*W]
        rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
        # overflow ranks (>= cap) scatter out of bounds and are dropped
        nodes = (
            jnp.full((cap,), default, dtype=jnp.int32)
            .at[rank_sorted]
            .set(s.astype(jnp.int32), mode="drop")
        )
        # a constant of the static shapes, not a traced repeat: the
        # sparse aggregators see a regular list in it and sum its rows
        src = np.repeat(np.arange(C, dtype=np.int32), W)
        dst = jnp.clip(rank, 0, cap - 1).astype(jnp.int32)
        live = flat != default  # a padded slot holds the default id
        if cap >= C * W:
            # at most C*W unique ids: no rank reaches the cap
            overflow = jnp.int32(0)
        else:
            live = live & (rank < cap)
            # unique real ids of this hop (padding entries all hold the
            # default id) against the room the cap gives them
            unique = jnp.sum(first & (s != default), dtype=jnp.int32)
            overflow = jnp.maximum(unique - cap, 0)
            ranked.append((h, cap, C * W))
        mask = live.astype(jnp.float32)
        hops.append(
            {
                "nodes": nodes,
                "src": src,
                "dst": dst,
                "ids": flat,
                "mask": mask,
                "w": mask,
                "edges": jnp.sum(mask),
                "overflow": overflow,
            }
        )
        sizes.append(C * W)
        cur = nodes
    _log_expand_route(tuple(sizes), tuple(ranked))
    return hops


@jax.named_scope("draw")
def sample_fanout(adjs, roots, key, counts):
    """Fused multi-hop device fanout (host analog: graph.sample_fanout).

    adjs: one adjacency dict per hop (repeat the same dict for a
    homogeneous metapath). Returns [roots, hop1, hop2, ...] flat id
    arrays, hop h sized prod(counts[:h+1]) * len(roots).

    Two-hop fanouts over packed slabs route through the CHAINED kernel
    (pallas_sampling.sample_fanout2): both hops in one program, the
    data-dependent hop-2 row DMAs hidden behind the next stage's hop-1
    compute — directly on a single device, per-shard via shard_map when
    a kernel mesh is registered. Everything else keeps the per-hop loop
    (whose single-hop draws still use the kernel when eligible).
    """
    if len(adjs) != len(counts):
        raise ValueError(
            f"sample_fanout needs one adjacency per hop: got {len(adjs)} "
            f"adjacencies for {len(counts)} fanout counts"
        )
    roots = jnp.asarray(roots, dtype=jnp.int32).reshape(-1)

    chained = _sample_fanout2_route(adjs, roots, key, counts)
    if chained is not None:
        return chained

    out = [roots]
    cur = roots
    for h, (adj, c) in enumerate(zip(adjs, counts)):
        k = jax.random.fold_in(key, h)
        cur = sample_neighbor(adj, cur, k, c).reshape(-1)
        out.append(cur)
    return out


def _sample_fanout2_route(adjs, roots, key, counts):
    """[roots, hop1, hop2] via the chained kernel when this fanout
    qualifies, else None (caller keeps the per-hop loop, whose draws
    log their own path). Mirrors sample_neighbor's routing: direct
    kernel on a single device (available()), shard_map per-shard when a
    kernel mesh is registered."""
    from euler_tpu.graph import pallas_sampling

    m = int(roots.shape[0])
    draw = f"fanout {m}x{'x'.join(map(str, counts))}"

    def per_hop(why):
        """Say why the chained kernel is not taken; the route is None."""
        _log_route(draw, "per-hop draws", why)

    if len(adjs) != 2:
        return per_hop(f"{len(adjs)} hops (the chained kernel fuses 2)")
    a1, a2 = adjs
    if "packed" not in a1 or "packed" not in a2:
        return per_hop("an adjacency has no packed slab")
    if a1["nbr"].shape[0] != a2["nbr"].shape[0]:
        return per_hop("the hops' slabs cover different id spaces")
    f1, f2 = counts
    if m == 0:
        return per_hop("no roots")
    n_rows = a1["nbr"].shape[0]
    k1 = a1["packed"].shape[0] // (2 * n_rows)
    k2 = a2["packed"].shape[0] // (2 * n_rows)

    def kernel_seed():
        return jax.random.randint(key, (2,), 0, jnp.iinfo(jnp.int32).max)

    over = "exceeds the chained kernel's budgets (pallas_sampling.eligible2)"
    if _KERNEL_MESH is not None:
        mesh, axis = _KERNEL_MESH
        n_sh = mesh.shape[axis]
        if m % n_sh:
            return per_hop(f"{m} roots do not divide {n_sh} '{axis}' shards")
        if not pallas_sampling.eligible2(m // n_sh, f1, f2, k1, k2):
            return per_hop(f"per-shard fanout {m // n_sh}x{f1}x{f2} {over}")
        _log_route(draw, "chained two-hop Pallas kernel",
                   f"per shard over {n_sh} '{axis}' shards")
        h1, h2 = pallas_sampling.sample_fanout2_sharded(
            a1, a2, roots, kernel_seed(), f1, f2, mesh, axis
        )
    elif not pallas_sampling.eligible2(m, f1, f2, k1, k2):
        return per_hop(f"fanout {over}")
    elif not pallas_sampling.available():
        return per_hop(no_kernel_why())
    else:
        _log_route(draw, "chained two-hop Pallas kernel", "single device")
        h1, h2 = pallas_sampling.sample_fanout2(
            a1, a2, roots, kernel_seed(), f1, f2
        )
    return [roots, h1.reshape(-1), h2.reshape(-1)]
