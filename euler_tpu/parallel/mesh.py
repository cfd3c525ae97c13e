"""Device mesh + sharding rules.

The TPU replacement for the reference's parameter-server data parallelism
(reference tf_euler/python/run_loop.py:371-397 ClusterSpec{ps,worker} +
replica_device_setter): parameters are replicated across the mesh, each
batch is sharded over the 'data' axis, and XLA inserts the gradient
all-reduce over ICI inside the jitted train step. No parameter servers,
no explicit gradient exchange code.

A second optional 'model' axis row-shards the big per-node tables — the
device-resident feature/label consts and the Scalable* historical-embedding
stores. This is the TPU-native version of the reference's PS-sharded
embedding tables (reference tf_euler/python/utils/embedding.py:22-67 'mod'
partitioned scatter): total table HBM scales with the model axis, and XLA
inserts the gather/scatter collectives inside the jitted step.
"""

from __future__ import annotations

import contextlib
import os

import jax
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from euler_tpu.telemetry import setup_span

# Top-level train-state keys holding [num_nodes, dim]-shaped tables that
# row-shard over the 'model' axis.
_TABLE_KEYS = ("consts", "stores", "grad_stores")
# Of those, the keys whose tables the step gathers from AND scatters into
# (donated, mutable state): their device layout is pinned row-major.
_STORE_KEYS = ("stores", "grad_stores")
# Rows contiguous, the tiling left to the device's compiler (a TPU tiles
# it T(8,128), so a 64-wide float32 row pads to one 128-lane line; a CPU
# has none). What ``describe_state`` calls rows_major.
_ROWS_MAJOR = Layout(major_to_minor=(0, 1))

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache and return its
    directory. The ONE place that decides where compiled programs are
    kept (run_loop, serve, chip_smoke.py, the benchmark's harness and
    the on-chip tests all call it before their first jit): where
    JAX_COMPILATION_CACHE_DIR is set, JAX's own handling of the variable
    stands and nothing here touches ``jax_compilation_cache_dir``;
    otherwise the cache lives at ``<checkout>/.jax_cache`` — never a
    per-run directory, which would never hit.

    Every program is kept: a sealed machine starts with nothing
    compiled and a train step is ~80 programs, not one (chip run, PR 21:
    79 compiles before the first step, 12.6 s cold, 0.3 s warm), so
    JAX's 1 s minimum compile time is dropped unless the environment
    sets JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS.

    Off (None) on a CPU backend unless the variable asks for it: XLA:CPU
    in jaxlib 0.9.0 logs a ~4 KB machine-feature mismatch on EVERY cache
    hit, on the very machine that compiled the entry, which floods
    stderr (a child whose pipe nobody drains then blocks), and nobody
    waits minutes for a CPU compile. Initializes the backend to find
    out, so call it in the process that runs the step, after
    jax.distributed.initialize."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        if jax.default_backend() == "cpu":
            return None
        d = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d


def force_cpu_devices(n_devices: int) -> None:
    """Force an n_devices-wide virtual CPU platform (the tests' mesh),
    overriding any ambient JAX_PLATFORMS / XLA_FLAGS. Must run BEFORE
    the backend initializes; raises if the backend is already up with
    too few devices."""
    import re

    opt = f"--xla_force_host_platform_device_count={n_devices}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", opt, flags
        )
    else:
        flags = (flags + " " + opt).strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if len(devs) < n_devices or devs[0].platform != "cpu":
        raise RuntimeError(
            f"{len(devs)} {devs[0].platform} devices visible after forcing "
            f"{n_devices} virtual CPU devices — the JAX backend was "
            "already initialized; call force_cpu_devices() before any "
            "jax.devices()/jit in this process"
        )


def make_mesh(
    num_devices: int | None = None,
    devices=None,
    model_parallel: int = 1,
) -> Mesh:
    """(data, model) mesh over the first num_devices devices.

    model_parallel=1 (default) is pure data parallelism; k>1 dedicates a
    k-wide 'model' axis for row-sharded tables.
    """
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    devices = np.asarray(devices)
    if len(devices) % model_parallel != 0:
        raise ValueError(
            f"{len(devices)} devices not divisible by "
            f"model_parallel={model_parallel}"
        )
    return Mesh(
        devices.reshape(-1, model_parallel), ("data", "model")
    )


def batch_sharding(mesh: Mesh, stacked: bool = False) -> NamedSharding:
    """Shard the batch dim over 'data' (replicated over 'model'): the
    leading dim, or, of batches ``stacked`` along a leading step axis,
    the second."""
    return NamedSharding(mesh, P(None, "data") if stacked else P("data"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Row-shard a [rows, dim] table over the 'model' axis."""
    return NamedSharding(mesh, P("model"))


def _model_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


def _top_key(path):
    key = path[0]
    return getattr(key, "key", getattr(key, "idx", None))


def _is_table(path, x) -> bool:
    """True for leaves under a _TABLE_KEYS top-level state key — the
    per-node tables that row-shard (and row-pad) over the model axis.
    Under consts, only the per-node lookup tables (features / labels)
    shard; device-sampling structures (adj / roots / negs and anything
    else) replicate — their cumulative-weight and alias arrays must stay
    contiguous and unpadded (zero-padding would unsort a searchsorted
    input and add slots to an alias table)."""
    name = _top_key(path)
    if name not in _TABLE_KEYS or np.ndim(x) < 1:
        return False
    if name == "consts" and len(path) > 1:
        sub = getattr(path[1], "key", getattr(path[1], "idx", None))
        if sub not in ("features", "labels", "sparse"):
            return False
    return True


def _is_store(path, x) -> bool:
    """True for the [rows, dim] leaves under ``stores`` / ``grad_stores``:
    the Scalable* historical-embedding tables."""
    return _top_key(path) in _STORE_KEYS and np.ndim(x) == 2


def state_sharding(mesh: Mesh, state):
    """Sharding pytree for a train state: params/optimizer replicated,
    per-node tables (consts, Scalable stores) row-sharded when the mesh has
    a model axis. Matches state's tree structure, for jit in_/out_shardings
    and ``put_global``.

    The store leaves (``stores``, ``grad_stores``) get a ``Format`` in the
    sharding's place: the same sharding with the device layout pinned
    rows-major. A TPU lays a [rows, dim] float32 table whose dim is no
    lane multiple column-major by default, and a step that gathers rows
    from it and scatters rows into it then copies the whole table to a
    row-major temporary and back, every step (four 1 GB copies, 9.65 of a
    10.35 ms step at Reddit's sizes: PERF.md section 6, PR 31). Pinned,
    the table is re-laid once at placement and goes round the donated
    step in place. Where rows-major is the device's own choice (a lane-
    multiple dim; any CPU) the pin is the default and changes nothing.
    The logical shape stays [rows, dim]: it is the checkpoint's format
    and what callers that hand ``train()`` a state build and read.
    ``consts`` is not pinned: its tables are read-only, the feature
    table is rows-major by its stored width (``stored_width``), and the
    narrow label table's gather is cheap beside the memory a pin costs."""
    rep = replicated_sharding(mesh)
    tab = table_sharding(mesh) if _model_axis_size(mesh) > 1 else rep

    def place(path, x):
        s = tab if _is_table(path, x) else rep
        return Format(_ROWS_MAJOR, s) if _is_store(path, x) else s

    return jax.tree_util.tree_map_with_path(place, state)


@contextlib.contextmanager
def compiles_keep_layouts(shardings):
    """Around the first call (the compile) of a program placed by
    ``shardings``: where a leaf pins a layout, the program is compiled
    here and not fetched from, or left in, the persistent compile cache.
    An executable that comes back from the cache has lost its pinned
    result layouts (jaxlib 0.9.0 on a TPU v5e; my chip runs, PR 31: the
    same re-lay and the same donated step give rows-major tables when
    compiled and column-major ones on the cache's hit, and the next call
    then refuses its own output), so the store family pays its step's
    compile in every process (about 5 s at Reddit's sizes). A pytree
    without a ``Format`` (every other family) keeps the cache."""
    pinned = any(isinstance(s, Format) for s in jax.tree.leaves(shardings))
    if not (
        pinned
        and jax.config.jax_enable_compilation_cache
        and jax.config.jax_compilation_cache_dir
    ):
        yield
        return
    from jax.experimental.compilation_cache import compilation_cache

    # the cache decides once whether it is used: it is asked anew on both
    # sides of the compile
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def pad_tables_for_mesh(state, mesh: Mesh):
    """Pad table rows (dim 0) up to a multiple of the model axis so they
    shard evenly. Extra rows are zero and never indexed (valid ids are
    <= max_id+1 < original row count). Resuming a checkpoint requires the
    same model_parallel setting, since store shapes include the padding."""
    k = _model_axis_size(mesh)
    if k <= 1:
        return state

    def pad(path, x):
        if _is_table(path, x):
            extra = (-x.shape[0]) % k
            if extra:
                return jax.numpy.pad(
                    x, [(0, extra)] + [(0, 0)] * (np.ndim(x) - 1)
                )
        return x

    with setup_span("setup_upload"):
        return jax.tree_util.tree_map_with_path(pad, state)


def _placed_as(x, s) -> bool:
    """True where ``x`` already sits on the devices as ``s`` (a sharding,
    or a ``Format`` of ``state_sharding``) asks. A pinned layout names no
    tiling, so the array's own tiling is not compared."""
    if not isinstance(x, jax.Array):
        return False
    if not isinstance(s, Format):
        return x.sharding == s
    layout = x.format.layout
    return (
        x.sharding == s.sharding
        and layout is not None
        and tuple(layout.major_to_minor) == s.layout.major_to_minor
    )


def put_global(tree, shardings, consume: bool = False):
    """device_put a host pytree onto its shardings (``state_sharding``'s
    pytree: a leaf is a sharding, or a ``Format`` that also pins the
    device layout), multi-process aware. A leaf that already sits there
    is handed back as it is.

    Single-controller: plain jax.device_put. Under jax.distributed
    (process_count > 1) the shardings span devices this process cannot
    address, so each leaf becomes a global jax.Array assembled from the
    process-local shards instead — every process must hold the SAME full
    host value (true for replicated params initialised from one PRNG
    seed and for consts derived from the same graph). This is the
    multi-host analog of the reference's parameter-server variable
    placement (reference tf_euler/python/run_loop.py:391-394).

    ``consume``: the caller gives ``tree`` up (``train()`` does: its step
    donates the state). A device array that had to be re-laid into a
    pinned layout is then freed once its copy exists, so a [rows, dim]
    store is not held twice through the run by whoever built it."""
    multi = jax.process_count() > 1
    moved = 0  # bytes of the leaves that came from the host

    def put(x, s):
        nonlocal moved
        if _placed_as(x, s):
            # already placed (e.g. a checkpoint-restored global array) —
            # np.asarray on it would crash for model-axis-sharded leaves
            # (spans non-addressable devices) and needlessly round-trip
            # everything else
            return x
        if not isinstance(x, jax.Array):
            moved += getattr(x, "nbytes", 0)
        if multi:
            host = np.asarray(x)
            with compiles_keep_layouts(s):
                return jax.make_array_from_callback(
                    host.shape, s, lambda idx: host[idx]
                )
        if not isinstance(s, Format):
            return jax.device_put(x, s)
        # onto the devices first (device_put to a Format re-lays an array
        # where it is and cannot move one), then the one layout copy,
        # where the device's own layout is another
        y = jax.device_put(x, s.sharding)
        if _placed_as(y, s):
            return y
        with compiles_keep_layouts(s):
            y = jax.device_put(y, s)
        if consume and isinstance(x, jax.Array):
            x.delete()
        return y

    # the host's part of the placement, the re-lay into a pinned layout
    # included; the copies may run on past the span's end (no fence)
    with setup_span("setup_upload") as upload:
        placed = jax.tree.map(put, tree, shardings)
        upload.nbytes = moved
    return placed


def shard_batch(batch, mesh: Mesh, stacked: bool = False):
    """Place a host batch pytree onto the mesh, batch dim sharded
    (scalars — e.g. a device-sampling seed — are replicated). A chunk of
    batches ``stacked`` along a leading step axis shards its second dim
    (``batch_sharding(mesh, stacked=True)``).

    Multi-process (jax.distributed): ``batch`` is this process's LOCAL
    shard — batch dims concatenate across processes in process order,
    so the global batch is num_processes x the local size. Scalars must
    be identical on every process (they replicate)."""
    sharding = batch_sharding(mesh, stacked)
    rep = replicated_sharding(mesh)
    if jax.process_count() > 1:
        def put(x):
            x = np.asarray(x)
            if np.ndim(x) == 0:
                return jax.make_array_from_callback(
                    x.shape, rep, lambda idx: x[idx]
                )
            return jax.make_array_from_process_local_data(sharding, x)

        return jax.tree.map(put, batch)
    return jax.tree.map(
        lambda x: jax.device_put(
            x, rep if np.ndim(x) == 0 else sharding
        ),
        batch,
    )
