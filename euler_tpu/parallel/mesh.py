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

import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Top-level train-state keys holding [num_nodes, dim]-shaped tables that
# row-shard over the 'model' axis.
_TABLE_KEYS = ("consts", "stores", "grad_stores")

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache and return its
    directory. The ONE place that decides where compiled programs are
    kept (run_loop, serve, bench.py, batch_sweep.py, chip_smoke.py and
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


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over 'data' (replicated over 'model')."""
    return NamedSharding(mesh, P("data"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Row-shard a [rows, dim] table over the 'model' axis."""
    return NamedSharding(mesh, P("model"))


def _model_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


def _is_table(path, x) -> bool:
    """True for leaves under a _TABLE_KEYS top-level state key — the
    per-node tables that row-shard (and row-pad) over the model axis.
    Under consts, only the per-node lookup tables (features / labels)
    shard; device-sampling structures (adj / roots / negs and anything
    else) replicate — their cumulative-weight arrays must stay contiguous
    and unpadded (zero-padding would unsort the searchsorted input)."""
    key = path[0]
    name = getattr(key, "key", getattr(key, "idx", None))
    if name not in _TABLE_KEYS or np.ndim(x) < 1:
        return False
    if name == "consts" and len(path) > 1:
        sub = getattr(path[1], "key", getattr(path[1], "idx", None))
        if sub not in ("features", "labels", "sparse"):
            return False
    return True


def state_sharding(mesh: Mesh, state):
    """Sharding pytree for a train state: params/optimizer replicated,
    per-node tables (consts, Scalable stores) row-sharded when the mesh has
    a model axis. Matches state's tree structure, for jit in_/out_shardings
    and device_put."""
    rep = replicated_sharding(mesh)
    if _model_axis_size(mesh) <= 1:
        return jax.tree.map(lambda _: rep, state)
    tab = table_sharding(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: tab if _is_table(path, x) else rep, state
    )


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

    return jax.tree_util.tree_map_with_path(pad, state)


def put_global(tree, shardings):
    """device_put a host pytree onto its shardings, multi-process aware.

    Single-controller: plain jax.device_put. Under jax.distributed
    (process_count > 1) the shardings span devices this process cannot
    address, so each leaf becomes a global jax.Array assembled from the
    process-local shards instead — every process must hold the SAME full
    host value (true for replicated params initialised from one PRNG
    seed and for consts derived from the same graph). This is the
    multi-host analog of the reference's parameter-server variable
    placement (reference tf_euler/python/run_loop.py:391-394)."""
    if jax.process_count() == 1:
        return jax.device_put(tree, shardings)

    def put(x, s):
        if isinstance(x, jax.Array) and x.sharding == s:
            # already placed (e.g. a checkpoint-restored global array) —
            # np.asarray on it would crash for model-axis-sharded leaves
            # (spans non-addressable devices) and needlessly round-trip
            # everything else
            return x
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, s, lambda idx: x[idx])

    return jax.tree.map(put, tree, shardings)


def shard_batch(batch, mesh: Mesh):
    """Place a host batch pytree onto the mesh, leading dim sharded
    (scalars — e.g. a device-sampling seed — are replicated).

    Multi-process (jax.distributed): ``batch`` is this process's LOCAL
    shard — leading dims concatenate across processes in process order,
    so the global batch is num_processes x the local size. Scalars must
    be identical on every process (they replicate)."""
    sharding = batch_sharding(mesh)
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
