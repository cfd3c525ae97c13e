from euler_tpu.parallel.mesh import (
    batch_sharding,
    compiles_keep_layouts,
    enable_compile_cache,
    force_cpu_devices,
    make_mesh,
    pad_tables_for_mesh,
    put_global,
    replicated_sharding,
    shard_batch,
    state_sharding,
    table_sharding,
)
from euler_tpu.parallel.prefetch import pipeline, prefetch

__all__ = [
    "batch_sharding",
    "compiles_keep_layouts",
    "enable_compile_cache",
    "force_cpu_devices",
    "make_mesh",
    "pad_tables_for_mesh",
    "put_global",
    "replicated_sharding",
    "shard_batch",
    "state_sharding",
    "table_sharding",
    "prefetch",
    "pipeline",
]
