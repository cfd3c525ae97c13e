"""euler_tpu: a TPU-native graph learning framework.

A ground-up rebuild of the capabilities of Alibaba Euler 1.x
(/root/reference) for TPU: a C++ host graph engine (weighted sampling,
random walks, feature gather over an immutable SoA store) feeding JAX/XLA
model compute through an async prefetch pipeline, with data-parallel
training over a jax.sharding.Mesh instead of parameter servers.
"""

from euler_tpu.graph.graph import Graph
from euler_tpu.graph.convert import convert, convert_dicts
from euler_tpu.graph.native import (
    counters,
    counters_reset,
    fault_clear,
    fault_config,
    fault_injected,
    reset_counters,
    stats,
    stats_reset,
)
from euler_tpu.graph.service import GraphService
from euler_tpu.telemetry import (
    metrics_text,
    scrape,
    set_telemetry,
    slow_spans,
    telemetry_json,
    telemetry_reset,
)
from euler_tpu.blackbox import (
    blackbox_json,
    postmortem_read,
    set_blackbox,
)
from euler_tpu.heat import (
    heat_json,
    heat_reset,
    heat_topk,
    set_heat,
)
from euler_tpu.devprof import (
    RecompileError,
    compile_summary,
    recompile_ledger,
    sample_device_mem,
    set_devprof,
    watch,
)
from euler_tpu.serving import (
    BusyError,
    DeadlineError,
    EmbedClient,
)
from euler_tpu.serve import EmbedServer

__version__ = "0.2.0"

__all__ = [
    "Graph", "GraphService", "convert", "convert_dicts", "stats",
    "stats_reset", "counters", "counters_reset", "reset_counters",
    "fault_config", "fault_clear", "fault_injected", "metrics_text",
    "scrape", "set_telemetry", "slow_spans", "telemetry_json",
    "telemetry_reset", "blackbox_json", "postmortem_read",
    "set_blackbox", "heat_json", "heat_topk", "heat_reset", "set_heat",
    "RecompileError", "compile_summary", "recompile_ledger",
    "sample_device_mem", "set_devprof", "watch",
    "EmbedServer", "EmbedClient", "BusyError", "DeadlineError",
]
