"""Device-plane observability: compile attribution, HBM gauges, transfer
counters (OBSERVABILITY.md "Device plane").

Four observability planes (PR 5-8) instrumented the host and the wire;
this module watches the DEVICE half of the step: every jit's way to an
executable as jax times it (the jaxpr trace, the lowering to an MLIR
module, the XLA backend compile: count + log2-µs latency histograms),
every recompile after a function's warmup (the classic silent 100x — a
shape/dtype drift makes jit quietly rebuild the program), device memory
in use, what the compiled train step needs in temporaries, and the
host<->device transfer volume. Everything lands in the existing native
surfaces through the eg_counter_add / eg_phase_record / eg_devprof ABI,
so metrics_text(), the STATS scrape, blackbox postmortems and
scripts/metrics_dump.py report the device plane with zero new plumbing:

    devprof.install()                once per process, before first jit
    devprof.uninstall()              take the listener out, stop the sampler
    fn = devprof.watch(jitted, "loss_step")   recompile attribution
    devprof.recompile_ledger()       journaled recompiles, newest last
    devprof.sample_device_mem()      one-shot HBM/buffer gauge refresh
    devprof.record_feature_table(w, stored)   feature-table width gauges
    devprof.record_store_table(w, stored)     per-node store width gauges
    devprof.record_step_memory(compiled)      the step's temporaries gauge
    devprof.count_h2d(batch)         transfer-byte bracketing
    devprof.set_devprof(False)       process-global kill-switch

Compile COUNTS ride ``device_compiles`` / ``device_recompiles`` /
``serve_recompiles`` (eg_stats.h), compile LATENCY rides the
``phase:compile`` histogram (eg_phase.h) with ``phase:trace`` and
``phase:lower`` beside it, memory gauges ride the blackbox resource
section (eg_blackbox.h + eg_devprof.h). The detector is a pair of
``jax.monitoring`` listeners: jax stamps the start of each timed stretch
(a scalar event) and hands over its duration at the end (exact backend
compile durations; a persistent-cache hit fires it too, with the
retrieval time), each with the function's name. jax times a jit traced
inside another jit's trace as well, and an eager op's whole compile
inside a trace: a stretch records its SELF time (its duration less the
stretches and set-up spans it holds, ``telemetry.span_open`` /
``span_close``), so the three sums are a union and never exceed the wall
time they cover on a thread. Attribution (WHICH function recompiled,
WHAT drifted) comes from :class:`Watched`'s jit-cache-size delta plus
the arg shape signature; :func:`function_compile_ms` says what one
function's trace / lower / compile took.
"""

from __future__ import annotations

import logging
import threading
import time

from euler_tpu import telemetry
from euler_tpu.graph import native
from euler_tpu.graph.native import lib

log = logging.getLogger("euler_tpu.devprof")

# The jax.monitoring event key of one XLA backend compile (fires once
# per compile, duration in seconds). Pinned by tests against the live
# jax in the image.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Its two siblings: tracing a jitted function to a jaxpr, and lowering
# the jaxpr to an MLIR module. Each event's phase:
EVENT_PHASE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    COMPILE_EVENT: "compile",
}

_LEDGER_CAP = 256
# {function: {phase: self ms}} of the listener's events, for the
# first-step summary; bounded: a program has a few hundred jits
_FN_CAP = 1024
_fn_ms: dict = {}

_enabled = True
_installed = False
_start_installed = False  # the start listener, armed with the other
_lock = threading.Lock()
_ledger: list = []
_sampler_stop = None
_sampler_thread = None


class RecompileError(RuntimeError):
    """A watched function recompiled after warmup under strict=True
    (the eg_serve ``strict_bucket=`` contract: the padded fixed-bucket
    forward must compile exactly once)."""


def devprof_enabled() -> bool:
    return _enabled


def set_devprof(on: bool) -> None:
    """Process-global device-plane kill-switch (`devprof=` config key):
    False stops compile counting/journaling, memory sampling and
    transfer-byte counting — the listener and wrappers stay in place
    but write nothing."""
    global _enabled
    _enabled = bool(on)


def _fn_key(fun_name) -> str:
    # the lowering and the compile name the module: "jit(<function>)"
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _on_event_start(event: str, value: float, **kw) -> None:
    # jax stamps the start of a timed stretch — must never raise.
    try:
        phase = EVENT_PHASE.get(event)
        # (inert once the duration listener is out: a start with no end
        # would leave its span open)
        if _enabled and _installed and phase is not None:
            telemetry.span_open(phase, {"fn": _fn_key(kw.get("fun_name"))})
    except Exception:  # pragma: no cover - defensive
        pass


def _on_event_duration(event: str, duration: float, **kw) -> None:
    # Called from inside jax's compile path — must never raise.
    try:
        phase = EVENT_PHASE.get(event)
        if not _enabled or phase is None:
            return
        if phase == "compile":
            native.counter_add("device_compiles")
        span = telemetry.open_span(phase)
        if span is None:  # armed inside the stretch: no start was seen
            telemetry.record_phase(phase, duration * 1e6)
            return
        self_us = telemetry.span_close(span, us=duration * 1e6)
        fn = span.args["fn"]
        if fn in _fn_ms or len(_fn_ms) < _FN_CAP:
            ms = _fn_ms.setdefault(fn, {})
            ms[phase] = ms.get(phase, 0.0) + self_us / 1e3
    except Exception:  # pragma: no cover - defensive
        pass


def function_compile_ms(name: str) -> dict:
    """{"trace": ms, "lower": ms, "compile": ms} the listener has seen
    for the jitted function ``name`` (self times; a phase it has not
    seen is absent)."""
    return dict(_fn_ms.get(name, ()))


def install(sample_ms: int = 0) -> None:
    """Arm the device plane (idempotent): register the jax.monitoring
    listeners; with ``sample_ms > 0`` also start the background
    device-memory sampler."""
    global _installed, _start_installed
    with _lock:
        if not _installed:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration
            )
            # (a caller that took the duration listener out by hand has
            # left this one in)
            if not _start_installed:
                jax.monitoring.register_scalar_listener(_on_event_start)
                _start_installed = True
            _installed = True
    if sample_ms > 0:
        start_sampler(sample_ms)


def uninstall() -> None:
    """Disarm what ``install`` armed (idempotent): take the compile
    listener out of jax.monitoring and stop the memory sampler. For a
    process that goes on after the run that armed the plane (a test
    worker): a listener left in records ``compile`` spans into whatever
    runs next."""
    global _installed, _start_installed
    with _lock:
        if _installed or _start_installed:
            import jax.monitoring

        if _installed:
            jax.monitoring.unregister_event_duration_listener(
                _on_event_duration
            )
            _installed = False
        if _start_installed:
            jax.monitoring.unregister_scalar_listener(_on_event_start)
            _start_installed = False
    stop_sampler()


def setup(enabled: bool = True, sample_ms: int = 0) -> bool:
    """CLI-startup arming shared by `python -m euler_tpu.run_loop` and
    `python -m euler_tpu.serve` (their --devprof flag lands here).
    Disarms the plane when ``enabled`` is False; otherwise installs the
    compile listener and optionally starts the memory sampler. Returns
    devprof_enabled(). The persistent compile cache is NOT decided here
    (parallel.enable_compile_cache — on with or without --devprof)."""
    if not enabled:
        set_devprof(False)
        return False
    install(sample_ms=sample_ms)
    return True


# ---------------------------------------------------------------------------
# compile attribution: per-function shape-signature registry
# ---------------------------------------------------------------------------


def _leaf_sig(x) -> tuple:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    return (type(x).__name__,)


def _signature(args: tuple, kwargs: dict) -> tuple:
    import jax

    return tuple(
        _leaf_sig(leaf)
        for leaf in jax.tree_util.tree_leaves((args, kwargs))
    )


def sig_diff(old, new) -> list:
    """Human-readable per-leaf diff between two signatures — the
    'exactly WHAT drifted' half of a recompile journal entry."""
    if old is None:
        return ["first compile"]
    out = []
    n = max(len(old), len(new))
    for i in range(n):
        a = old[i] if i < len(old) else None
        b = new[i] if i < len(new) else None
        if a != b:
            out.append(f"leaf{i}: {_fmt_sig(a)} -> {_fmt_sig(b)}")
    return out or [f"leaf count {len(old)} -> {len(new)}"]


def _fmt_sig(s) -> str:
    if s is None:
        return "absent"
    if len(s) == 2:
        return f"{s[0]} {s[1]}"
    return str(s[0])


def _journal(entry: dict) -> None:
    with _lock:
        _ledger.append(entry)
        del _ledger[:-_LEDGER_CAP]
    # the same event lands in the slow-span journal (op 0 = "other",
    # client side) so a scrape's slowest-N view shows the recompile
    # wall time next to the RPC spans it starved
    telemetry.record_span(int(entry["wall_us"]), op=0, side="client")
    log.warning("devprof: recompile of %s after warmup: %s",
                entry["fn"], "; ".join(entry["diff"]))


def recompile_ledger() -> list:
    """Journaled post-warmup recompiles, oldest first (bounded to the
    last 256): [{"t_us", "fn", "diff", "sig", "prev", "wall_us"}]."""
    with _lock:
        return list(_ledger)


def devprof_reset() -> None:
    """Clear the recompile ledger and the per-function compile times
    (native gauges/counters reset with telemetry_reset()/
    counters_reset())."""
    with _lock:
        del _ledger[:]
    _fn_ms.clear()


class Watched:
    """A jitted callable with recompile attribution: detects every
    compile the call triggered (jit cache-size delta) and journals any
    compile AFTER warmup as a recompile with the exact arg-shape/dtype
    diff that caused it.

    ``on_recompile(entry)`` is the serve compile-storm hook;
    ``strict=True`` raises :class:`RecompileError` (the result is
    computed first — the caller may catch and keep it)."""

    def __init__(self, fn, name: str | None = None, strict: bool = False,
                 counter: str = "device_recompiles",
                 on_recompile=None):
        self._fn = fn
        self.name = name or getattr(fn, "__name__", "jit_fn")
        self.strict = strict
        self._counter = counter
        self._on_recompile = on_recompile
        self._last_sig = None
        self.warm = False
        self.compiles = 0
        self.recompiles = 0

    def mark_warm(self) -> None:
        """Declare warmup done: the NEXT compile is a recompile even if
        no tracked call compiled yet (serve warms up out-of-band)."""
        self.warm = True

    def __call__(self, *args, **kwargs):
        if not _enabled:
            return self._fn(*args, **kwargs)
        before = self._fn._cache_size()
        t0 = time.monotonic()
        out = self._fn(*args, **kwargs)
        wall_us = int((time.monotonic() - t0) * 1e6)
        if self._fn._cache_size() == before:
            # steady state — in-bucket dispatch, nothing compiled, so
            # the arg signature (the expensive half of attribution) is
            # never built; _last_sig stays at the sig that triggered the
            # last compile, which is exactly the "previous" side a
            # future recompile diffs against
            return out
        sig = _signature(args, kwargs)
        self.compiles += 1
        if self.warm:
            self.recompiles += 1
            entry = {
                "t_us": int(time.monotonic() * 1e6),
                "fn": self.name,
                "diff": sig_diff(self._last_sig, sig),
                "sig": sig,
                "prev": self._last_sig,
                "wall_us": wall_us,
            }
            native.counter_add(self._counter)
            _journal(entry)
            self._last_sig = sig
            if self._on_recompile is not None:
                self._on_recompile(entry)
            if self.strict:
                raise RecompileError(
                    f"{self.name} recompiled after warmup: "
                    f"{'; '.join(entry['diff'])}"
                )
            return out
        self.warm = True
        self._last_sig = sig
        return out


def watch(fn, name: str | None = None, strict: bool = False,
          counter: str = "device_recompiles", on_recompile=None) -> Watched:
    """Wrap a jitted callable with recompile attribution (see
    :class:`Watched`). The wrapper is transparent (same args/returns)
    and free when the kill-switch is off."""
    return Watched(fn, name=name, strict=strict, counter=counter,
                   on_recompile=on_recompile)


# ---------------------------------------------------------------------------
# device memory & transfer telemetry
# ---------------------------------------------------------------------------


def sample_device_mem() -> tuple:
    """One device-memory sample pushed into the native gauges (and from
    there into blackbox resource rings, postmortems and metrics_text):
    (bytes_in_use, live_buffers). Uses device.memory_stats() where the
    backend reports it (TPU/GPU); falls back to a jax.live_arrays()
    census (CPU — the census IS the live-buffer truth there)."""
    if not _enabled:
        return (0, 0)
    import jax

    arrs = jax.live_arrays()
    buffers = len(arrs)
    bytes_in_use = None
    try:
        stats = jax.devices()[0].memory_stats()
        if stats:
            bytes_in_use = int(stats.get("bytes_in_use", 0)) or None
    except Exception:  # noqa: BLE001 - backend without memory_stats
        bytes_in_use = None
    if bytes_in_use is None:
        bytes_in_use = int(sum(getattr(a, "nbytes", 0) for a in arrs))
    lib().eg_devprof_set_mem(bytes_in_use, buffers)
    return (bytes_in_use, buffers)


def record_feature_table(width: int, stored_width: int) -> None:
    """The gauges ``feature_table_width`` / ``feature_table_stored_width``
    of the resource section: a model's feature_dim and the lane-multiple
    width its device-resident table stores rows at (models/base.py
    build_consts calls this once per table it builds)."""
    if _enabled:
        lib().eg_devprof_set_feature_table(int(width), int(stored_width))


def record_store_table(width: int, stored_width: int) -> None:
    """The gauges ``store_table_width`` / ``store_table_stored_width`` of
    the resource section: the width of a training state's per-node
    stores and the lanes a stored row takes in device memory, 0 where the
    table lies column-major: a state that did not pass
    ``parallel.state_sharding``, which pins the stores rows-major
    (models/base.py ScalableStoreModel.describe_state, once per
    ``train()``)."""
    if _enabled:
        lib().eg_devprof_set_store_table(int(width), int(stored_width))


def record_step_memory(compiled) -> dict | None:
    """The gauge ``step_temp_bytes`` of the resource section, from a
    compiled train step's ``memory_analysis()``: what the program needs
    beside its arguments and results, which ``memory_stats()`` does not
    count (train.write_step_hlo calls this once, in a profiled run).
    Returns the analysis' sizes in bytes, None where the backend gives
    none."""
    if not _enabled:
        return None
    try:
        ma = compiled.memory_analysis()
        sizes = {
            k: int(getattr(ma, k + "_size_in_bytes"))
            for k in ("temp", "argument", "output", "alias")
        }
    except Exception:  # noqa: BLE001 - backend without the analysis
        return None
    lib().eg_devprof_set_step_temp(sizes["temp"])
    return sizes


def start_sampler(period_ms: int = 1000) -> None:
    """Background device-memory sampler (daemon; idempotent): refreshes
    the native gauges every ``period_ms`` so the blackbox resource ring
    (eg_blackbox.h SamplerLoop reads the gauges on ITS cadence) and any
    scrape see a live trajectory, not just the last manual sample."""
    global _sampler_stop, _sampler_thread
    with _lock:
        if _sampler_thread is not None and _sampler_thread.is_alive():
            return
        stop = threading.Event()

        def loop():
            while not stop.wait(max(period_ms, 50) / 1000.0):
                # stamped for the training loop's stall journal: the
                # census below holds the interpreter lock
                try:
                    telemetry.job_tick("eg-devprof-sampler")
                    try:
                        sample_device_mem()
                    finally:
                        telemetry.job_tick("eg-devprof-sampler", end=True)
                except Exception:  # pragma: no cover - keep sampling
                    pass

        t = threading.Thread(target=loop, name="eg-devprof-sampler",
                             daemon=True)
        t.start()
        _sampler_stop, _sampler_thread = stop, t


def stop_sampler() -> None:
    global _sampler_stop, _sampler_thread
    with _lock:
        if _sampler_stop is not None:
            _sampler_stop.set()
        _sampler_stop = _sampler_thread = None


def tree_bytes(tree) -> int:
    """Total array bytes across a pytree's leaves."""
    import jax

    # size * itemsize rather than .nbytes: jax.Array's nbytes property
    # re-derives the byte count through the sharding machinery (~2.5 us
    # per leaf) and this census rides every step's h2d hook
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * dtype.itemsize
        else:
            total += int(getattr(leaf, "nbytes", 0))
    return total


def count_h2d(tree) -> int:
    """Bump ``h2d_bytes`` by the byte size of a pytree about to cross
    host->device (train shard_batch / serve dispatch call sites).
    Returns the bytes counted (0 when the kill-switch is off)."""
    if not _enabled:
        return 0
    n = tree_bytes(tree)
    if n:
        native.counter_add("h2d_bytes", n)
    return n


def count_d2h(tree) -> int:
    """Bump ``d2h_bytes`` for a device->host materialization (fetched
    losses/metrics, served embedding rows)."""
    if not _enabled:
        return 0
    n = tree_bytes(tree)
    if n:
        native.counter_add("d2h_bytes", n)
    return n


# ---------------------------------------------------------------------------
# summaries (train()'s first-step line, scripts/devprof_dump.py)
# ---------------------------------------------------------------------------


def first_step_line(step_name: str) -> str:
    """The once-a-run line ``train()`` logs at its first dispatched
    step: the compiles so far (with the persistent compile cache warm
    their time drops to ~0 on the second launch), every set-up leaf with
    its seconds (and GB where its spans named bytes), and what the step
    function's own trace / lower / compile took beside all jits'."""
    data = telemetry.telemetry_json()
    cs = compile_summary(data)
    leaves = ", ".join(
        f"{name.removeprefix('setup_')} {secs:.1f} s"
        + (f" ({nbytes / 1e9:.2f} GB)" if nbytes else "")
        for name, (secs, nbytes) in telemetry.setup_summary(data).items()
    )
    fn = function_compile_ms(step_name)
    step = " / ".join(
        f"{phase} {fn.get(phase, 0.0) / 1e3:.1f}"
        for phase in ("trace", "lower", "compile")
    )
    return (
        f"first step dispatched: {cs['compile_events']} XLA compile(s), "
        f"{cs['compile_ms_total']:.0f} ms compile time; all jits: trace "
        f"{cs['trace_ms_total'] / 1e3:.1f} s, lower "
        f"{cs['lower_ms_total'] / 1e3:.1f} s; {step_name}: {step} s; "
        f"set-up: {leaves or 'no span recorded'}"
    )


def compile_summary(data: dict | None = None) -> dict:
    """One-line compile economics from a telemetry dump (default: this
    process): counts, total/percentile compile wall, memory high-water.
    The run_loop logs this after the first step so a relaunch with a
    warm compilation cache is visibly cheap."""
    data = data or telemetry.telemetry_json()
    none = {"b": [0], "count": 0, "sum_us": 0}
    h = data["hist"].get("phase:compile") or none
    pct = telemetry.percentiles(h, (50, 99)) if h["count"] else {}
    res = data.get("resource", {})
    return {
        "compiles": data["counters"].get("device_compiles", 0),
        "recompiles": data["counters"].get("device_recompiles", 0),
        "serve_recompiles": data["counters"].get("serve_recompiles", 0),
        "compile_events": h["count"],
        "compile_ms_total": round(h["sum_us"] / 1000.0, 1),
        "compile_ms_p50": round(pct.get(50, 0.0) / 1000.0, 1),
        "compile_ms_p99": round(pct.get(99, 0.0) / 1000.0, 1),
        "trace_ms_total": round(
            (data["hist"].get("phase:trace") or none)["sum_us"] / 1000.0, 1),
        "lower_ms_total": round(
            (data["hist"].get("phase:lower") or none)["sum_us"] / 1000.0, 1),
        "h2d_bytes": data["counters"].get("h2d_bytes", 0),
        "d2h_bytes": data["counters"].get("d2h_bytes", 0),
        "device_mem_bytes": res.get("device_mem_bytes", 0),
        "device_mem_peak_bytes": res.get("device_mem_peak_bytes", 0),
        "device_buffers": res.get("device_buffers", 0),
        "step_temp_bytes": res.get("step_temp_bytes", 0),
    }
