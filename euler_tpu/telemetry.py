"""Observability surface over the native telemetry subsystem.

The native layer (graph/_native/eg_telemetry.{h,cc}) records log2-
bucketed latency histograms per RPC op (client whole-call, server
handler, server queue wait, dial, retry backoff), keeps a slowest-N
span journal on each side correlated by wire-propagated trace ids, and
answers the STATS wire opcode with one JSON dump of everything plus the
admission gauges. This module is the Python half:

    euler_tpu.metrics_text()            Prometheus text, local process
    euler_tpu.metrics_text(graph=g)     every shard of a live cluster
    euler_tpu.slow_spans()              local slow-span journal
    euler_tpu.scrape(g, shard)          one shard's raw telemetry dict
    euler_tpu.set_telemetry(False)      process-global kill-switch

plus the step-phase profiler surface (native eg_phase.{h,cc}): the
training loop and prefetch pipeline record per-step phase timers
(input_stall / sample / h2d / device / host / step, and the training
thread's leaves of :data:`PHASE_PARENT`) and prefetch pipeline gauges
through :func:`record_phase` / :func:`record_prefetch_gauges`,
:class:`StallJournal` journals the steps that took several times their
median, and the set-up path records what comes before the first step
through :class:`setup_span` (:data:`SETUP_PHASES`; OBSERVABILITY.md
"Set-up phases"); they land in the same native "hist" map
as the RPC latency histograms, so metrics_text(), snapshot(), the STATS
scrape, and scripts/metrics_dump.py all report them with one renderer
(OBSERVABILITY.md "Step phases"), and the percentile/bucket arithmetic
here is shared with scripts/metrics_dump.py and the --metrics_every
JSONL emitter used by run_loop.
"""

from __future__ import annotations

import ctypes
import functools
import gc
import json
import resource
import statistics
import threading
import time
from collections import deque

from euler_tpu.graph.native import lib

# Bucket layout — MUST match eg_telemetry.h HistBucketOf: bucket 0 =
# [0, 1µs); bucket b (1..26) = [2^(b-1), 2^b) µs; bucket 27 = [2^26, inf).
NUM_BUCKETS = 28

# Step-phase order — MUST match eg_phase.h StepPhase (the profiler
# records by index through the eg_phase_record ABI, pinned by tests).
# "compile", "trace" and "lower" are the device-plane add-on
# (euler_tpu/devprof.py): what jax.monitoring times of a jit's way to an
# executable, NOT part of the step-sum identity. The "setup_*" leaves are
# recorded by :class:`setup_span` on the way to train()'s first step.
PHASES = ("input_stall", "sample", "h2d", "device", "host", "step",
          "compile", "input_other", "dispatch", "fence", "hook",
          "log_flush", "checkpoint", "host_other", "stall",
          "trace", "lower", "setup_graph_load", "setup_table_export",
          "setup_adjacency", "setup_pack", "setup_upload",
          "setup_state_place")
_PHASE_INDEX = {name: i for i, name in enumerate(PHASES)}
SETUP_PHASES = tuple(p for p in PHASES if p.startswith("setup_"))

# The training thread's LEAVES (input_stall, input_other, h2d when it
# runs there, and the keys below) do not overlap and together cover one
# iteration; a parent is the sum of its leaves, kept as a histogram only
# (OBSERVABILITY.md "Step phases"). Static, by name: a trace event needs
# no parent field.
PHASE_PARENT = {
    "dispatch": "device", "fence": "device",
    "hook": "host", "log_flush": "host", "checkpoint": "host",
    "host_other": "host",
    # (`setup` is a name only: the sum of its leaves, no histogram)
    **{leaf: "setup" for leaf in SETUP_PHASES},
}

# The program's periodic jobs — MUST match eg_phase.h PeriodicJob. Each
# stamps its last tick through :func:`job_tick`; the stall journal names
# those that ticked inside a slow step.
PERIODIC_JOBS = ("eg-devprof-sampler", "blackbox-sampler", "metrics_every")

# Serve-request phase order — MUST match eg_phase.h ServePhase (the
# serving layer records by index through the eg_serve_record ABI,
# pinned by tests). OBSERVABILITY.md "Serve phases".
SERVE_PHASES = ("queue_wait", "sample", "dispatch", "total")


def bucket_of(us: int) -> int:
    """Bucket index of a microsecond value (the Python twin of the
    native HistBucketOf, pinned against it by tests)."""
    if us <= 0:
        return 0
    b = int(us).bit_length()
    return min(b, NUM_BUCKETS - 1)


def bucket_edges_us() -> list:
    """Upper bucket edges in µs (27 finite edges, last bucket +Inf)."""
    return [1 << b for b in range(NUM_BUCKETS - 1)]


def percentiles(hist: dict, qs=(50, 90, 99)) -> dict:
    """Estimate percentiles from one histogram dict ({"b": [...],
    "count": n, "sum_us": s}) by linear interpolation inside the
    containing log2 bucket. Returns {q: µs float}; empty hist -> {}."""
    buckets = hist["b"]
    total = sum(buckets)
    if total == 0:
        return {}
    out = {}
    for q in qs:
        rank = q / 100.0 * total
        acc = 0.0
        for b, n in enumerate(buckets):
            if n == 0:
                continue
            if acc + n >= rank:
                lo = 0.0 if b == 0 else float(1 << (b - 1))
                # the open-ended last bucket gets a 2x-wide estimate span
                hi = float(1 << b) if b < NUM_BUCKETS - 1 else lo * 2.0
                frac = (rank - acc) / n
                out[q] = lo + (hi - lo) * frac
                break
            acc += n
    return out


# ---------------------------------------------------------------------------
# native calls
# ---------------------------------------------------------------------------


def _json_abi(call) -> dict:
    """Run a (buf, cap) -> needed-length ABI call, growing the buffer
    until the dump fits, and parse the JSON."""
    cap = 1 << 16
    while True:
        buf = ctypes.create_string_buffer(cap)
        n = call(buf, cap)
        if n < 0:
            raise RuntimeError(lib().eg_last_error().decode())
        if n < cap:
            return json.loads(buf.value.decode())
        cap = n + 1


def telemetry_json() -> dict:
    """This process's full telemetry dump: counters, span-timer stats,
    every histogram, the slow-span journal (no admission gauges — those
    belong to a serving process and arrive via :func:`scrape`)."""
    return _json_abi(lambda buf, cap: lib().eg_telemetry_json(buf, cap))


def scrape(graph, shard: int) -> dict:
    """Scrape one live shard's telemetry over the STATS wire opcode.

    Returns the shard process's dump — same shape as
    :func:`telemetry_json` plus a ``gauges`` section (handler pool size,
    workers busy, queue depth, open conns, draining) — fetched with the
    graph's ordinary transport config (retries, deadline, failover)."""
    if getattr(graph, "mode", None) != "remote":
        raise ValueError("scrape() needs a mode='remote' graph "
                         "(a local graph IS this process: use "
                         "telemetry_json())")
    h = graph._h
    return _json_abi(
        lambda buf, cap: lib().eg_remote_scrape(h, shard, buf, cap)
    )


def ping(graph, shard: int) -> bool:
    """One kPing round trip to ``shard`` through the full transport
    stack (retries, deadline, wire negotiation) — the health probe a
    readiness check should use, because it exercises exactly the path
    real calls take. True when the shard answered."""
    if getattr(graph, "mode", None) != "remote":
        raise ValueError("ping() needs a mode='remote' graph")
    return lib().eg_remote_ping(graph._h, shard) == 1


def telemetry_enabled() -> bool:
    return lib().eg_telemetry_enabled() == 1


def set_telemetry(on: bool) -> None:
    """Process-global telemetry kill-switch (`telemetry=` config key):
    False stops histogram + slow-span recording everywhere (counters
    and span-timer stats keep working — they predate this subsystem)."""
    lib().eg_telemetry_set_enabled(1 if on else 0)


def telemetry_reset() -> None:
    """Zero every histogram and both-side span journals (the enabled
    flag and journal capacity survive)."""
    lib().eg_telemetry_reset()
    _setup_bytes.clear()


def set_slow_capacity(n: int) -> None:
    """Resize the slowest-N span journal (`slow_spans=` config key)."""
    lib().eg_telemetry_set_slow_capacity(int(n))


# ---------------------------------------------------------------------------
# step-phase profiler (native eg_phase.h; OBSERVABILITY.md "Step phases")
# ---------------------------------------------------------------------------

# Optional per-event sink the trace recorder (euler_tpu/trace.py)
# registers: fn(phase, us, step, end_us) called on every record_phase
# while a trace capture is active. None (the default) costs one global
# read.
_trace_sink = None


def set_trace_sink(fn) -> None:
    """Install (or clear, with None) the per-event phase sink — the
    trace recorder's tap into :func:`record_phase`."""
    global _trace_sink
    _trace_sink = fn


def now_us() -> int:
    """CLOCK_MONOTONIC µs: the one clock of every span (``trace.now_us``
    is this function), of the native spans' ``end_us`` stamps and of the
    ``eg_align`` stamp."""
    return time.monotonic_ns() // 1000


def record_phase(phase: str, us: float, step: int | None = None,
                 end_us: int | None = None) -> None:
    """One step-phase µs sample (train loop / prefetch pipeline call
    sites). Lands in the ``phase:<name>`` histogram of
    :func:`telemetry_json` (kill-switch honored natively) and, while a
    trace capture is active, in the trace recorder's event buffer.
    ``end_us`` is the CLOCK_MONOTONIC µs reading taken where the span
    ended (the clock ``us`` was measured on); without it the recorder
    places the span as ending when it was recorded."""
    lib().eg_phase_record(_PHASE_INDEX[phase], max(int(us), 0))
    sink = _trace_sink
    if sink is not None:
        sink(phase, us, step, end_us)


def record_phase_hist(phase: str, us: float) -> None:
    """A histogram-only sample: a parent of :data:`PHASE_PARENT` (the sum
    of leaves that reached the sink themselves), a leaf whose span went
    out in pieces (:func:`record_phase_span`), or ``stall``."""
    lib().eg_phase_record(_PHASE_INDEX[phase], max(int(us), 0))


def record_phase_span(phase: str, start_us: int, end_us: int,
                      step: int | dict | None = None) -> None:
    """A trace-sink-only span [start_us, end_us) on CLOCK_MONOTONIC: one
    piece of a leaf that another leaf interrupts (``input_other`` lies on
    both sides of ``input_stall``); its histogram sample is recorded once,
    with :func:`record_phase_hist`. A span outside the loop (set-up, the
    compile listener's) has no step: it hands over its ``args`` (what it
    worked on: ``bytes``, ``fn``) in the step's place."""
    sink = _trace_sink
    if sink is not None and end_us > start_us:
        sink(phase, end_us - start_us, step, end_us)


# ---------------------------------------------------------------------------
# spans outside the loop: set-up, and the compile listener's
# (OBSERVABILITY.md "Set-up phases")
# ---------------------------------------------------------------------------

# Each thread's open spans, innermost last. A span that holds another
# records its SELF time: the histograms of SETUP_PHASES and of devprof's
# trace / lower / compile then add up to the wall time they cover on a
# thread, and the pieces that reach the trace sink do not overlap.
_open_spans = threading.local()
# bytes the spans of a phase said they moved (the first-step summary)
_setup_bytes: dict = {}


def _span_stack() -> list:
    try:
        return _open_spans.stack
    except AttributeError:
        _open_spans.stack = []
        return _open_spans.stack


class _Span:
    __slots__ = ("phase", "args", "start", "cursor", "inside", "muted")

    def __init__(self, phase, args, muted, start):
        self.phase, self.args, self.muted = phase, args, muted
        self.start = self.cursor = start
        self.inside = 0  # µs of closed spans within this one


def span_open(phase: str, args: dict | None = None,
              muted: bool = False) -> _Span:
    """Open a span of ``phase`` on this thread, now. ``muted``: it and
    the set-up spans opened under it record nothing."""
    stack = _span_stack()
    span = _Span(phase, args, muted or bool(stack and stack[-1].muted),
                 now_us())
    stack.append(span)
    return span


def open_span(phase: str) -> _Span | None:
    """This thread's innermost open span of ``phase`` (the compile
    listener finds its own again: jax hands it a start and an end, no
    handle)."""
    for span in reversed(_span_stack()):
        if span.phase == phase:
            return span
    return None


def span_close(span: _Span, us: float | None = None,
               record: bool = True) -> int:
    """Close ``span`` (and whatever was left open inside it), now: its
    self time goes to its phase's histogram (of ``us``, where the caller
    has the span's length from another clock reading: jax's own), its
    last piece to the trace sink, its whole length to the span around
    it. Returns the self time in µs."""
    end = now_us()
    stack = _span_stack()
    if span in stack:
        del stack[stack.index(span):]
    whole = end - span.start if us is None else int(us)
    self_us = max(whole - span.inside, 0)
    outer = stack[-1] if stack else None
    if outer is not None:
        before = outer.cursor  # the outer span's piece before this one
        outer.cursor = end
        outer.inside += end - span.start
    if record:
        record_phase_hist(span.phase, self_us)
        if outer is not None and not outer.muted:
            record_phase_span(outer.phase, before, span.start, outer.args)
        record_phase_span(span.phase, span.cursor, end, span.args)
    return self_us


class setup_span:
    """``with setup_span("setup_upload", nbytes=table.nbytes):`` times a
    stage of set-up (a leaf of :data:`SETUP_PHASES`) where the work is
    done, on :func:`now_us`'s clock. Follows the telemetry kill-switch;
    ``on=False`` (``train(phase_profile=False)``) records nothing of it
    nor of the set-up spans inside it. ``nbytes`` may be set on the way
    (``as span: ... span.nbytes = n``)."""

    __slots__ = ("phase", "nbytes", "on", "_span")

    def __init__(self, phase: str, nbytes: int = 0, on: bool = True):
        self.phase, self.nbytes, self.on = phase, nbytes, on
        self._span = None

    def __enter__(self):
        if not self.on:
            self._span = span_open(self.phase, muted=True)
        elif telemetry_enabled():
            self._span = span_open(self.phase)
        return self

    def __exit__(self, *exc):
        span, self._span = self._span, None
        if span is None:
            return False
        record = not span.muted
        if record and self.nbytes:
            span.args = {"bytes": int(self.nbytes)}
            _setup_bytes[self.phase] = (
                _setup_bytes.get(self.phase, 0) + int(self.nbytes))
        span_close(span, record=record)
        return False


def setup_spanned(phase: str):
    """Decorator: the whole call is one :class:`setup_span` of ``phase``."""
    def wrap(fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with setup_span(phase):
                return fn(*args, **kwargs)
        return spanned
    return wrap


def setup_summary(data: dict | None = None) -> dict:
    """{leaf: (seconds, bytes)} of the set-up leaves recorded so far in
    this process (seconds from the histograms' sums; bytes 0 where the
    spans named none)."""
    hists = phase_hists(data)
    return {
        name: (hists[name]["sum_us"] / 1e6, _setup_bytes.get(name, 0))
        for name in SETUP_PHASES
        if name in hists and hists[name]["count"]
    }


def job_tick(job: str, end: bool = False) -> None:
    """Stamp the begin (or, with ``end``, the end) of one tick of a
    periodic job of :data:`PERIODIC_JOBS` on CLOCK_MONOTONIC."""
    lib().eg_phase_tick(PERIODIC_JOBS.index(job), 1 if end else 0)


def job_ticks() -> dict:
    """{job: (begin_us, end_us)} of every periodic job's last tick
    (0 = never)."""
    n = len(PERIODIC_JOBS)
    buf = (ctypes.c_int64 * (2 * n))()
    lib().eg_phase_ticks(buf)
    return {job: (buf[2 * i], buf[2 * i + 1])
            for i, job in enumerate(PERIODIC_JOBS)}


def record_prefetch_gauges(queue_depth: int, workers_busy: int) -> None:
    """One prefetch-pipeline sample at consumer dequeue: ready batches
    waiting and workers inside make_batch — the two value histograms
    that tell queue starvation (depth pinned at 0, workers busy) apart
    from slow/dead workers (depth 0, workers idle)."""
    L = lib()
    L.eg_phase_gauge(0, max(int(queue_depth), 0))
    L.eg_phase_gauge(1, max(int(workers_busy), 0))


def record_serve_phase(phase: str, us: float) -> None:
    """One serve-request phase µs sample (euler_tpu/serving call
    sites). Lands in the ``serve:<name>`` histogram of
    :func:`telemetry_json`; the kill-switch is honored natively, so
    ``telemetry=0`` leaves the serve hot path histogram-free."""
    lib().eg_serve_record(SERVE_PHASES.index(phase), max(int(us), 0))


def record_serve_batch(unique_ids: int) -> None:
    """One micro-batch device dispatch: unique ids in the batch. Count
    over the ``serve_batch`` value histogram is dispatches, sum is ids —
    their ratio the request-coalescing factor."""
    lib().eg_serve_batch(max(int(unique_ids), 0))


def serve_hists(data: dict | None = None) -> dict:
    """{phase: histogram dict} for the serve-request phases, extracted
    from a telemetry dump (default: this process's)."""
    data = data or telemetry_json()
    return {
        key.partition(":")[2]: h
        for key, h in data["hist"].items()
        if key.startswith("serve:")
    }


def phase_hists(data: dict | None = None) -> dict:
    """{phase: histogram dict} extracted from a telemetry dump
    (default: this process's)."""
    data = data or telemetry_json()
    return {
        key.partition(":")[2]: h
        for key, h in data["hist"].items()
        if key.startswith("phase:")
    }


def record_span(total_us: int, op: int = 0, side: str = "client",
                outcome: int = 0, shard: int = -1, trace: int = 0,
                queue_us: int = 0, handler_us: int = 0,
                wire_us: int = 0) -> None:
    """Offer an app-level span to the local journal (the same primitive
    the native transport sites use)."""
    lib().eg_telemetry_record_span(
        1 if side == "server" else 0, int(op), int(outcome), int(shard),
        int(trace), int(queue_us), int(handler_us), int(wire_us),
        int(total_us),
    )


def slow_spans(graph=None, shard: int | None = None) -> list:
    """Slowest-N spans, slowest first: local journal by default, a live
    shard's when (graph, shard) name one. Trace ids come back as
    Python ints (0 = not propagated: v1/v2 peer or telemetry off)."""
    data = telemetry_json() if graph is None else scrape(graph, shard)
    spans = data["slow_spans"]
    for s in spans:
        s["trace"] = int(s["trace"])
        if "detail" in s:  # an app-level span's JSON text (StallJournal)
            s["detail"] = json.loads(s["detail"])
    return spans


# ---------------------------------------------------------------------------
# stall journal (OBSERVABILITY.md "Step phases": "Stall journal")
# ---------------------------------------------------------------------------


class StallJournal:
    """Journals the training thread's steps that took over
    max(FACTOR x the running median step, FLOOR_US), with what can tell
    the causes apart: the leaf that held the excess, the thread's CPU
    time and context switches, the collections the Python collector ran
    inside the step, and the periodic jobs that ticked inside it.
    Entries go to the slow-span journal (:func:`slow_spans`,
    ``detail["kind"] == "train_stall"``; :func:`stall_journal`), one
    sample of the excess to the ``stall`` histogram.

    The thread's CPU clock and ``getrusage`` are system calls (a dozen µs
    each on a sandboxed host), so they are read once per REFRESH steps
    and at the end of a journalled step: an entry's ``cpu_us`` / ``vcsw``
    / ``ivcsw`` are over the ``since_steps`` steps since the last reading
    (the stalled one is the last of them), beside what a step usually
    takes of each (``usual_*``, per step, over the stretch before).

    Owned by one ``train()`` call, used from its thread only; the
    collector's callbacks may run on any thread (a collection holds the
    interpreter lock, so it pauses the training thread wherever it
    runs). ``close()`` removes the callback."""

    FACTOR = 5
    FLOOR_US = 50_000
    WINDOW = 128      # steps the running median is over
    REFRESH = 32      # steps between two readings of median and thread
    FIRST = 8         # no median, and no entry, before this many steps

    def __init__(self):
        self._recent: deque = deque(maxlen=self.WINDOW)
        self._n = 0
        self._median_us = 0
        self._limit_us = None
        self._typical: dict = {}
        self._gc: list = []
        self._gc_t0 = 0
        self._read_n = 0
        self._read = self._thread_stats()
        self._usual = (0.0, 0.0, 0.0)
        gc.callbacks.append(self._on_gc)

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    @staticmethod
    def _thread_stats():
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        return time.thread_time_ns() // 1000, ru.ru_nvcsw, ru.ru_nivcsw

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.monotonic_ns()
        else:
            self._gc.append((
                info["generation"],
                (time.monotonic_ns() - self._gc_t0) // 1000,
                threading.current_thread().name,
            ))

    def step(self, step: int, start_us: int, end_us: int,
             leaves: dict) -> None:
        """One finished step: [start_us, end_us) on CLOCK_MONOTONIC and
        the µs of each of its leaves."""
        dur = end_us - start_us
        self._n += 1
        if self._limit_us is not None and dur > self._limit_us:
            self._journal(step, start_us, end_us, leaves)
        else:
            self._typical = leaves
        if self._gc:
            self._gc = []
        self._recent.append(dur)
        if self._n == self.FIRST or self._n % self.REFRESH == 0:
            self._median_us = int(statistics.median(self._recent))
            self._limit_us = max(self.FACTOR * self._median_us,
                                 self.FLOOR_US)
            steps = self._n - self._read_n
            if steps:  # (none where this very step was journalled)
                now = self._thread_stats()
                self._usual = tuple(
                    (a - b) / steps for a, b in zip(now, self._read))
                self._read, self._read_n = now, self._n

    def _journal(self, step, start_us, end_us, leaves):
        dur = end_us - start_us
        excess = dur - self._median_us
        leaf = max(leaves, key=lambda k: leaves[k] - self._typical.get(k, 0))
        ticked = []
        for job, (t0, t1) in job_ticks().items():
            if t0 and t0 <= end_us and (t1 < t0 or t1 >= start_us):
                ticked.append(job)
        now = self._thread_stats()
        cpu_us, vcsw, ivcsw = (a - b for a, b in zip(now, self._read))
        detail = {
            "kind": "train_stall", "step": step, "median_us": self._median_us,
            "excess_us": excess, "leaf": leaf, "leaf_us": leaves[leaf],
            "leaf_typical_us": self._typical.get(leaf, 0),
            "since_steps": self._n - self._read_n,
            "cpu_us": cpu_us, "vcsw": vcsw, "ivcsw": ivcsw,
            "usual_cpu_us": round(self._usual[0], 1),
            "usual_vcsw": round(self._usual[1], 2),
            "usual_ivcsw": round(self._usual[2], 2),
            "gc": [list(c) for c in self._gc], "ticks": ticked,
        }
        lib().eg_telemetry_record_detail_span(
            dur, end_us, json.dumps(detail, separators=(",", ":")).encode())
        record_phase_hist("stall", excess)
        # the next entry's stretch starts here
        self._read, self._read_n = now, self._n


def stall_journal() -> list:
    """The ``train_stall`` entries of this process's slow-span journal,
    slowest first: each the span's ``total_us``/``end_us`` over the
    fields of :class:`StallJournal`'s detail."""
    return [
        {"total_us": s["total_us"], "end_us": s["end_us"], **s["detail"]}
        for s in slow_spans()
        if isinstance(s.get("detail"), dict)
        and s["detail"].get("kind") == "train_stall"
    ]


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

# kind -> (family, help, series-label for the per-kind key suffix;
# scalar kinds have no suffix and ignore the label)
_HIST_FAMILIES = {
    "client_call": ("eg_client_call_latency_us",
                    "Client whole-call latency per RPC op (retries "
                    "included), microseconds", "op"),
    "server_handler": ("eg_server_handler_latency_us",
                       "Server handler time per RPC op (decode + "
                       "execute + encode), microseconds", "op"),
    "server_queue": ("eg_server_queue_wait_us",
                     "Poller-ready to handler pickup wait, microseconds",
                     "op"),
    "dial": ("eg_dial_latency_us", "DialTcp latency, microseconds", "op"),
    "backoff": ("eg_retry_backoff_us",
                "Retry backoff sleeps, microseconds", "op"),
    "phase": ("eg_step_phase_us",
              "Training step-phase wall time (input_stall/sample/h2d/"
              "device/host/step and the training thread's leaves, plus "
              "jit trace/lower/XLA compile, journalled stall excess and "
              "the set-up leaves setup_*), microseconds",
              "phase"),
    "prefetch_depth": ("eg_prefetch_queue_depth",
                       "Ready batches in the prefetch queue at consumer "
                       "dequeue (value histogram)", "op"),
    "prefetch_busy": ("eg_prefetch_workers_busy",
                      "Prefetch workers inside make_batch at consumer "
                      "dequeue (value histogram)", "op"),
    "heat_spread": ("eg_heat_shard_spread",
                    "Shards touched per client call (value histogram "
                    "per op — data-plane heat fan-out attribution)",
                    "op"),
    "serve": ("eg_serve_phase_us",
              "Serve-request phase wall time (queue_wait/sample/"
              "dispatch/total), microseconds", "phase"),
    "serve_batch": ("eg_serve_batch_ids",
                    "Unique ids per micro-batch device dispatch (value "
                    "histogram; count = dispatches, sum = ids)", "op"),
}

_GAUGE_FAMILIES = {
    "workers": ("eg_workers", "Fixed handler pool size"),
    "workers_active": ("eg_workers_active", "Workers currently serving"),
    "queue_depth": ("eg_queue_depth",
                    "Ready connections waiting for a worker"),
    "conns": ("eg_conns", "Admitted open connections"),
    "draining": ("eg_draining", "1 while the server drains"),
    "epoch": ("eg_epoch",
              "Current serving snapshot epoch (0 = base load; each "
              "applied delta flips it up by one)"),
}

# Process resource gauges (eg_blackbox.h: sampled live for every dump,
# background-sampled into the HISTORY ring, frozen into postmortems).
_RESOURCE_FAMILIES = {
    "rss_bytes": ("eg_rss_bytes",
                  "Resident set size of the process, bytes"),
    "open_fds": ("eg_open_fds", "Open file descriptors"),
    "threads": ("eg_threads", "Live OS threads"),
    "cache_bytes": ("eg_cache_bytes",
                    "Client feature-row cache resident bytes"),
    "nbr_cache_bytes": ("eg_nbr_cache_bytes",
                        "Client neighbor-list cache resident bytes"),
    "device_mem_bytes": ("eg_device_mem_bytes",
                         "Device (HBM) bytes in use — memory_stats() "
                         "where present, live-array census on CPU"),
    "device_mem_peak_bytes": ("eg_device_mem_peak_bytes",
                              "High-water mark of eg_device_mem_bytes "
                              "since start/reset"),
    "device_buffers": ("eg_device_buffers",
                       "Live device buffers at the last devprof sample"),
    "feature_table_width": ("eg_feature_table_width",
                            "Logical width (feature_dim) of the device-"
                            "resident dense feature table; 0 = none built"),
    "feature_table_stored_width": ("eg_feature_table_stored_width",
                                   "Width the feature table's rows are "
                                   "stored at: feature_dim rounded up to "
                                   "128 lanes, so rows are contiguous"),
    "store_table_width": ("eg_store_table_width",
                          "Logical width (dim) of the per-node "
                          "historical-embedding stores of the training "
                          "state; 0 = the state has none"),
    "store_table_stored_width": ("eg_store_table_stored_width",
                                 "Lanes a stored row of a store takes in "
                                 "device memory; 0 = the device keeps the "
                                 "store column-major (rows not contiguous)"),
    "alias_edges": ("eg_alias_edges",
                    "Edges of the last flat-CSR alias tables built for the "
                    "exact device draw (--alias_sampling); 0 = none built"),
    "alias_max_degree": ("eg_alias_max_degree",
                         "Longest row of those alias tables: the width a "
                         "padded slab of the same graph would need"),
    "alias_table_bytes": ("eg_alias_table_bytes",
                          "Bytes of those alias tables as uploaded: 12 an "
                          "edge (nbr, alias, prob), 9 a row (off, deg, "
                          "sampleable)"),
    "step_temp_bytes": ("eg_step_temp_bytes",
                        "Temporaries of the compiled train step beside its "
                        "arguments and results (memory_analysis; set by a "
                        "profiled run, 0 otherwise): memory_stats() does "
                        "not count them"),
}


# Counters of the native ledger that are also families of their own
# (beside their eg_counter_total{name=...} series): a rate or a ratio of
# these is what a dashboard plots.
_COUNTER_FAMILIES = {
    "expand_slots": ("eg_expand_slots",
                     "Padded slots the full-neighbourhood expansion "
                     "(graph/device.py multi_hop_neighbor) worked on"),
    "expand_edges": ("eg_expand_edges",
                     "True edges among eg_expand_slots (the mask's sum): "
                     "their ratio is the expansion's slot fill"),
    "expand_overflow_nodes": ("eg_expand_overflow_nodes",
                              "Unique neighbours a hop's static cap had "
                              "no room for, dropped with their edges; 0 "
                              "where the whole neighbourhood is kept"),
}


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in labels.items())
    return "{" + inner + "}"


def _render(sources: list) -> str:
    """Render [(telemetry dict, base labels), ...] as one Prometheus
    text exposition — families emitted once, series per source."""
    lines = []
    edges = bucket_edges_us()

    for kind, (fam, help_text, label) in _HIST_FAMILIES.items():
        lines.append(f"# HELP {fam} {help_text}")
        lines.append(f"# TYPE {fam} histogram")
        for data, base in sources:
            for key, h in sorted(data["hist"].items()):
                k, _, op = key.partition(":")
                if k != kind:
                    continue
                labels = dict(base)
                if op:
                    labels[label] = op
                cum = 0
                for b, n in enumerate(h["b"]):
                    cum += n
                    le = str(edges[b]) if b < len(edges) else "+Inf"
                    bl = dict(labels)
                    bl["le"] = le
                    lines.append(f"{fam}_bucket{_fmt_labels(bl)} {cum}")
                lines.append(
                    f"{fam}_sum{_fmt_labels(labels)} {h['sum_us']}"
                )
                lines.append(
                    f"{fam}_count{_fmt_labels(labels)} {h['count']}"
                )

    lines.append("# HELP eg_counter_total Transport/server event "
                 "counters (see FAULTS.md)")
    lines.append("# TYPE eg_counter_total counter")
    for data, base in sources:
        for name, v in sorted(data["counters"].items()):
            labels = dict(base)
            labels["name"] = name
            lines.append(f"eg_counter_total{_fmt_labels(labels)} {v}")

    for ckey, (fam, help_text) in _COUNTER_FAMILIES.items():
        lines.append(f"# HELP {fam} {help_text}")
        lines.append(f"# TYPE {fam} counter")
        for data, base in sources:
            lines.append(
                f"{fam}{_fmt_labels(dict(base))} "
                f"{data['counters'].get(ckey, 0)}"
            )

    lines.append("# HELP eg_stat_calls_total Span-timer call counts "
                 "per engine op")
    lines.append("# TYPE eg_stat_calls_total counter")
    for data, base in sources:
        for name, (count, total_ns, max_ns) in sorted(
            data["stats"].items()
        ):
            labels = dict(base)
            labels["op"] = name
            lines.append(
                f"eg_stat_calls_total{_fmt_labels(labels)} {count}"
            )

    for gkey, (fam, help_text) in _GAUGE_FAMILIES.items():
        emitted_header = False
        for data, base in sources:
            gauges = data.get("gauges")
            if gauges is None or gkey not in gauges:
                continue
            if not emitted_header:
                lines.append(f"# HELP {fam} {help_text}")
                lines.append(f"# TYPE {fam} gauge")
                emitted_header = True
            lines.append(f"{fam}{_fmt_labels(dict(base))} {gauges[gkey]}")

    for rkey, (fam, help_text) in _RESOURCE_FAMILIES.items():
        emitted_header = False
        for data, base in sources:
            resource = data.get("resource")
            if resource is None or rkey not in resource:
                continue
            if not emitted_header:
                lines.append(f"# HELP {fam} {help_text}")
                lines.append(f"# TYPE {fam} gauge")
                emitted_header = True
            lines.append(
                f"{fam}{_fmt_labels(dict(base))} {resource[rkey]}"
            )

    # live serve-SLO gauges (eg_devprof.h "serve_slo" section): the
    # windowed p50/p99 the SLOTracker pushes through the ABI, plus the
    # lifetime violation count — a scrape reads serving latency without
    # draining the server. Headers always (the section is always
    # emitted, zeros included).
    lines.append("# HELP eg_serve_slo_ms Serve request latency over the "
                 "SLO tracker window, milliseconds")
    lines.append("# TYPE eg_serve_slo_ms gauge")
    for data, base in sources:
        slo = data.get("serve_slo")
        if slo is None:
            continue
        for q in ("p50", "p99"):
            labels = dict(base)
            labels["quantile"] = q
            lines.append(
                f"eg_serve_slo_ms{_fmt_labels(labels)} "
                f"{slo[q + '_us'] / 1000.0:.3f}"
            )
    lines.append("# HELP eg_serve_slo_violations_total Lifetime serve "
                 "replies over the SLO target")
    lines.append("# TYPE eg_serve_slo_violations_total counter")
    for data, base in sources:
        slo = data.get("serve_slo")
        if slo is None:
            continue
        lines.append(
            f"eg_serve_slo_violations_total{_fmt_labels(dict(base))} "
            f"{slo['violations']}"
        )

    # data-plane heat (eg_heat.h "heat" section): per-(side, op) id
    # feeds, cache-efficacy classes, and the top-K concentration
    # headline — nonzero series only, headers always (dashboards before
    # traffic)
    lines.append("# HELP eg_heat_ids_total Vertex ids fed to the heat "
                 "profiler per side and op (client: post-coalesce; "
                 "server: pre-execute)")
    lines.append("# TYPE eg_heat_ids_total counter")
    for data, base in sources:
        heat = data.get("heat")
        if not heat:
            continue
        for key, v in sorted(heat["ids"].items()):
            side, _, op = key.partition(":")
            labels = dict(base)
            labels["side"] = side
            labels["op"] = op
            lines.append(f"eg_heat_ids_total{_fmt_labels(labels)} {v}")
    lines.append("# HELP eg_heat_cache_class_total Feature-cache events "
                 "bucketed by the key's sketch-estimated frequency class "
                 "(class c covers estimates in [2^(c-1), 2^c))")
    lines.append("# TYPE eg_heat_cache_class_total counter")
    for data, base in sources:
        heat = data.get("heat")
        if not heat:
            continue
        for event, classes in sorted(heat["cache_class"].items()):
            for cls, v in enumerate(classes):
                if not v:
                    continue
                labels = dict(base)
                labels["event"] = event
                labels["class"] = str(cls)
                lines.append(
                    f"eg_heat_cache_class_total{_fmt_labels(labels)} {v}"
                )
    lines.append("# HELP eg_heat_topk_share Share of the side's access "
                 "stream absorbed by its tracked top-K hot ids")
    lines.append("# TYPE eg_heat_topk_share gauge")
    for data, base in sources:
        heat = data.get("heat")
        if not heat:
            continue
        for side in ("client", "server"):
            total = heat["sketch"]["total"].get(side, 0)
            if not total:
                continue
            share = min(
                1.0,
                sum(e["count"] for e in heat["topk"][side]) / total,
            )
            labels = dict(base)
            labels["side"] = side
            lines.append(
                f"eg_heat_topk_share{_fmt_labels(labels)} {share:.6f}"
            )

    return "\n".join(lines) + "\n"


def metrics_text(graph=None, shard: int | None = None) -> str:
    """Prometheus text exposition of the telemetry state.

    * no arguments — this process (training client, or a shard served
      in-process);
    * ``graph`` (remote mode) — scrape every shard of the live cluster
      over the STATS opcode, one series set per shard (label
      ``shard="N"``); pass ``shard=`` to scrape just one.

    Every RPC op appears in both the client_call and server_handler
    histogram families even at zero count, so dashboards can be built
    before traffic exists."""
    if graph is None:
        return _render([(telemetry_json(), {})])
    shards = [shard] if shard is not None else list(
        range(graph.num_shards)
    )
    return _render(
        [(scrape(graph, s), {"shard": str(s)}) for s in shards]
    )


# ---------------------------------------------------------------------------
# JSONL emission (run_loop --metrics_every)
# ---------------------------------------------------------------------------


def snapshot(step: int | None = None) -> dict:
    """One compact metrics record for periodic JSONL emission: non-zero
    counters, per-op client-call count + p50/p99 µs, step-phase
    count/p50/p99 per phase plus the headline ``input_stall_ms`` (mean
    consumer stall per step — ROADMAP item 1's acceptance metric), and
    prefetch pipeline means. Gauges-free (local process)."""
    data = telemetry_json()
    ops = {}
    for key, h in data["hist"].items():
        kind, _, op = key.partition(":")
        if kind != "client_call" or h["count"] == 0:
            continue
        pct = percentiles(h, (50, 99))
        ops[op] = {
            "count": h["count"],
            "p50_us": round(pct.get(50, 0.0), 1),
            "p99_us": round(pct.get(99, 0.0), 1),
        }
    phases = {}
    for name, h in phase_hists(data).items():
        if h["count"] == 0:
            continue
        pct = percentiles(h, (50, 99))
        phases[name] = {
            "count": h["count"],
            "p50_us": round(pct.get(50, 0.0), 1),
            "p99_us": round(pct.get(99, 0.0), 1),
        }
    out = {
        "step": step,
        "unix_ms": int(time.time() * 1000),
        "counters": {k: v for k, v in data["counters"].items() if v},
        "ops": ops,
        "phases": phases,
    }
    stall = phase_hists(data).get("input_stall")
    if stall and stall["count"]:
        out["input_stall_ms"] = round(
            stall["sum_us"] / stall["count"] / 1000.0, 3
        )
    for key, name in (("prefetch_depth", "mean_queue_depth"),
                      ("prefetch_busy", "mean_workers_busy")):
        h = data["hist"].get(key)
        if h and h["count"]:
            out.setdefault("prefetch", {})[name] = round(
                h["sum_us"] / h["count"], 2
            )
    return out


def append_metrics_line(path: str, step: int | None = None) -> None:
    """Append one :func:`snapshot` line to a JSONL file (the
    ``run_loop --metrics_every=N`` emitter)."""
    with open(path, "a") as f:
        f.write(json.dumps(snapshot(step)) + "\n")
