"""Unified Chrome-trace / Perfetto export for the step-phase profiler.

The histograms (telemetry.py) say how MUCH time each phase takes; this
module says WHEN — one merged trace where a slow training step can be
followed from the consumer's ``input_stall`` slice to the prefetch
worker's ``sample`` slice to the exact shard handler that caused it,
linked by the PR-5 wire-v3 trace ids.

Three inputs merge into one ``traceEvents`` JSON (the Chrome trace
format Perfetto and chrome://tracing both open):

  * per-step phase events — :class:`TraceRecorder` taps
    ``telemetry.record_phase`` while active, so the train loop and
    prefetch workers need no extra plumbing;
  * this process's slow-span journal (client side of every RPC);
  * each live shard's journal via the STATS scrape (server side).

Timeline: CLOCK_MONOTONIC microseconds — ``time.monotonic_ns()//1000``
in Python, ``std::chrono::steady_clock`` in the native spans
(``end_us``). The epoch is machine-wide, so phase events and shard
spans from different PROCESSES on one host line up exactly. Shards on
other hosts sit at their own clock offset; the trace-id FLOW events
("s"/"f" pairs) still draw the client-call → server-handler arrows
regardless of skew.

Surfaces: ``run_loop --trace_file=`` writes the merged trace at the end
of training; ``scripts/trace_dump.py`` exports from a live cluster (or
merges into an existing trace file) standalone.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import threading
from collections import deque

from euler_tpu import telemetry as _telemetry

# Synthetic pids: one "process" lane per source in the merged view.
PID_TRAIN = 1
PID_SHARD_BASE = 100  # shard s renders as pid 100+s
PID_DEVICE_BASE = 200  # jax.profiler device lanes render from pid 200

# Name of the alignment marker devprof stamps into a jax.profiler
# capture. The profiler's timestamps sit on their own epoch (NOT
# CLOCK_MONOTONIC — observed ~850 s apart on Linux); embedding the
# monotonic µs in an annotation name lets ingest_profiler_dir solve
# for the offset exactly instead of guessing from wall clocks.
ALIGN_PREFIX = "eg_align:"

# The ``jax.named_scope`` names at the layer boundaries of the jitted
# train step (OBSERVABILITY.md "Device plane": "Step scopes"). Every
# device op of the step carries the scope it was traced under in its HLO
# ``op_name`` (the backward pass as ``transpose(jvp(...))/<scope>/...``),
# which is how a capture's device time is split by what the program was
# doing and not by XLA's instruction names. The benchmark's readers
# (benchmark/layers/) each claim the scopes they sum; a test holds every
# name here to exactly one reader. ``stores_read`` and ``stores_write``
# are the per-node stores of models/base.py ScalableStoreModel: the
# gathers from the stores and gradient stores with the clearing set, and
# the scatter-add of gradients with the set of fresh activations.
# ``walk``, ``negatives`` and ``pair_rows`` are the shallow embedding
# models' (models/shallow.py): the chained single-neighbour draws of the
# device walks with the pair indexing, the negatives' draw from the node
# sampler, and the gathers from the id-embedding tables with, transposed,
# the scatter-adds of their gradients. ``expand`` and ``segment_agg``
# are the full-neighbourhood family's (models/gcn.py): all of
# graph/device.py multi_hop_neighbor (slab-row gathers, the sort, the
# rank, the two scatters), and the sparse aggregators' work over the
# padded edge list (nn/sparse_aggregators.py: the gather by ``dst``, the
# mask, the degree, the segment sum, the division). ``edge_softmax`` is
# the attention aggregator's work over that list (the logits, the max,
# the exp, the sum, the weighting of the messages and the normalisation,
# forward and transposed; its projections and gates are matmuls under
# ``dense``); innermost, so inside ``segment_agg`` it is its own.
STEP_SCOPES = ("draw", "gather_features", "gather_labels", "aggregate",
               "dense", "loss", "optimizer", "stores_read", "stores_write",
               "walk", "negatives", "pair_rows", "expand", "segment_agg",
               "edge_softmax")

# File ``train(profile_dir=)`` leaves the compiled step's HLO text in,
# beside the capture: the map from a trace event's instruction name to
# its scope.
STEP_HLO_FILE = "train_step.hlo.txt"


# CLOCK_MONOTONIC µs — the exporter's one clock (matches the native
# spans' steady_clock end_us stamps)
now_us = _telemetry.now_us


class TraceRecorder:
    """Bounded in-memory buffer of step-phase events.

    ``start()`` registers the recorder as the telemetry phase sink;
    every ``record_phase(phase, us, step, end_us)`` anywhere in the
    process (train loop, prefetch consumer, prefetch workers) then lands
    here with its thread identity, until ``stop()``: placed to end at
    the caller's ``end_us`` stamp (CLOCK_MONOTONIC µs, read where the
    span was measured), or now when the caller gave none. Events are
    (phase, start_us, dur_us, step, thread) tuples; a leaf's parent is
    ``telemetry.PHASE_PARENT[phase]``; a span outside the loop (set-up,
    the compile listener's) carries, in the step's place, a dict of what
    it worked on or None. The buffer is a ring:
    beyond ``capacity`` events the oldest fall off (``dropped`` counts
    them) — a week-long run cannot OOM the trainer."""

    def __init__(self, capacity: int = 200_000):
        self._events: deque = deque(maxlen=max(int(capacity), 1))
        self._lock = threading.Lock()
        self.dropped = 0
        self.started_us: int | None = None

    def start(self) -> "TraceRecorder":
        self.started_us = now_us()
        _telemetry.set_trace_sink(self._on_phase)
        return self

    def stop(self) -> None:
        # ``==``: two reads of a bound method are equal, never identical
        if _telemetry._trace_sink == self._on_phase:
            _telemetry.set_trace_sink(None)

    def _on_phase(self, phase: str, us: float, step: int | None,
                  end_us: int | None = None) -> None:
        end = now_us() if end_us is None else end_us
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(
                (phase, end - max(int(us), 0), int(us), step,
                 threading.current_thread().name)
            )

    def events(self) -> list:
        with self._lock:
            return list(self._events)


def _phase_trace_events(phase_events: list) -> list:
    """Recorder tuples -> complete ("X") slice events on the train pid,
    one tid lane per recording thread."""
    out = []
    tids: dict = {}
    for phase, ts, dur, step, thread_name in phase_events:
        tid = tids.setdefault(thread_name, len(tids) + 1)
        ev = {
            "name": phase, "cat": "phase", "ph": "X",
            "ts": ts, "dur": dur, "pid": PID_TRAIN, "tid": tid,
        }
        if isinstance(step, dict):
            ev["args"] = step
        elif step is not None:
            ev["args"] = {"step": step}
        out.append(ev)
    for thread_name, tid in tids.items():
        out.append({
            "name": "thread_name", "ph": "M", "pid": PID_TRAIN,
            "tid": tid, "args": {"name": thread_name},
        })
    return out


def _span_trace_events(data: dict, pid: int, label: str) -> list:
    """One telemetry dump's slow-span journal -> slice events (client
    spans on tid 90, server spans on tid 91) carrying the wire-v3 trace
    id, outcome, and the queue/handler/wire decomposition."""
    out = []
    for s in data.get("slow_spans", []):
        end = int(s.get("end_us", 0))
        dur = int(s["total_us"])
        server = s["side"] == "server"
        out.append({
            "name": s["op"], "cat": "rpc", "ph": "X",
            "ts": end - dur, "dur": dur,
            "pid": pid, "tid": 91 if server else 90,
            "args": {
                "trace": f"{int(s['trace']):#x}",
                "side": s["side"], "outcome": s["outcome"],
                "shard": s["shard"], "queue_us": s["queue_us"],
                "handler_us": s["handler_us"], "wire_us": s["wire_us"],
                "source": label,
            },
        })
    out.append({
        "name": "process_name", "ph": "M", "pid": pid,
        "args": {"name": label},
    })
    for tid, name in ((90, "rpc client calls"), (91, "rpc handlers")):
        out.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": name},
        })
    return out


def _flow_events(span_events: list) -> list:
    """Client-call -> server-handler flow arrows: for every wire-v3
    trace id seen on BOTH a client and a server span, emit an
    "s"/"f" pair so Perfetto links them across process lanes (and
    across clock skew, when shards live on other hosts)."""
    by_trace: dict = {}
    for ev in span_events:
        args = ev.get("args")
        if not args or "trace" not in args:
            continue
        if int(args["trace"], 16) == 0:
            continue  # id not propagated (v1/v2 peer / telemetry off)
        side = args["side"]
        by_trace.setdefault(args["trace"], {})[side] = ev
    out = []
    for trace, sides in by_trace.items():
        if "client" not in sides or "server" not in sides:
            continue
        cli, srv = sides["client"], sides["server"]
        common = {"name": "rpc", "cat": "rpc-flow", "id": trace}
        out.append({**common, "ph": "s", "ts": cli["ts"],
                    "pid": cli["pid"], "tid": cli["tid"]})
        out.append({**common, "ph": "f", "bp": "e",
                    "ts": srv["ts"] + srv["dur"],
                    "pid": srv["pid"], "tid": srv["tid"]})
    return out


def align_annotation(monotonic_us: int | None = None):
    """Context manager stamping the clock-alignment marker into an
    active ``jax.profiler`` capture: a named TraceAnnotation whose name
    carries CLOCK_MONOTONIC µs, so ingestion can map the profiler's
    private epoch onto the exporter's timeline exactly. Enter it (with
    an empty body) right after ``start_trace``."""
    import jax

    return jax.profiler.TraceAnnotation(
        f"{ALIGN_PREFIX}{monotonic_us if monotonic_us is not None else now_us()}"
    )


def stamp_alignment() -> int:
    """Stamp the alignment marker into the ``jax.profiler`` capture that
    has just started, and return the µs the stamp is good to: the time
    between the clock reading the marker's name carries and the reading
    after the marker closed (the marker's own start lies between them).

    The first annotation after ``start_trace`` pays the tracer's set-up
    (about a millisecond on the v5e host, PERF.md PR 27, which put every
    host span that much late against the device lanes), so a throwaway
    one goes first."""
    import jax

    with jax.profiler.TraceAnnotation("eg_align_warmup"):
        pass
    t0 = now_us()
    with align_annotation(t0):
        pass
    return now_us() - t0


def _latest_profiler_trace(profile_dir: str) -> str | None:
    """Newest ``*.trace.json(.gz)`` under the TensorBoard-style layout
    ``<dir>/plugins/profile/<run>/`` that jax.profiler writes."""
    root = os.path.join(profile_dir, "plugins", "profile")
    paths = glob.glob(os.path.join(root, "*", "*.trace.json.gz"))
    paths += glob.glob(os.path.join(root, "*", "*.trace.json"))
    return max(paths, key=os.path.getmtime) if paths else None


# Which profiler lanes are device-plane: TPU/GPU device processes, or
# the XLA runtime executor threads (on CPU the kernel slices land on
# threads named ``tf_XLATfrtCpuClient/...`` inside the python process).
_DEVICE_PID_RE = re.compile(r"XLA|TPU|GPU|[Dd]evice")
_DEVICE_TID_RE = re.compile(r"XLA")


def ingest_profiler_dir(profile_dir: str, max_events: int = 50_000) -> list:
    """A ``jax.profiler`` trace directory -> device-lane trace events
    aligned to the exporter's CLOCK_MONOTONIC timeline.

    Reads the newest capture, keeps the complete ("X") slices on
    device/XLA-runtime lanes, shifts their timestamps by the offset
    solved from the ``eg_align:<monotonic_us>`` annotation (raw
    profiler time if no marker was stamped), and remaps pids to the
    PID_DEVICE_BASE block so the kernels render as their own process
    lanes next to the host phases. Returns [] when the directory holds
    no capture — trace export must never fail a training teardown."""
    path = _latest_profiler_trace(profile_dir)
    if path is None:
        return []
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            raw = json.load(f)
    except Exception:
        return []
    events = raw.get("traceEvents") or []

    pid_names: dict = {}
    tid_names: dict = {}
    offset = None
    for ev in events:
        if ev.get("ph") == "M":
            if ev.get("name") == "process_name":
                pid_names[ev.get("pid")] = (ev.get("args") or {}).get(
                    "name", ""
                )
            elif ev.get("name") == "thread_name":
                tid_names[(ev.get("pid"), ev.get("tid"))] = (
                    ev.get("args") or {}
                ).get("name", "")
        elif offset is None and "ts" in ev:
            m = re.search(ALIGN_PREFIX + r"(\d+)", str(ev.get("name", "")))
            if m:
                offset = int(m.group(1)) - int(ev["ts"])
    if offset is None:
        offset = 0  # unstamped capture: lanes keep the profiler epoch

    lanes: dict = {}  # source pid -> synthetic device pid
    used_tids: set = set()
    out = []
    for ev in events:
        if ev.get("ph") != "X" or "ts" not in ev:
            continue
        pid, tid = ev.get("pid"), ev.get("tid")
        if not (
            _DEVICE_PID_RE.search(pid_names.get(pid, ""))
            or _DEVICE_TID_RE.search(tid_names.get((pid, tid), ""))
        ):
            continue
        new_pid = lanes.setdefault(pid, PID_DEVICE_BASE + len(lanes))
        used_tids.add((pid, tid))
        out.append({
            "name": ev.get("name", "?"), "cat": "device", "ph": "X",
            "ts": int(ev["ts"]) + offset, "dur": int(ev.get("dur", 0)),
            "pid": new_pid, "tid": tid,
        })
    if len(out) > max_events:
        # Keep the biggest slices: a multi-step device capture can hold
        # millions of sub-µs events that would swamp the merged export.
        out.sort(key=lambda e: e["dur"], reverse=True)
        del out[max_events:]
        out.sort(key=lambda e: e["ts"])
    for pid, new_pid in lanes.items():
        out.append({
            "name": "process_name", "ph": "M", "pid": new_pid,
            "args": {"name": f"device: {pid_names.get(pid) or pid}"},
        })
    for pid, tid in used_tids:
        name = tid_names.get((pid, tid))
        if name:
            out.append({
                "name": "thread_name", "ph": "M", "pid": lanes[pid],
                "tid": tid, "args": {"name": name},
            })
    return out


def chrome_trace(phase_events: list | None = None,
                 span_sources: list | None = None,
                 base_events: list | None = None) -> dict:
    """Build the merged trace dict.

    phase_events: TraceRecorder tuples (or None);
    span_sources: [(telemetry dump dict, pid, label), ...];
    base_events: pre-built traceEvents to merge under (an existing
    trace file's, in trace_dump.py's merge mode)."""
    events = list(base_events or [])
    if phase_events:
        events.extend(_phase_trace_events(phase_events))
        events.append({
            "name": "process_name", "ph": "M", "pid": PID_TRAIN,
            "args": {"name": "train (step phases)"},
        })
    span_events: list = []
    for data, pid, label in span_sources or []:
        span_events.extend(_span_trace_events(data, pid, label))
    events.extend(span_events)
    events.extend(_flow_events(
        [e for e in events if e.get("cat") == "rpc"]
    ))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def gather_span_sources(graph=None) -> list:
    """This process's journal plus — for a live remote graph — every
    reachable shard's, as ``chrome_trace`` span_sources. A shard that
    fails to scrape is skipped (trace export must never fail a training
    teardown), noted under its label."""
    sources = [(_telemetry.telemetry_json(), PID_TRAIN,
                "train (client journal)")]
    if graph is not None and getattr(graph, "mode", None) == "remote":
        for s in range(graph.num_shards):
            try:
                sources.append((_telemetry.scrape(graph, s),
                                PID_SHARD_BASE + s, f"shard {s}"))
            except Exception:
                pass  # unreachable shard: trace ships without its side
    return sources


def write_trace(path: str, recorder: TraceRecorder | None = None,
                graph=None, base_events: list | None = None,
                profile_dir: str | None = None) -> dict:
    """Export the merged trace to ``path`` and return it. When a
    ``jax.profiler`` capture directory is given its device lanes merge
    in, time-aligned with the host phase events."""
    base = list(base_events or [])
    if profile_dir:
        base.extend(ingest_profiler_dir(profile_dir))
    trace = chrome_trace(
        recorder.events() if recorder is not None else None,
        gather_span_sources(graph),
        base,
    )
    with open(path, "w") as f:
        json.dump(trace, f)
    return trace


def validate_chrome_trace(trace: dict) -> list:
    """Structural validity check (tests + trace_dump --smoke): returns
    the trace's events after asserting the Chrome-trace invariants the
    viewers rely on. Raises ValueError on the first violation."""
    if not isinstance(trace, dict) or "traceEvents" not in trace:
        raise ValueError("not a Chrome trace: no traceEvents key")
    events = trace["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("traceEvents is not a list")
    for ev in events:
        for k in ("name", "ph", "pid"):
            if k not in ev:
                raise ValueError(f"event missing {k!r}: {ev}")
        if ev["ph"] == "X":
            if "ts" not in ev or "dur" not in ev:
                raise ValueError(f"X event missing ts/dur: {ev}")
            if ev["dur"] < 0 or not isinstance(ev["ts"], int):
                raise ValueError(f"bad X timing: {ev}")
        if ev["ph"] in ("s", "f") and "id" not in ev:
            raise ValueError(f"flow event missing id: {ev}")
    return events


def correlated_trace_ids(trace: dict) -> set:
    """Trace ids carried by BOTH a client and a server rpc slice — the
    cross-process correlation the acceptance test pins."""
    sides: dict = {}
    for ev in trace["traceEvents"]:
        args = ev.get("args") or {}
        if ev.get("cat") == "rpc" and "trace" in args:
            sides.setdefault(args["trace"], set()).add(args["side"])
    return {t for t, ss in sides.items()
            if {"client", "server"} <= ss and int(t, 16) != 0}
