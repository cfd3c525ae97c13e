"""Reduction of a ``jax.profiler`` capture (``*.xplane.pb``) to numbers.

Reads the capture with ``jax.profiler.ProfileData`` (nothing but JAX).
A TPU capture holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line carries one event per executed HLO op, and host planes
whose lines are threads. Everything below is on the profiler's own
clock (ns); ``align_offset_ns`` maps CLOCK_MONOTONIC onto it through the
``eg_align:<us>`` annotation that ``train()`` stamps after start_trace.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ALIGN = re.compile(r"eg_align:(\d+)")
# the draw kernels are the step's Mosaic custom calls; the trace names an
# op by its HLO text, which carries the call target
DRAW_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
# collectives as XLA names them in a TPU trace
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I,
)


def latest_xplane(profile_dir: str) -> str | None:
    paths = glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(paths, key=os.path.getmtime) if paths else None


def _union(intervals: list) -> list:
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class DeviceLane:
    """The ops of one chip in the capture."""

    def __init__(self, index: int, events: list):
        self.index = index
        # (name, start_ns, end_ns), in start order
        self.events = sorted(events, key=lambda e: e[1])
        self.busy = _union([(s, e) for _, s, e in self.events])

    @property
    def start_ns(self) -> float:
        return self.events[0][1]

    @property
    def end_ns(self) -> float:
        return max(e for _, _, e in self.events)

    def busy_ns(self) -> float:
        return float(sum(e - s for s, e in self.busy))

    def gaps(self) -> list:
        """Idle (start, end) intervals between the first and last op."""
        return [
            (a[1], b[0]) for a, b in zip(self.busy, self.busy[1:])
            if b[0] > a[1]
        ]

    def op_seconds(self, pattern=None) -> dict:
        """name -> seconds, over ops whose name matches ``pattern``
        (all ops when None). Nested events of one op line do not occur on
        the ``XLA Ops`` line, so durations add."""
        out: dict = {}
        for name, s, e in self.events:
            if pattern is None or pattern.search(name):
                out[name] = out.get(name, 0.0) + (e - s) * 1e-9
        return out


class Capture:
    """What the metric readers take from one capture."""

    def __init__(self, lanes: list, align_offset_ns: float | None):
        if not lanes:
            raise ValueError("capture holds no device plane with ops")
        self.lanes = lanes
        self.align_offset_ns = align_offset_ns
        # the traced window: first op start to last op end, over chips
        self.start_ns = min(l.start_ns for l in lanes)
        self.end_ns = max(l.end_ns for l in lanes)

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def busy_s(self) -> float:
        """Mean over the chips of the union of op intervals."""
        return sum(l.busy_ns() for l in self.lanes) / len(self.lanes) * 1e-9

    def fullest(self) -> DeviceLane:
        return max(self.lanes, key=lambda l: l.busy_ns())

    def top_ops(self, n: int = 10, name_chars: int = 160) -> list:
        """The ops that took most device time on the fullest chip, under
        the names the trace shows (HLO text, cut to ``name_chars``)."""
        ops = self.fullest().op_seconds()
        return [
            [k[:name_chars], v]
            for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:n]
        ]

    def idle_by_host_phase(self, phase_events: list, n: int = 10) -> list:
        """Split the idle time of the fullest chip by what the training
        thread was doing meanwhile: each idle gap is shared out among the
        program's spans on that thread that overlap it, and what no span
        covers goes to ``host_between_spans``.

        phase_events: (phase, start_us, dur_us, step, thread) tuples on
        CLOCK_MONOTONIC, as the program's TraceRecorder keeps them (the
        whole-step span ``step`` and the prefetch workers' spans are left
        out). Returns [[name, seconds], ...], largest first."""
        gaps = self.fullest().gaps()
        if self.align_offset_ns is None:
            return [["unaligned", sum(b - a for a, b in gaps) * 1e-9]]
        spans = sorted(
            (ts * 1e3 - self.align_offset_ns,
             (ts + dur) * 1e3 - self.align_offset_ns, phase)
            for phase, ts, dur, _step, thread in phase_events
            if thread == "MainThread" and phase != "step"
        )
        out: dict = {}
        i = 0
        for a, b in gaps:
            while i < len(spans) and spans[i][1] <= a:
                i += 1
            covered = 0.0
            j = i
            while j < len(spans) and spans[j][0] < b:
                lap = min(b, spans[j][1]) - max(a, spans[j][0])
                if lap > 0:
                    name = "host_in_" + spans[j][2]
                    out[name] = out.get(name, 0.0) + lap * 1e-9
                    covered += lap
                j += 1
            rest = (b - a) - covered
            if rest > 0:
                out["host_between_spans"] = (
                    out.get("host_between_spans", 0.0) + rest * 1e-9)
        return [
            [k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]
        ]


def read_capture(path: str) -> Capture:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lanes = []
    align = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = [
                    (ev.name, float(ev.start_ns),
                     float(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                ]
                if events:
                    lanes.append(DeviceLane(int(m.group(1)), events))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                hit = ALIGN.search(ev.name)
                if hit and align is None:
                    # monotonic ns minus profiler ns
                    align = int(hit.group(1)) * 1e3 - float(ev.start_ns)
    return Capture(lanes, align)
