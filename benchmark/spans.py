"""Reductions of the program's host spans that more than one reader needs.

The program's training thread tiles each iteration with leaf spans on
CLOCK_MONOTONIC (``euler_tpu.telemetry.PHASE_PARENT``, OBSERVABILITY.md
"Step phases"); its ``TraceRecorder`` keeps them as (phase, start_us,
dur_us, step, thread) tuples and its eg_phase histograms keep their sums.
Imports nothing of the program. A program from before the leaves has no
such histogram and no such span: every function here then returns None.
"""

from __future__ import annotations

TRAIN_THREAD = "MainThread"


def has_phase(ctx, name: str) -> bool:
    """Whether the program keeps an eg_phase histogram of this name."""
    return name in ctx.at_close["phases"]


def phase_ms_per_step(ctx, name: str):
    """Sum of a phase over the window, over the window's steps: what a
    span that not every step has (``log_flush``) costs a step."""
    if not has_phase(ctx, name) or ctx.steps <= 0:
        return None
    return ctx.phase(name)[1] / ctx.steps / 1e3


def _union_us(spans: list) -> int:
    total, reach = 0, None
    for s, e in sorted(spans):
        if reach is None or s > reach:
            total += e - s
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total


def unspanned_ms(phase_events: list):
    """Mean over the recorded steps of the training thread of: the
    ``step`` span less the union of the thread's other spans of that step
    (clipped to it). The earliest recorded step is left out where there
    is a later one: the recorder is switched on inside it, so the leaves
    of its beginning are not in the record, and counting them as bare
    read one step's length over the recorded steps (0.0002 ms over a
    whole window, 0.01 over the 250 steps the recorder keeps since PR 29).
    None where the thread recorded no leaf at all."""
    steps, leaves = {}, {}
    for phase, ts, dur, step, thread in phase_events:
        if thread != TRAIN_THREAD or step is None:
            continue
        if phase == "step":
            steps[step] = (ts, ts + dur)
        else:
            leaves.setdefault(step, []).append((ts, ts + dur))
    if not steps or not any(k in leaves for k in steps):
        return None
    if len(steps) > 1:
        del steps[min(steps)]
    bare = 0
    for k, (s0, s1) in steps.items():
        clipped = [(max(a, s0), min(b, s1)) for a, b in leaves.get(k, ())
                   if min(b, s1) > max(a, s0)]
        bare += (s1 - s0) - _union_us(clipped)
    return bare / len(steps) / 1e3


def edge_gaps_ms(capture, phase_events: list, trace_steps: int):
    """(launch gaps, fence returns) in ms, one pair per traced step: the
    first device op's start less the start of the step's ``dispatch``
    span, and the end of its ``fence`` span less the last device op's end,
    on the fullest chip with the host spans moved onto the profiler's
    clock.

    Which ops are a step's is read off the device lane alone: every
    traced step runs the same program, so the lane's ops in start order
    fall into ``trace_steps`` groups of equal size. The first group is
    the step whose ``dispatch`` began nearest to it (the profiler's device
    clock is off the host's by up to a millisecond a capture, far under a
    step), the others follow in step order. A clock that is off shows as
    a negative gap, not as a wrong match; the two gaps' sum does not
    depend on it. None where the capture is unaligned, the lane does not
    divide into the traced steps, or the spans are missing."""
    if capture is None or capture.align_offset_ns is None:
        return None
    off = capture.align_offset_ns
    dispatch, fence = {}, {}
    for phase, ts, dur, step, thread in phase_events:
        if thread != TRAIN_THREAD:
            continue
        if phase == "dispatch":
            dispatch[step] = ts * 1e3 - off
        elif phase == "fence":
            fence[step] = (ts + dur) * 1e3 - off
    events = capture.fullest().events  # in start order
    per_step, rest = divmod(len(events), max(int(trace_steps), 1))
    if not dispatch or rest or not per_step:
        return None
    first = min(dispatch, key=lambda k: abs(dispatch[k] - events[0][1]))
    launch, ret = [], []
    for i in range(int(trace_steps)):
        d, f = dispatch.get(first + i), fence.get(first + i)
        if d is None or f is None:
            continue
        ops = events[i * per_step:(i + 1) * per_step]
        launch.append((ops[0][1] - d) * 1e-6)
        ret.append((f - max(e[2] for e in ops)) * 1e-6)
    return (launch, ret) if launch else None
