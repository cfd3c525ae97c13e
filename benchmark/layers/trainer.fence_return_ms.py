"""From the step's last device op's end to the end of its ``fence``
span, on the aligned clock: median over the traced steps, fullest chip.
How long the host takes to notice that the device is done."""

import statistics

from benchmark import spans


def read(ctx):
    gaps = spans.edge_gaps_ms(
        ctx.capture, ctx.phase_events, ctx.trace_steps)
    return statistics.median(gaps[1]) if gaps else None
