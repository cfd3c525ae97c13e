"""From the start of the step's ``dispatch`` span to its first device
op's start, on the aligned clock: median over the traced steps, fullest
chip. How long the chip waits for the launch."""

import statistics

from benchmark import spans


def read(ctx):
    gaps = spans.edge_gaps_ms(
        ctx.capture, ctx.phase_events, ctx.trace_steps)
    return statistics.median(gaps[0]) if gaps else None
