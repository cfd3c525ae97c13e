"""Mean of the program's eg_phase ``input_other`` leaf over the window:
the training thread between two loop bodies, less the queue wait
(``input.stall_ms``): the prefetch generator's bookkeeping, the gauges,
the span recording itself."""


def read(ctx):
    return ctx.phase_mean_ms("input_other")
