"""Mean of the program's eg_phase ``device`` span over the window: the
wall time from dispatching the jitted step to its ``block_until_ready``
returning. The fenced dispatch wall, not device busy time."""


def read(ctx):
    return ctx.phase_mean_ms("device")
