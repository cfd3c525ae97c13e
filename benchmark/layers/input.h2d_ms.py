"""Mean of the program's eg_phase ``h2d`` span over the window: the
host-to-device copy of one batch (inside the prefetch workers)."""


def read(ctx):
    return ctx.phase_mean_ms("h2d")
