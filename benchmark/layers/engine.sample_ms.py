"""Mean of the program's eg_phase ``sample`` span over the window: one
``model.sample()`` call inside a prefetch worker (the host engine's
fan-out in the host-sampled cells; a seed and a clip in the others)."""


def read(ctx):
    return ctx.phase_mean_ms("sample")
