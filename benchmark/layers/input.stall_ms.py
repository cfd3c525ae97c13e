"""Mean of the program's eg_phase ``input_stall`` span over the window:
how long the training thread waited for the prefetch queue, per step."""


def read(ctx):
    return ctx.phase_mean_ms("input_stall")
