"""Mean of the program's eg_phase ``fence`` leaf over the window: the
``block_until_ready`` on the step's loss, from the dispatch's return to
the fence's."""


def read(ctx):
    return ctx.phase_mean_ms("fence")
