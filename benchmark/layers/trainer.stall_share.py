"""Share of the window's wall time that the journalled stalls took: the
``sum_us`` of the program's eg_phase ``stall`` histogram over the window
(one sample per step over max(5 x the running median, 50 ms), its value
the step's excess over that median) over the window's wall time."""

from benchmark import spans


def read(ctx):
    if not spans.has_phase(ctx, "stall") or ctx.window_s <= 0:
        return None
    return 100.0 * ctx.phase("stall")[1] * 1e-6 / ctx.window_s
