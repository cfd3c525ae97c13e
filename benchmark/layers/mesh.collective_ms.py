"""Device time per step of the collective ops (all-reduce and kin) in the
traced window, on the fullest chip."""

from benchmark import xplane


def read(ctx):
    if ctx.chips < 2:
        return None
    return ctx.device_ms_per_step(xplane.COLLECTIVE)
