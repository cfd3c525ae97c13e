"""Device time per step of the draw kernel: the Mosaic custom calls of
the traced window on the fullest chip, over the traced steps."""

from benchmark import xplane


def read(ctx):
    return ctx.device_ms_per_step(xplane.DRAW_KERNEL)
