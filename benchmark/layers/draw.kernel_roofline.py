"""The draw kernel's share of its roofline: the bytes the draws need by
the cell's shapes (for every row drawn from, W ids and W cumulative
weights, plus the picks written) over the chip's HBM bandwidth, over the
kernel's device time per step. Memory-bound: the draws do no matmul."""

from benchmark import xplane


def read(ctx):
    ms = ctx.device_ms_per_step(xplane.DRAW_KERNEL)
    if ms is None or ctx.peaks is None or ctx.costs["draw_bytes"] <= 0:
        return None
    least_ms = ctx.costs["draw_bytes"] / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
