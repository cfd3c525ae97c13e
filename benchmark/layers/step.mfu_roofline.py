"""The whole step's share of the chip's peak: the larger of FLOPs over
peak FLOP/s and bytes over peak bytes/s that one step needs by the cell's
shapes (``benchmark/costs.py``), times the traced steps, over the traced
window's wall time. Whatever implements the step, the numerator stays."""


def read(ctx):
    cap = ctx.capture
    if cap is None or ctx.peaks is None:
        return None
    least_s = max(
        ctx.costs["flops"] / ctx.peaks["flops_per_s"],
        ctx.costs["bytes"] / ctx.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s * ctx.trace_steps / cap.window_s
