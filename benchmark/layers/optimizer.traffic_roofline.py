"""The optimizer's share of the chip's memory bandwidth: the bytes the
optimizer needs by the cell's shapes (the cost function's ``opt_bytes``:
for Adam the parameter, the gradient and both moments read, the
parameter and both moments written) over the chip's HBM bandwidth, over
the device time under the ``optimizer`` scope (``step.optimizer_ms``).
Memory-bound by nature. Silent without a capture and its HLO text, or
where no op carries the scope."""

from benchmark import scopes


def read(ctx):
    ms = scopes.scopes_ms(ctx, "optimizer")
    if not ms or ctx.peaks is None or not ctx.costs.get("opt_bytes"):
        return None
    least_ms = ctx.costs["opt_bytes"] / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
