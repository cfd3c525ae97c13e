"""The alias draw's share of its roofline: the bytes the cost function
counts for a step's O(1) alias draws (``draw_bytes`` of
``configs/graphsage_alias_costs.py``: 9 B a row drawn from, 16 B a draw)
over the chip's HBM bandwidth, over the device time per traced step under
the program's ``draw`` scope, fullest chip (as ``draw.kernel_roofline``
reads the slab kernel). Memory-bound by nature: the draw does no matmul.
Claims no scope (``draw.scope_ms`` does). Silent where the cost function
counts no alias draw, or the capture has no scope table."""

from benchmark import scopes


def read(ctx):
    if not ctx.costs.get("alias_draws") or ctx.peaks is None:
        return None
    ms = scopes.scopes_ms(ctx, "draw")
    if not ms:
        return None
    least_ms = ctx.costs["draw_bytes"] / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
