"""The pair rows' share of the chip's memory bandwidth: the bytes the
gathers from the two id-embedding tables and the scatter-adds of their
gradients need by the cell's shapes (the cost function's
``pair_row_bytes``: a row read per pair member, a row read and written
per gradient share) over the chip's HBM bandwidth, over the device time
under the ``pair_rows`` scope. Memory-bound by nature: a row does one
add. Silent where the cost function counts no pair row or the program
names no such scope."""

from benchmark import scopes


def read(ctx):
    pair_row_bytes = ctx.costs.get("pair_row_bytes", 0)
    ms = scopes.scopes_ms(ctx, "pair_rows")
    if not ms or not pair_row_bytes or ctx.peaks is None:
        return None
    least_ms = pair_row_bytes / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
