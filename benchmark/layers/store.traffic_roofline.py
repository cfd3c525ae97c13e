"""The store traffic's share of the chip's memory bandwidth: the bytes
the per-node stores' reads and writes need by the cell's shapes (the
cost function's ``store_bytes``: a row per neighbour read, three per
root, two per neighbour for the scatter-add) over the chip's HBM
bandwidth, over ALL the device time a step spends on the stores: the two
store scopes and the whole-table layout copies the compiler puts around
them (``store.layout_copy_ms``). Memory-bound by nature: the stores do
no arithmetic but one add a row. Silent where the cost function counts
no store or the program names no store scope."""

from benchmark import scopes, store_tables


def read(ctx):
    store_bytes = ctx.costs.get("store_bytes", 0)
    ms = scopes.scopes_ms(ctx, "stores_read", "stores_write")
    if not ms or not store_bytes or ctx.peaks is None:
        return None
    ms += store_tables.layout_copy_ms(ctx) or 0.0
    least_ms = store_bytes / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
