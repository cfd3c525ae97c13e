"""The attention's share of the chip's memory bandwidth: the bytes the
graph attention over whole neighbourhoods needs by the graph function's
degree law (the cost function's ``gather_bytes`` + ``attention_bytes``:
every unique node's feature row once; per true edge and head the
projected message read once and two scalars, whatever the padding) over
the chip's HBM bandwidth, over the device time a step spends under the
``gather_features``, ``segment_agg`` and ``edge_softmax`` scopes.
Memory-bound by nature: a message does one multiply-add an element.
Silent where the cost function counts no ``attention_bytes`` or the
program names no ``edge_softmax`` scope."""

from benchmark import scopes


def read(ctx):
    attention_bytes = ctx.costs.get("attention_bytes", 0)
    if not attention_bytes or ctx.peaks is None:
        return None
    if not scopes.scopes_ms(ctx, "edge_softmax"):
        return None
    ms = scopes.scopes_ms(
        ctx, "gather_features", "segment_agg", "edge_softmax")
    least_ms = (ctx.costs["gather_bytes"] + attention_bytes) \
        / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
