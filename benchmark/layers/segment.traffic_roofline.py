"""The aggregation's share of the chip's memory bandwidth: the bytes the
full-neighbourhood aggregation needs by the graph function's degree law
(the cost function's ``gather_bytes`` + ``message_bytes``: every unique
node's feature row once, every true edge's message once, whatever the
padding) over the chip's HBM bandwidth, over the device time a step
spends under the ``gather_features``, ``gather_labels`` and
``segment_agg`` scopes. Memory-bound by nature: a message does one add.
Silent where the cost function counts no message or the program names no
``segment_agg`` scope."""

from benchmark import scopes


def read(ctx):
    message_bytes = ctx.costs.get("message_bytes", 0)
    if not message_bytes or ctx.peaks is None:
        return None
    if not scopes.scopes_ms(ctx, "segment_agg"):
        return None
    ms = scopes.scopes_ms(
        ctx, "gather_features", "gather_labels", "segment_agg")
    least_ms = (ctx.costs["gather_bytes"] + message_bytes) \
        / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
