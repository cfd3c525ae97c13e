"""Device time per traced step under the program's ``aggregate``,
``dense`` and ``loss`` scopes, forward and transposed, fullest chip."""

from benchmark import scopes


def read(ctx):
    return scopes.scopes_ms(ctx, "aggregate", "dense", "loss")
