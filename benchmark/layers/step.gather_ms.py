"""Device time per traced step under the program's ``gather_features``
and ``gather_labels`` scopes, forward and transposed, fullest chip."""

from benchmark import scopes


def read(ctx):
    return scopes.scopes_ms(ctx, "gather_features", "gather_labels")
