"""Device time per traced step under the program's ``optimizer`` scope
(``opt.update`` and ``apply_updates``), fullest chip."""

from benchmark import scopes


def read(ctx):
    return scopes.scopes_ms(ctx, "optimizer")
