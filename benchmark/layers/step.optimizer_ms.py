"""Device time per traced step under the program's ``optimizer`` scope
(``opt.update`` and ``apply_updates``), fullest chip."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("optimizer",)


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES)
