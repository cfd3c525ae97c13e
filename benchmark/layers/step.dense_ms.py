"""Device time per traced step under the program's ``aggregate``,
``dense`` and ``loss`` scopes, forward and transposed, fullest chip."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("aggregate", "dense", "loss")


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES)
