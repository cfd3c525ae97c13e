"""Device time per traced step under the program's ``gather_features``
and ``gather_labels`` scopes, forward and transposed, fullest chip."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("gather_features", "gather_labels")


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES)
