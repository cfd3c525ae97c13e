"""Device time per traced step under the program's ``stores_read`` scope
(``models/base.py`` ``ScalableStoreModel``): the gathers of stale rows
from the per-node stores at the neighbours and of stale gradients from
the gradient stores at the roots, and the ``set`` that clears the rows
just read; fullest chip. Silent on a program that names no such scope."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("stores_read",)


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES) or None
