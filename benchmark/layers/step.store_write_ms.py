"""Device time per traced step under the program's ``stores_write`` scope
(``models/base.py`` ``ScalableStoreModel``): the scatter-add of the
reads' gradients into the gradient stores at the neighbours and the
``set`` of fresh activations into the stores at the roots (with the
rule that picks one row for a root drawn twice); fullest chip. Silent
on a program that names no such scope."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("stores_write",)


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES) or None
