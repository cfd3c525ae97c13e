"""Device time per traced step under the program's ``pair_rows`` scope
(``models/shallow.py`` ``_ShallowUnsupModule``): the gathers of the
pairs' and the negatives' rows from the two id-embedding tables and,
transposed, the scatter-adds of their gradients into the dense
gradients; fullest chip. Silent on a program that names no such scope."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("pair_rows",)


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES) or None
