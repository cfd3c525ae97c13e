"""Device time per traced step under the program's ``walk`` and
``negatives`` scopes (``models/shallow.py`` ``_ShallowUnsupModule._inputs``):
the chained single-neighbour draws of the walks with the pair indexing,
and the draw of the negatives from the node sampler; fullest chip.
Silent on a program that names no such scope."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("walk", "negatives")


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES) or None
