"""Device time per traced step under the program's ``expand`` scope
(``graph/device.py`` ``multi_hop_neighbor``: the whole full-neighbourhood
expansion of the GCN family: the slab-row gathers of every hop, the sort
of the padded slots, the rank, and the two element scatters that make the
hop's set and the inverse map); fullest chip. Silent on a program that
names no such scope."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("expand",)


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES) or None
