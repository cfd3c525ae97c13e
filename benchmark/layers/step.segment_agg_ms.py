"""Device time per traced step under the program's ``segment_agg`` scope
(``nn/sparse_aggregators.py``: the sparse aggregators' work over the
padded edge list: the gather of the messages by ``dst``, the mask, the
degree, the segment sum and the division, forward and transposed; the
matmuls ride ``dense``, the feature rows ``gather_features``); fullest
chip. Silent on a program that names no such scope."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("segment_agg",)


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES) or None
