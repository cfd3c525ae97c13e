"""Device time per traced step under the program's ``edge_softmax`` scope
(``nn/sparse_aggregators.py``: the attention aggregator's work over the
padded edge list: the logits, the max, the exp, the sum, the weighting
of the messages and the normalisation, forward and transposed; the
projections and the gates ride ``dense``, the table rows
``gather_features``, the gather of later layers' messages by ``dst``
``segment_agg``); fullest chip. Silent on a program that names no such scope."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("edge_softmax",)


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES) or None
