"""The engine's whole-table exports through numpy
(``graph.get_dense_feature`` of the feature and the label table in
``Model.build_consts``, the sparse tables' host half): the program's
span ``setup_table_export``, seconds before the window opened. Silent on
a model that keeps no such table (the walk family)."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "setup_table_export")
