"""``Graph._connect``'s native parse of the ``.dat`` partitions into the
engine (``eg_load``): the program's span ``setup_graph_load``, seconds
before the window opened. The harness's marks time the same stretch
from outside as ``graph_load`` - ``graph_files``, with flag parsing and
``build_graph`` around it."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "setup_graph_load")
