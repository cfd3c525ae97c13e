"""``pallas_sampling.pack_adjacency``: the slab's second copy, node-major
and lane-aligned for the draw kernels (1,024 B a node at width up to
128), made with numpy on the host: the program's span ``setup_pack``,
seconds before the window opened. Silent where no slab is offered to
the kernels."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "setup_pack")
