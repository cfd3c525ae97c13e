"""The device-sampling structures built on the host: the chunked
full-neighbour export and the numpy slab build of
``device.build_adjacency``, the alias tables (``eg_build_alias_csr``)
and the node samplers of ``add_sampling_consts``: the program's span
``setup_adjacency``, seconds before the window opened. Silent where the
host samples (no slab is built)."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "setup_adjacency")
