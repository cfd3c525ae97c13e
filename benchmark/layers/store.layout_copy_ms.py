"""Device time per traced step of whole-table copies of the per-node
stores that the compiler inserted around the program's store scopes (a
change of layout before the gathers and back after the writes):
unscoped ops whose result has the stores' shape; fullest chip. A part of
``step.unscoped_ms``, named so that the store layer's whole device cost
is ``step.store_read_ms`` + ``step.store_write_ms`` + this. 0 where the
device keeps the stores in the layout the step works in. Silent on a
program that names no store scope."""

from benchmark import store_tables


def read(ctx):
    return store_tables.layout_copy_ms(ctx)
