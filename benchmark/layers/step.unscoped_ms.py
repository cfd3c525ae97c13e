"""Device time per traced step of the ops that no scope of the program
claims: what the compiler inserted itself (a layout change of a whole
table is the case in point) and the glue between the scopes."""

from benchmark import scopes


def read(ctx):
    return scopes.scopes_ms(ctx, scopes.UNSCOPED)
