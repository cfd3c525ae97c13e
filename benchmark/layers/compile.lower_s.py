"""Lowering jaxprs to MLIR modules, every jit of the process up to the
window's opening, as ``jax.monitoring`` times it and the program's
compile listener records it under the phase ``lower``."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "lower")
