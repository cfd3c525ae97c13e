"""Tracing jitted functions to jaxprs, every jit of the process up to the
window's opening (the step, the tables' conversions, the reference's
replay of the draws), as ``jax.monitoring`` times it and the program's
compile listener records it under the phase ``trace``: self times, so a
jit traced inside another's trace counts once. An unrolled kernel stage
shows here and nowhere in ``compile.first_step_ms``."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "trace")
