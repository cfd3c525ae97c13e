"""Time of the training thread that no leaf span covers: per recorded
step, the ``step`` span less the union of the thread's leaves, mean."""

from benchmark import spans


def read(ctx):
    if not spans.has_phase(ctx, "input_other"):
        return None  # a program from before the leaves: nothing tiles
    return spans.unspanned_ms(ctx.phase_events)
