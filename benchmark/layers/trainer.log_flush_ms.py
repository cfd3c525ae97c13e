"""What the log flush costs a step: the sum of the program's eg_phase
``log_flush`` leaf over the window (one span every ``log_every`` steps)
over the steps of the window."""

from benchmark import spans


def read(ctx):
    return spans.phase_ms_per_step(ctx, "log_flush")
