"""What the training thread's fences cost a step: the sum of the
program's eg_phase ``fence`` leaf over the window (one span on each step
that fences, every step or every 32nd) over the steps of the window."""

from benchmark import spans


def read(ctx):
    return spans.phase_ms_per_step(ctx, "fence")
