"""Median step time over all steps of the window, from the harness's
``step_hook`` timestamps (host clock; every step is fenced by the
program while telemetry is on)."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx.step_ms, 50)) if len(ctx.step_ms) else None
