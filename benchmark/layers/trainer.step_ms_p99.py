"""99th percentile of the step time over all steps of the window, from
the harness's ``step_hook`` timestamps. Needs ten steps beyond it, so it
is silent in a window of fewer than 1,000 steps."""

import numpy as np


def read(ctx):
    if len(ctx.step_ms) < 1000:
        return None
    return float(np.percentile(ctx.step_ms, 99))
