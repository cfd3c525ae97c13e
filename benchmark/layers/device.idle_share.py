"""Share of the traced window in which no op ran on the fullest chip."""


def read(ctx):
    cap = ctx.capture
    if cap is None:
        return None
    lane = cap.fullest()
    return 100.0 * (1.0 - lane.busy_ns() / (cap.end_ns - cap.start_ns))
