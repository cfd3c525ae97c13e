"""Device busy time per step: the union of the device-op intervals of
the traced window on the fullest chip, over the traced steps."""


def read(ctx):
    cap = ctx.capture
    if cap is None:
        return None
    return cap.fullest().busy_ns() * 1e-6 / ctx.trace_steps
