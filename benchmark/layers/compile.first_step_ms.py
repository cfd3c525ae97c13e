"""Compile wall time before the first dispatched step, as the program's
``devprof.compile_summary()`` gives it at that step: the part of
``setup_s`` the persistent compile cache removes on a second run."""


def read(ctx):
    cs = ctx.first_step_compile
    if not cs or ctx.peaks is None:
        return None
    return float(cs["compile_ms_total"])
