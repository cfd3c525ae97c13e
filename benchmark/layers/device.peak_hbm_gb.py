"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest
chip, in GB (1e9 bytes). Silent where the backend reports none."""


def read(ctx):
    if not ctx.memory_peak_bytes or ctx.peaks is None:
        return None
    return ctx.memory_peak_bytes / 1e9
