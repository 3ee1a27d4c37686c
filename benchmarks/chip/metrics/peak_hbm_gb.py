"""Peak device memory of the run (``memory_stats()["peak_bytes_in_use"]``
on the fullest chip), read after the window, in GB."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9
