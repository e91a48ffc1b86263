"""peak_bytes_in_use of the fullest chip after the window, in GB."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes > 0 \
        else None
