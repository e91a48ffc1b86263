"""1 - (union of device operation intervals / traced window), averaged
over the chips used."""

from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 1.0 - trace.mean_busy_s(ctx.trace) / ctx.trace.window_s
