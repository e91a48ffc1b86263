"""Bytes the pager moved to and from the far tier in the window (sum of
its bytes_moved_* counters), in MB per 1000 generated tokens."""

from bench.loop import window_tokens


def read(ctx):
    n = window_tokens(ctx.sent, ctx.w0, ctx.w1)
    return 1e3 * ctx.pager_bytes / 1e6 / n if n else None
