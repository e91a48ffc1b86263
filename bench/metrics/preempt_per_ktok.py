"""Engine preemptions in the window per 1000 generated tokens."""

from bench.loop import window_tokens


def read(ctx):
    n = window_tokens(ctx.sent, ctx.w0, ctx.w1)
    return 1e3 * ctx.stats.get("preemptions", 0) / n if n else None
