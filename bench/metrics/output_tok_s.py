"""Every token generated inside the window over the window's seconds."""

from bench.loop import window_tokens


def read(ctx):
    return window_tokens(ctx.sent, ctx.w0, ctx.w1) / ctx.window_s
