"""Process start to the opening of the window: making the weights,
building the engine, loading or compiling its programs, and the warm-up
the traffic needs."""


def read(ctx):
    return ctx.w0 - ctx.t_start
