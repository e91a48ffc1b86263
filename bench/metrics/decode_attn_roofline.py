"""Share of its roofline that paged decode attention reached: the work
of bench/roofline/paged_decode_attention.py over the kernel's device
time in the trace, in percent."""


def read(ctx):
    r = ctx.roofline("paged_decode_attention")
    return None if r is None else 100.0 * r
