"""Share of its roofline that chunked paged prefill attention reached:
the work of bench/roofline/paged_prefill_flash.py over the kernel's
device time in the trace, in percent."""


def read(ctx):
    r = ctx.roofline("paged_prefill_flash")
    return None if r is None else 100.0 * r
