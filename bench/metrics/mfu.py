"""Model FLOPs of every prompt and generated token the window's steps
processed (bench/roofline/model_step.py) over window x chips x peak
bf16 FLOP/s, in percent."""

from bench import spec


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    flops = spec.load_module("roofline", "model_step").work(ctx)[0]
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
