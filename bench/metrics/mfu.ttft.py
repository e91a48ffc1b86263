"""The whole step's share of the chip's peak (as mfu), held beside the
prefill kernel's roofline in the cells judged on time to first token."""

from bench import spec


def read(ctx):
    return spec.load_module("metrics", "mfu").read(ctx)
