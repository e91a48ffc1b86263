"""``device_idle_frac`` (bench/metrics/device_idle_frac.py), held beside
the end-to-end metric output_tok_s in the cells judged on it."""

from bench import spec


def read(ctx):
    return spec.load_module("metrics", "device_idle_frac").read(ctx)
