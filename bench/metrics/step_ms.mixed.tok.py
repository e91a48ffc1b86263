"""``step_ms.mixed`` (bench/metrics/step_ms.mixed.py), held beside
the end-to-end metric output_tok_s in the cells judged on it."""

from bench import spec


def read(ctx):
    return spec.load_module("metrics", "step_ms.mixed").read(ctx)
