"""``step_ms.decode`` (bench/metrics/step_ms.decode.py), held beside
the end-to-end metric output_tok_s in the cells judged on it."""

from bench import spec


def read(ctx):
    return spec.load_module("metrics", "step_ms.decode").read(ctx)
