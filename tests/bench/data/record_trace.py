"""Record the small device trace that tests/bench reads.

Run on one TPU chip, from the repository root:

    python3 tests/bench/data/record_trace.py

It profiles a few steps of a small jitted program inside the benchmark's
``bench/window`` and ``bench/step`` annotations, with host-only gaps
between them, and writes ``tests/bench/data/small.xplane.pb``.
"""

import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "small.xplane.pb"


def main() -> None:
    assert jax.devices()[0].platform == "tpu", "record this on a TPU"
    f = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(f(a, a))
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/step"):
                jax.block_until_ready(f(a, a))
                time.sleep(0.002)           # host work after the device's
            with jax.profiler.TraceAnnotation("bench/wait"):
                time.sleep(0.003)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(src, OUT)
    print(OUT, OUT.stat().st_size)


if __name__ == "__main__":
    main()
