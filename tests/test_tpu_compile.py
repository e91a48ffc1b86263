"""Compile the paged attention kernels for a described TPU v5e.

Interpret mode never checks Mosaic's blocking rules (the last two dims
of every block divisible by (8, 128) or equal to the array's), so a
kernel can pass every interpret-vs-XLA anchor and still be refused by
the TPU compiler.  These tests compile the three page-gather kernels of
the serving path — decode, speculative verify, chunked prefill — in
bf16 and int8-pool form at phi4-mini-3.8b's published widths (24 query
heads, 8 KV heads, head_dim 128, page 16) for a ``v5e:2x2`` topology
described, not attached, and check that a Mosaic kernel is in the
program: at max_len 4096 (256 pages a row), at 4000 (250 pages, which
their blocks of frames do not divide, so a row's last block is ragged)
and with the 2 (decode and prefill also 1) KV heads a device holds when
the pool is sharded over 4 or 8 devices (where they are fewer than the
pool dtype's row tile, XLA keeps each frame head-major).  No
operation outside the kernel may write an array the size of the pool:
the kernels read the pool as it lies, with no copy, pad or relayout of
it per call.  Nothing runs; no chip is needed.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and under several
pytest workers only the worker that runs this file should.
"""

import math
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_verify_attention)
from repro.kernels.flash_attention import paged_prefill_flash

# phi4-mini-3.8b widths; the serving shapes chip_smoke.py runs
Q_HEADS, HKV, D, PAGE = 24, 8, 128, 16
MAX_BATCH, N_FRAMES = 16, 1025
VERIFY_ROWS = 4                       # speculate_k = 3
CHUNK_ROWS, CHUNK_TOKENS = 2, 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "absent"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but can never be read back without one: keep it off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _pool_args(sharding, pool_dtype, kv_heads):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = s((N_FRAMES, PAGE, kv_heads, D), pool_dtype)
    scales = {}
    if pool_dtype != jnp.bfloat16:
        scales = {"k_scales": s((N_FRAMES, kv_heads), jnp.float32),
                  "v_scales": s((N_FRAMES, kv_heads), jnp.float32)}
    return s, pool, scales


def _lower(kernel, pool_dtype, sharding, pages_per_seq, kv_heads):
    s, pool, scales = _pool_args(sharding, pool_dtype, kv_heads)
    H = kv_heads * (Q_HEADS // HKV)
    if kernel == "decode":
        return paged_decode_attention.lower(
            s((MAX_BATCH, H, D), jnp.bfloat16), pool, pool,
            s((MAX_BATCH, pages_per_seq), jnp.int32),
            s((MAX_BATCH,), jnp.int32), interpret=False, **scales)
    if kernel == "verify":
        return paged_verify_attention.lower(
            s((MAX_BATCH, VERIFY_ROWS, H, D), jnp.bfloat16), pool, pool,
            s((MAX_BATCH, pages_per_seq), jnp.int32),
            s((MAX_BATCH, VERIFY_ROWS), jnp.int32), interpret=False,
            **scales)
    return paged_prefill_flash.lower(
        s((CHUNK_ROWS, H, CHUNK_TOKENS, D), jnp.bfloat16), pool, pool,
        s((CHUNK_ROWS, pages_per_seq), jnp.int32),
        s((CHUNK_ROWS,), jnp.int32), s((CHUNK_ROWS,), jnp.int32),
        interpret=False, **scales)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("kernel,max_len,kv_heads", [
    ("decode", 4096, HKV), ("verify", 4096, HKV), ("prefill", 4096, HKV),
    ("decode", 4000, HKV), ("verify", 4000, HKV), ("prefill", 4000, HKV),
    ("decode", 4096, 2), ("decode", 4096, 1), ("verify", 4096, 2),
    ("prefill", 4096, 2), ("prefill", 4096, 1)],
    ids=["decode", "verify", "prefill", "decode-4000", "verify-4000",
         "prefill-4000", "decode-2kv", "decode-1kv", "verify-2kv",
         "prefill-2kv", "prefill-1kv"])
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, max_len, kv_heads,
                                       pool_dtype):
    compiled = _lower(kernel, pool_dtype, one_chip, max_len // PAGE,
                      kv_heads).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _pool_sized_ops(text, N_FRAMES * PAGE * kv_heads * D)


def _pool_sized_ops(hlo: str, pool_elems: int) -> list:
    """Instructions that produce an array of at least the pool's size,
    other than the parameters, their bitcasts, the kernel itself and
    XLA's asynchronous moves of a whole buffer between memories."""
    found = []
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if m and m.group(2) not in ("parameter", "bitcast", "custom-call",
                                    "copy-start", "copy-done"):
            if math.prod(int(d) for d in m.group(1).split(",")) >= pool_elems:
                found.append(line.strip())
    return found
