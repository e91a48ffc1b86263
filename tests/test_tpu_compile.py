"""Compile the paged attention kernels for a described TPU v5e.

Interpret mode never checks Mosaic's blocking rules (the last two dims
of every block divisible by (8, 128) or equal to the array's), so a
kernel can pass every interpret-vs-XLA anchor and still be refused by
the TPU compiler.  These tests compile the three page-gather kernels of
the serving path — decode, speculative verify, chunked prefill — in
bf16 and int8-pool form at phi4-mini-3.8b's published widths (24 query
heads, 8 KV heads, head_dim 128, page 16) for a ``v5e:2x2`` topology
described, not attached, and check that a Mosaic kernel is in the
program.  Nothing runs; no chip is needed.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and under several
pytest workers only the worker that runs this file should.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_verify_attention)
from repro.kernels.flash_attention import paged_prefill_flash

# phi4-mini-3.8b widths; the serving shapes chip_smoke.py runs
H, HKV, D, PAGE = 24, 8, 128, 16
MAX_BATCH, PAGES_PER_SEQ, N_FRAMES = 16, 4096 // PAGE, 1025
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


def _pool_args(sharding, pool_dtype):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = s((N_FRAMES, PAGE, HKV, D), pool_dtype)
    scales = {}
    if pool_dtype != jnp.bfloat16:
        scales = {"k_scales": s((N_FRAMES, HKV), jnp.float32),
                  "v_scales": s((N_FRAMES, HKV), jnp.float32)}
    return s, pool, scales


def _lower(kernel, pool_dtype, sharding):
    s, pool, scales = _pool_args(sharding, pool_dtype)
    if kernel == "decode":
        return paged_decode_attention.lower(
            s((MAX_BATCH, H, D), jnp.bfloat16), pool, pool,
            s((MAX_BATCH, PAGES_PER_SEQ), jnp.int32),
            s((MAX_BATCH,), jnp.int32), interpret=False, **scales)
    if kernel == "verify":
        return paged_verify_attention.lower(
            s((MAX_BATCH, VERIFY_ROWS, H, D), jnp.bfloat16), pool, pool,
            s((MAX_BATCH, PAGES_PER_SEQ), jnp.int32),
            s((MAX_BATCH, VERIFY_ROWS), jnp.int32), interpret=False,
            **scales)
    return paged_prefill_flash.lower(
        s((CHUNK_ROWS, H, CHUNK_TOKENS, D), jnp.bfloat16), pool, pool,
        s((CHUNK_ROWS, PAGES_PER_SEQ), jnp.int32),
        s((CHUNK_ROWS,), jnp.int32), s((CHUNK_ROWS,), jnp.int32),
        interpret=False, **scales)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "verify", "prefill"])
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, pool_dtype):
    compiled = _lower(kernel, pool_dtype, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
