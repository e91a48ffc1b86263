"""Indexed row gather — the AMU *gather pattern* at kernel level.

MoE dispatch (and paged-KV fetch) reduce to: out[i] = src[idx[i]] for a
dynamic index vector.  This kernel uses ``PrefetchScalarGridSpec`` so the
index vector is prefetched into SMEM *before* the grid runs — the Pallas
analogue of the paper's Access Pattern Register: the pattern (the
indices) is programmed into the unit first, then the unit streams the
granules.  Each grid step copies one ``rows_per_block`` granule whose
source rows are resolved from the prefetched indices via the BlockSpec
index map (for block-aligned gathers) or a manual DMA per row (general
case, ``gather_rows``).

``granularity``: rows per DMA — the paper's variable-granularity knob.
Coalescing for semi-sorted indices happens upstream in
``repro.core.patterns.GatherPattern``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import resolve_interpret

__all__ = ["gather_rows", "gather_blocks"]


def _gather_rows_kernel(idx_ref, src_hbm, o_ref, row_buf, sem, *,
                        rows_per_block: int):
    """General gather: one manual DMA per row (aload), landing in the
    output VMEM block (SPM), paced by a single semaphore (getfin)."""
    i = pl.program_id(0)

    def body(r, _):
        src_row = idx_ref[i * rows_per_block + r]
        copy = pltpu.make_async_copy(
            src_hbm.at[pl.ds(src_row, 1), :], row_buf, sem)
        copy.start()
        copy.wait()
        o_ref[pl.ds(r, 1), :] = row_buf[...]
        return ()

    jax.lax.fori_loop(0, rows_per_block, body, ())


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def gather_rows(
    src: jnp.ndarray,          # (N, d)
    idx: jnp.ndarray,          # (M,) int32
    *,
    rows_per_block: int = 8,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    N, d = src.shape
    M = idx.shape[0]
    assert M % rows_per_block == 0, (M, rows_per_block)
    kernel = functools.partial(_gather_rows_kernel,
                               rows_per_block=rows_per_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // rows_per_block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows_per_block, d), lambda i, idx_ref: (i, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), src.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), src.dtype),
        interpret=resolve_interpret(interpret),
    )(idx, src)


def _gather_blocks_kernel(idx_ref, src_ref, o_ref):
    # src block already resolved by the index map from prefetched indices
    o_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gather_blocks(
    src: jnp.ndarray,          # (N, d): N = nblocks * block_rows
    block_idx: jnp.ndarray,    # (Mb,) int32 — indices of row-blocks
    *,
    block_rows: int = 8,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Block-aligned gather: the index map itself reads the prefetched
    scalar indices, so the compiler pipelines the DMAs (large-granularity
    fast path — one aload per block)."""
    N, d = src.shape
    Mb = block_idx.shape[0]
    assert N % block_rows == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Mb,),
        in_specs=[pl.BlockSpec((block_rows, d),
                               lambda i, idx_ref: (idx_ref[i], 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        _gather_blocks_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mb * block_rows, d), src.dtype),
        interpret=resolve_interpret(interpret),
    )(block_idx, src)
