"""AMU matmul — the paper's programming model inside one Pallas kernel.

This is the flagship kernel: it does NOT use BlockSpec index-map
pipelining for its inputs.  Instead the operands live in HBM
(``memory_space=ANY``) and the kernel body itself plays the role of the
paper's software:

  * ``aload``  = ``pltpu.make_async_copy(hbm_slice, vmem_buf, sem).start()``
  * SPM        = double-buffered VMEM scratch (two slots per operand —
    the reconfigurable cache/SPM split from ``core/spm.py`` decides the
    tile shape),
  * ``getfin`` = ``copy.wait()`` on the slot's DMA semaphore,
  * event loop = issue tile ``k+1`` while the MXU consumes tile ``k``.

On real TPU hardware the DMA engines run concurrently with the MXU, so
the wait on slot ``(k+1) % 2`` returns long after the matmul on slot
``k % 2`` has been issued — compute/copy overlap, which is exactly the
paper's Fig-1 argument (keep many outstanding requests in flight so
far-memory latency never idles the core).  In ``interpret=True`` mode the
semantics (not the timing) are validated.

Grid: ``(M/bm, N/bn)``; the K loop is a ``fori_loop`` inside the kernel so
that the manual double-buffering is explicit rather than compiler-owned.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spm import plan_matmul_blocks
from repro.kernels.decode_attention import resolve_interpret

__all__ = ["amu_matmul"]


def _amu_matmul_kernel(x_hbm, w_hbm, o_ref, xb, wb, acc, sem_x, sem_w,
                       *, bm: int, bk: int, bn: int, n_k: int):
    """x_hbm: (M,K) in ANY; w_hbm: (K,N) in ANY; o_ref: (bm,bn) VMEM block.

    xb/wb: (2, bm, bk) / (2, bk, bn) VMEM double buffers.
    sem_x/sem_w: DMA semaphore arrays, one per slot.
    """
    i, j = pl.program_id(0), pl.program_id(1)

    def issue(k, slot):
        cx = pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * bm, bm), pl.ds(k * bk, bk)],
            xb.at[slot], sem_x.at[slot])
        cw = pltpu.make_async_copy(
            w_hbm.at[pl.ds(k * bk, bk), pl.ds(j * bn, bn)],
            wb.at[slot], sem_w.at[slot])
        cx.start()
        cw.start()

    def wait(k, slot):
        pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * bm, bm), pl.ds(k * bk, bk)],
            xb.at[slot], sem_x.at[slot]).wait()
        pltpu.make_async_copy(
            w_hbm.at[pl.ds(k * bk, bk), pl.ds(j * bn, bn)],
            wb.at[slot], sem_w.at[slot]).wait()

    # aload tile 0 (and 1, if any) — fill the pipeline
    issue(0, 0)

    @pl.when(n_k > 1)
    def _():
        issue(1, 1)

    acc[...] = jnp.zeros_like(acc)

    def body(k, _):
        slot = jax.lax.rem(k, 2)
        # getfin: wait for tile k's DMA to land in SPM slot
        wait(k, slot)
        acc[...] += jnp.dot(xb[slot], wb[slot],
                            preferred_element_type=jnp.float32)
        # slot is consumed — keep the pipeline full: aload tile k+2 into it
        @pl.when(k + 2 < n_k)
        def _():
            issue(k + 2, slot)
        return ()

    jax.lax.fori_loop(0, n_k, body, (), unroll=False)
    o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def amu_matmul(
    x: jnp.ndarray,              # (M, K)
    w: jnp.ndarray,              # (K, N)
    *,
    bm: Optional[int] = None,
    bk: Optional[int] = None,
    bn: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    if bm is None or bk is None or bn is None:
        plan = plan_matmul_blocks(M, K, N, dtype_bytes=x.dtype.itemsize)
        bm = bm or min(plan.block_shapes["x"][0], M)
        bk = bk or min(plan.block_shapes["x"][1], K)
        bn = bn or min(plan.block_shapes["w"][1], N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, \
        f"dims ({M},{K},{N}) must tile by ({bm},{bk},{bn})"
    n_k = K // bk

    kernel = functools.partial(_amu_matmul_kernel, bm=bm, bk=bk, bn=bn,
                               n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),    # x stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),    # w stays in HBM
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bm, bk), x.dtype),        # SPM slots for x
            pltpu.VMEM((2, bk, bn), w.dtype),        # SPM slots for w
            pltpu.VMEM((bm, bn), jnp.float32),       # accumulator
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=resolve_interpret(interpret),
    )(x, w)
