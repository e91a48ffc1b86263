"""Streaming flash attention (causal / sliding-window / GQA) in Pallas.

K/V stream through VMEM block-by-block — the AMU *stream* pattern, here
with compiler-managed pipelining (BlockSpec index maps double-buffer the
DMA automatically; contrast with the manual version in
``amu_matmul.py``).  Online softmax state (m, l, acc) lives in VMEM
scratch and is carried across the sequential KV grid dimension.

Layout: q (B, H, Sq, D); k/v (B, Hkv, Skv, D); out like q.
Block-sparsity: fully-masked KV blocks (outside the causal wedge or the
SWA window) are skipped with ``pl.when`` — the skipped blocks never even
issue their DMA on TPU (the index map still points at them, but Mosaic
elides dead loads within revisited blocks; the FLOP savings are what
matters for the roofline).

Paged layout (``paged_prefill_flash``, chunked prefill): q is one prompt
chunk per row at its own absolute offset, and K/V live in the
``repro.paging`` pool.  The pool stays in HBM; a grid step serves one
query block and loops over the KV blocks its queries can reach only,
each block P frames the kernel gathers itself by DMA through the
scalar-prefetched page table, double-buffered, as the paged decode
kernel does (``decode_attention``).  Each block meets the grouped query
heads of one KV head as one score tile, and only blocks on the causal
diagonal, the row's written extent or the window's edge are masked.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (_BLOCK_TOKENS, _scale_width,
                                            heads_major, resolve_interpret)

__all__ = ["flash_attention", "paged_prefill_flash"]

NEG_INF = -1e30
_LANE = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
                  scale: float, causal: bool, window: int, bq: int, bkv: int,
                  kv_valid: int, q_offset: int):
    iq, ikv = pl.program_id(2), pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ikv == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_pos = ikv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)

    # block-level liveness: skip blocks fully outside the mask
    first_q = q_offset + iq * bq
    last_q = first_q + bq - 1
    first_kv = ikv * bkv
    live = first_kv < kv_valid
    if causal:
        live &= first_kv <= last_q
    if window:
        live &= (first_kv + bkv - 1) > first_q - window

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)
        mask = kv_pos < kv_valid
        if causal:
            mask &= q_pos >= kv_pos
        if window:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[:, :1]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                      # (bq, 1)
        l_s[:, :1] = l_s[:, :1] * corr + p.sum(-1, keepdims=True)
        m_s[:, :1] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                 # (bkv, D)
        acc[...] = acc[...] * corr + jax.lax.dot(p, v)

    @pl.when(ikv == n_kv - 1)
    def _():
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "q_offset", "kv_valid", "interpret"))
def flash_attention(
    q: jnp.ndarray,            # (B, H, Sq, D)
    k: jnp.ndarray,            # (B, Hkv, Skv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_valid: Optional[int] = None,
    bq: int = 128,
    bkv: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    B, H, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    g = H // Hkv
    scale = 1.0 / math.sqrt(D)
    kv_valid = Skv if kv_valid is None else kv_valid

    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0, (Sq, bq, Skv, bkv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bkv=bkv, kv_valid=kv_valid, q_offset=q_offset)
    grid = (B, H, Sq // bq, Skv // bkv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# paged prefill: flash-attend prompt chunks against page-table-gathered KV
# ---------------------------------------------------------------------------
#
# Blocking: a grid step per query block of ``bq`` tokens of one chunk row,
# run in order.  The pool is an HBM operand, as in the paged decode kernel
# (``decode_attention._paged_blocks_kernel``): the step loops over the KV
# blocks its queries can reach only, each block P whole frames copied by
# one DMA per frame (and stream) through the scalar-prefetched page table
# into one slot of a double-buffered VMEM block, the next block's copies
# (or the next step's first) in flight while this one is folded.  A block
# is folded one KV head at a time as one (G*bq, P*page) score tile, the G
# grouped query heads of that KV head stacked as rows; the softmax carry
# of every head lives in VMEM scratch across the blocks.

# Query tokens of one block, and the VMEM the blocks' buffers, the carry
# and one head's working set may take (the compiler is given the budget
# and the headroom above it).
_Q_BLOCK = 128
_PREFILL_VMEM = 24 << 20
_VMEM_HEADROOM = 8 << 20


def prefill_blocks(T: int, page: int, pages_per_seq: int, Hkv: int, G: int,
                   D: int, itemsize: int, quant: bool) -> tuple:
    """(bq, P): query tokens and frames of one block.  bq is at most
    ``_Q_BLOCK`` and the chunk; P the most frames within the decode
    kernel's ``_BLOCK_TOKENS`` and the row's table.  P, then bq, is
    halved until the staged K/V (and scale) blocks, the q and output
    blocks, the carry and one head's f32 working set fit
    ``_PREFILL_VMEM``."""
    bq = min(_Q_BLOCK, T)
    P = max(1, min(_BLOCK_TOKENS // page, pages_per_seq))

    def vmem(bq, P):
        H, cols = Hkv * G, P * page
        staged = 2 * 2 * cols * Hkv * D * itemsize      # K, V x 2 slots
        if quant:
            staged += 2 * 2 * P * Hkv * _scale_width(page, 1) * 4
        io = 2 * 2 * H * bq * D * 4                     # q, out x 2 buffers
        carry = H * bq * (D + 2 * _LANE) * 4
        work = 3 * G * bq * cols * 4 + 2 * cols * D * 4
        return staged + io + carry + work

    while vmem(bq, P) > _PREFILL_VMEM and (P > 1 or bq > 8):
        if P > 1:
            P //= 2
        else:
            bq //= 2
    return bq, P


def _paged_prefill_kernel(pt_ref, off_ref, len_ref, q_ref, *refs,
                          scale: float, page: int, P: int, Hkv: int, G: int,
                          bq: int, window: int, quant: bool,
                          head_major: bool):
    """Grid ``(C, T // bq)``: step (c, i) attends query block i of chunk
    row c (absolute positions ``offset[c] + i * bq`` on) to the row's KV
    through position ``offset[c] + lengths[c] - 1``, causally (and within
    ``window``).  It visits blocks ``b0 .. b1 - 1`` of the row's table,
    ``b1 = cdiv(reach, P * page)`` with ``reach`` the first position its
    queries cannot attend, ``b0`` the first block inside the window; a
    query block past the row's length (all of a length-0 row) visits
    none and writes zeros.  Frames past ``reach`` are never copied.

    ``refs``: the HBM streams (K, V and, when ``quant``, their scale
    rows), the output block, one VMEM buffer per stream, the carry
    (``acc``, ``m``, ``l``: one slab per KV head), the DMA semaphores
    and the SMEM slot of the block in flight across steps."""
    n_streams = 4 if quant else 2
    hbm, o_ref = refs[:n_streams], refs[n_streams]
    bufs = refs[n_streams + 1:2 * n_streams + 1]
    acc_ref, m_ref, l_ref, sems, slot_ref = refs[2 * n_streams + 1:]
    c, i = pl.program_id(0), pl.program_id(1)
    n_q = pl.num_programs(1)
    has_next = c * n_q + i + 1 < pl.num_programs(0) * n_q
    c_next = jnp.where(i + 1 < n_q, c, c + 1)
    i_next = jnp.where(i + 1 < n_q, i + 1, 0)
    D = q_ref.shape[-1]
    bt = P * page                                   # tokens per block
    dt = q_ref.dtype if quant else jnp.promote_types(q_ref.dtype,
                                                     bufs[0].dtype)

    def span(row, qb):
        """Blocks ``b0 .. b1 - 1`` step (row, qb) visits and the frames
        of the row up to its reach (none past the row's length)."""
        first_q = off_ref[row] + qb * bq
        reach = jnp.minimum(off_ref[row] + len_ref[row], first_q + bq)
        live = qb * bq < len_ref[row]
        b1 = jnp.where(live, (reach + bt - 1) // bt, 0)
        b0 = (jnp.maximum(first_q - window + 1, 0) // bt if window
              else jnp.int32(0))
        return (jnp.minimum(b0, b1), b1,
                jnp.where(live, (reach + page - 1) // page, 0))

    def transfer(row, blk, slot, n_fr, start: bool):
        """Start (or wait for) the DMAs of block ``blk`` of ``row`` into
        ``slot``: one per frame below ``n_fr`` and stream."""
        def one(p, carry):
            frame = pt_ref[row, blk * P + p]
            for s in range(n_streams):
                cp = pltpu.make_async_copy(hbm[s].at[frame],
                                           bufs[s].at[slot, p],
                                           sems.at[s, slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_fr - blk * P, 0, P), one, 0)

    def start_next(slot):
        b0, _, n_fr = span(c_next, i_next)
        transfer(c_next, b0, slot, n_fr, True)

    @pl.when((c == 0) & (i == 0))
    def _():
        # frames never copied keep what the buffer held: make that
        # finite, since a masked column still meets V in the p @ v dot
        for buf in bufs[1:]:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        b0, _, n_fr = span(0, 0)
        transfer(0, b0, 0, n_fr, True)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    slot0 = slot_ref[0]
    b0, b1, n_fr = span(c, i)

    @pl.when((b1 == b0) & has_next)
    def _():
        start_next(slot0)

    first_q = off_ref[c] + i * bq
    kv_valid = off_ref[c] + len_ref[c]
    q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, (G * bq, 1), 0) % bq
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)

    def head(s, slot, h):
        """KV head ``h`` of stream ``s``'s block in ``slot``: (P*page, D)."""
        x = bufs[s][slot, :, h] if head_major else bufs[s][slot, :, :, h]
        return x.astype(dt if s == 0 else jnp.float32).reshape(bt, D)

    def scale_rows(s, slot):
        """Stream ``s``'s per-(frame, head) scales as (Hkv, P*page)."""
        return jnp.concatenate([bufs[s][slot, p][:, :page] for p in range(P)],
                               axis=-1)

    def fold(blk, slot, masked: bool):
        if masked:
            kv = blk * bt + col
            mask = (kv <= q_pos) & (kv < kv_valid)
            if window:
                mask &= kv > q_pos - window
        if quant:
            ks, vs = scale_rows(2, slot) * scale, scale_rows(3, slot)
        for h in range(Hkv):
            q = q_ref[0, h * G:(h + 1) * G].reshape(G * bq, D).astype(dt)
            s = jax.lax.dot_general(q, head(0, slot, h),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * (ks[h:h + 1] if quant else scale)
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h, :, :1] = l_ref[h, :, :1] * corr + p.sum(-1, keepdims=True)
            m_ref[h, :, :1] = m_new
            if quant:
                p = p * vs[h:h + 1]
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(p, head(1, slot, h))

    def body(blk, slot):
        nxt = 1 - slot

        @pl.when(blk + 1 < b1)
        def _():
            transfer(c, blk + 1, nxt, n_fr, True)

        @pl.when((blk + 1 == b1) & has_next)
        def _():
            start_next(nxt)

        transfer(c, blk, slot, n_fr, False)
        # only a block that crosses the causal diagonal or the window's
        # edge takes a mask (one past the row's written extent crosses
        # the diagonal: the block's first query lies inside the extent)
        last = blk * bt + bt - 1
        edge = last > first_q
        if window:
            edge |= blk * bt <= first_q + bq - 1 - window

        @pl.when(edge)
        def _():
            fold(blk, slot, True)

        @pl.when(jnp.logical_not(edge))
        def _():
            fold(blk, slot, False)

        return nxt

    slot_ref[0] = jax.lax.fori_loop(b0, b1, body, slot0)
    for h in range(Hkv):
        out = acc_ref[h] / jnp.maximum(l_ref[h, :, :1], 1e-30)
        o_ref[0, h * G:(h + 1) * G] = out.reshape(G, bq, D).astype(o_ref.dtype)


def _paged_prefill(q, k_pages, v_pages, page_rows, offset, lengths,
                   k_scales, v_scales, *, window: int, bq: int, P: int,
                   interpret: bool):
    """pallas_call over ``q`` (C, H, T, D), T a multiple of ``bq``."""
    C, H, T, D = q.shape
    N, page, Hkv, _ = k_pages.shape
    quant = k_scales is not None
    head_major = heads_major(Hkv, k_pages.dtype.itemsize)
    if head_major:
        # the pool's own layout there: the swap costs no copy
        k_pages, v_pages = (jnp.swapaxes(a, 1, 2) for a in (k_pages, v_pages))
    operands = [k_pages, v_pages]
    scratch = [pltpu.VMEM((2, P) + k_pages.shape[1:], k_pages.dtype),
               pltpu.VMEM((2, P) + v_pages.shape[1:], v_pages.dtype)]
    if quant:
        # each frame's scales as one row per KV head, spread over the
        # frame's tokens and padded to whole lanes
        width = _scale_width(page, 1)
        operands += [jnp.pad(jnp.broadcast_to(s[:, :, None], (N, Hkv, page)),
                             ((0, 0), (0, 0), (0, width - page)))
                     for s in (k_scales, v_scales)]
        scratch += [pltpu.VMEM((2, P, Hkv, width), jnp.float32)] * 2
    n_streams = len(operands)
    G = H // Hkv
    scratch += [pltpu.VMEM((Hkv, G * bq, D), jnp.float32),
                pltpu.VMEM((Hkv, G * bq, _LANE), jnp.float32),
                pltpu.VMEM((Hkv, G * bq, _LANE), jnp.float32),
                pltpu.SemaphoreType.DMA((n_streams, 2)),
                pltpu.SMEM((1,), jnp.int32)]
    kernel = functools.partial(_paged_prefill_kernel,
                               scale=1.0 / math.sqrt(D), page=page, P=P,
                               Hkv=Hkv, G=G, bq=bq, window=window,
                               quant=quant, head_major=head_major)
    q_spec = pl.BlockSpec((1, H, bq, D),
                          lambda c, i, pt, off, ln: (c, 0, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(C, T // bq),
        in_specs=[q_spec] + [pl.BlockSpec(memory_space=pltpu.HBM)] * n_streams,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # steps run in order: each starts the next one's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM + _VMEM_HEADROOM),
        interpret=interpret,
    )(page_rows.astype(jnp.int32), offset.astype(jnp.int32),
      lengths.astype(jnp.int32), q, *operands)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_flash(
    q: jnp.ndarray,            # (C, H, T, D) — one prompt chunk per row
    k_pages: jnp.ndarray,      # (N, page, Hkv, D) — the device page pool
    v_pages: jnp.ndarray,
    page_rows: jnp.ndarray,    # (C, pages_per_seq) int32 physical frame ids
    offset: jnp.ndarray,       # (C,) int32 absolute position of q[:, :, 0]
    lengths: jnp.ndarray,      # (C,) int32 valid tokens in each chunk row
    *,
    window: int = 0,
    k_scales: Optional[jnp.ndarray] = None,   # (N, Hkv) f32 — quantized pool
    v_scales: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Chunked-prefill flash attention reading KV through the page table.

    The paged-KV variant of :func:`flash_attention`: each row is one
    admitting sequence's prompt chunk at its own absolute ``offset``
    (scalar-prefetched with ``lengths`` and the page table), so one call
    serves chunk rows of different sequences at different prefill
    depths.  A query block of ``bq`` tokens visits only the KV blocks
    its queries can reach, P frames a block gathered from the pool by
    DMA through the page table, double-buffered; each block meets the G
    query heads of one KV head as one (G*bq, P*page) score tile, and
    only blocks that cross the causal diagonal, the row's written extent
    or the window's edge are masked.  bq and P follow from the shapes
    (:func:`prefill_blocks`).  A query block that holds none of its
    row's tokens (every block of a length-0 row) copies nothing and
    returns zeros; callers read only positions below ``lengths``.
    """
    interpret = resolve_interpret(interpret)
    C, H, T, D = q.shape
    _, page, Hkv, _ = k_pages.shape
    bq, P = prefill_blocks(T, page, page_rows.shape[1], Hkv, H // Hkv, D,
                           k_pages.dtype.itemsize, k_scales is not None)
    pad_t = (-T) % bq
    if pad_t:
        # pad the chunk axis up to a block multiple; padded queries
        # produce don't-care rows that are sliced off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    out = _paged_prefill(q, k_pages, v_pages, page_rows, offset, lengths,
                         k_scales, v_scales, window=window, bq=bq, P=P,
                         interpret=interpret)
    return out[:, :, :T] if pad_t else out
