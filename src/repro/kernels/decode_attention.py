"""Decode attention: one query token vs a long KV cache (dense and paged).

The decode path is the purest far-memory case in the paper's sense: the
KV cache is huge (up to 500k tokens), cold, and read-once per step —
exactly the access profile the AMU targets.

Dense layout (``decode_attention``): q (B, H, D); k/v (B, Skv, Hkv, D);
valid_len masks the tail.  Pages of ``bkv`` positions stream through
VMEM (compiler-pipelined); online softmax state is carried in scratch
across the sequential page grid dimension, so the kernel is O(1) in VMEM
regardless of context length.  GQA computes all G = H/Hkv query heads of
one KV head together: a (G, bkv) score tile per page — G is a free MXU
dim, so grouped queries ride along for free (the variable-granularity
argument: one aload of a KV page serves G consumers).

Paged layout (``paged_decode_attention``, ``paged_verify_attention``):
k/v live in the ``repro.paging`` pool, (N, page, Hkv, D) frames, and a
page table maps each sequence's pages to frames.  The pool stays in HBM;
one grid step serves one sequence and loops over its live blocks only,
a block being P frames that the kernel gathers itself — one DMA per
frame through the scalar-prefetched page table into a double-buffered
VMEM block, the next block's copies in flight while this one is folded
into the online softmax (the AMU's asynchronous gather at block
granularity).  P follows from the shapes (:func:`block_pages`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "paged_decode_attention",
           "paged_verify_attention", "default_interpret",
           "resolve_interpret"]

NEG_INF = -1e30
_LANE = 128


def default_interpret() -> bool:
    """Backend auto-detection for the ``interpret`` flag.

    Mosaic can only compile Pallas kernels for TPU; every other backend
    (CPU containers, the tier-1 suite) must run the kernel body in
    interpreter mode.  Defaulting to a *hard-coded* ``True`` silently
    forced interpreter mode on TPU too — interpret only when no
    compiled-kernel backend is available.
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel entry point's ``interpret`` argument: ``None`` means
    backend auto-detection (:func:`default_interpret`)."""
    return default_interpret() if interpret is None else interpret


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
                   scale: float, bkv: int, valid_len: int, G: int):
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    first_kv = j * bkv

    @pl.when(first_kv < valid_len)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bkv)
        kv_pos = first_kv + jax.lax.broadcasted_iota(jnp.int32, (G, bkv), 1)
        s = jnp.where(kv_pos < valid_len, s, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, :1] = l_s[:, :1] * corr + p.sum(-1, keepdims=True)
        m_s[:, :1] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                # (bkv, D)
        acc[...] = acc[...] * corr + jax.lax.dot(p, v)

    @pl.when(j == n_kv - 1)
    def _():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_s[:, :1], 1e-30)) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("valid_len", "bkv", "interpret"))
def decode_attention(
    q: jnp.ndarray,            # (B, H, D)
    k: jnp.ndarray,            # (B, Skv, Hkv, D)
    v: jnp.ndarray,
    *,
    valid_len: Optional[int] = None,
    bkv: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    B, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = H // Hkv
    valid_len = Skv if valid_len is None else valid_len
    bkv = min(bkv, Skv)
    assert Skv % bkv == 0

    qg = q.reshape(B, Hkv, G, D)
    kT = k.transpose(0, 2, 1, 3)     # (B, Hkv, Skv, D)
    vT = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_kernel, scale=1.0 / math.sqrt(D),
                               bkv=bkv, valid_len=valid_len, G=G)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, Skv // bkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, _LANE), jnp.float32),
            pltpu.VMEM((G, _LANE), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kT, vT)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# paged variants: gather-by-page-table (repro.paging pool layout)
# ---------------------------------------------------------------------------
#
# Blocking (decode and verify): a grid step per sequence; the pool is an
# HBM operand (``pltpu.HBM``) and the kernel copies whole frames — every
# KV head of one page, so no DMA cuts a head out of a frame — into a
# double-buffered (2, P, *frame) VMEM buffer, P per block, one semaphore
# per stream and slot.  Only the row's live frames are copied (its last
# block may be ragged), and only its live blocks are visited: the work
# follows the row's length, not pages_per_seq.  A frame is copied as
# the pool holds it (:func:`heads_major`): (page, Hkv, D), or, where
# XLA keeps it head-major, (Hkv, page, D), so no copy of the pool is
# made for the kernel.  Viewed as (P*page*Hkv, D) rows, a block's K
# meets every query row in one score tile; a head-match mask keeps each
# row to its own KV head, so no per-head strided slice is taken.  A
# quantized pool's per-(frame, head) scales travel as one (1, page*Hkv)
# row per frame, laid out like those score columns, and scale the
# scores and the probabilities.  The chunked-prefill kernel
# (``flash_attention.paged_prefill_flash``) stages blocks the same way,
# a grid step per query block.


# Token budget of one block: a block of P frames is gathered by one
# round of DMAs and folded into the softmax as one (rows, P*page*Hkv)
# score tile.  At phi4-mini's shapes on a v5e (16-token pages) the
# kernel alone took 5.52-5.60 ms a step (32 layers, 16 rows) at P = 16,
# 32 and 64 and 6.20 at P = 8; inside the serving step P = 32 and 16
# tied end to end (one traced reading: 0.630 against 0.667 s).
_BLOCK_TOKENS = 512
# VMEM the blocks' buffers and f32 working set may take (v5e's default
# scoped limit is 16 MiB; the rest is left to the compiler).
_VMEM_BUDGET = 12 << 20


def _scale_width(page: int, Hkv: int) -> int:
    """Lanes of a frame's scale row: its page*Hkv scores, padded to
    whole 128-lane vectors (Mosaic copies only whole tiles)."""
    return -(-page * Hkv // _LANE) * _LANE


def heads_major(Hkv: int, itemsize: int) -> bool:
    """Whether a pool frame (page, Hkv, D) lies in HBM head-major.

    XLA tiles a frame's (Hkv, D) rows only where Hkv is a whole row tile:
    a power of two from the dtype's packing (4 // itemsize rows) up to
    8, or a multiple of 8.  Otherwise (1-3 heads of an int8 pool, 1 of a
    bf16 one, as on a device of a sharded pool) it lays each frame out as
    (Hkv, page, D), which is also the only order Mosaic can copy a frame
    in there."""
    packing = max(1, 4 // itemsize)
    tile = min(8, max(packing, 1 << (Hkv - 1).bit_length()))
    return Hkv % tile != 0


def block_pages(page: int, pages_per_seq: int, Hkv: int, D: int,
                itemsize: int, rows: int, quant: bool) -> int:
    """Frames per block P: the most whose block stays within
    ``_BLOCK_TOKENS`` tokens and ``pages_per_seq`` frames, halved until
    the double-buffered K/V (and scale) blocks plus the f32 upcasts and
    score tiles of one block fit ``_VMEM_BUDGET``."""
    P = max(1, min(_BLOCK_TOKENS // page, pages_per_seq))

    def vmem(P):
        cols = P * page * Hkv
        staged = 2 * 2 * cols * D * itemsize           # K, V x 2 slots
        if quant:
            staged += 2 * 2 * P * 8 * _scale_width(page, Hkv) * 4
        return staged + 2 * cols * D * 4 + 3 * rows * cols * 4

    while P > 1 and vmem(P) > _VMEM_BUDGET:
        P //= 2
    return P


def _paged_blocks_kernel(pt_ref, len_ref, q_ref, *refs, scale: float,
                         page: int, P: int, Hkv: int, S: int, G: int,
                         quant: bool, head_major: bool):
    """Shared body of the decode (S = 1) and verify (S = K + 1) kernels.

    Grid ``(B,)``, one sequence per step, run in order.  The step loops
    over the row's live blocks only (``cdiv(pages, P)``, from its longest
    verify row), each block the P frames the page table names gathered
    from the HBM pool by one DMA per frame into one slot of a
    double-buffered VMEM block; block i + 1's DMAs (or, on the last
    block, the next row's first) are in flight while block i is folded.
    Frames past the row's last live page are never copied.

    q is one (Hkv*S*G, D) block per sequence: row ``r`` belongs to KV
    head ``r // (S*G)`` and verify row ``(r % (S*G)) // G``.  A block's K
    is the (P*page*Hkv, D) view of its frames (column ``c`` is token
    ``c // Hkv`` of the block, KV head ``c % Hkv``; with ``head_major``
    frames, KV head ``c // page % Hkv`` and token ``c % page`` of frame
    ``c // (page*Hkv)``), so one score tile serves every head; a column
    counts for a row only where its head matches and its token lies
    under the verify row's length.

    ``refs``: the HBM streams (K, V and, when ``quant``, their scale
    rows), the output block, one VMEM buffer per stream, the DMA
    semaphores and the SMEM slot of the block in flight across rows."""
    n_streams = 4 if quant else 2
    hbm, (o_ref,), bufs = (refs[:n_streams], refs[n_streams:n_streams + 1],
                           refs[n_streams + 1:2 * n_streams + 1])
    sems, slot_ref = refs[2 * n_streams + 1:]
    b = pl.program_id(0)
    has_next = b + 1 < pl.num_programs(0)
    R = S * G
    RR, D = q_ref.shape[1], q_ref.shape[2]
    bt = P * page                                   # tokens per block
    C = bt * Hkv                                    # score-tile columns

    def n_pages(row):
        longest = len_ref[row, 0]
        for s in range(1, S):
            longest = jnp.maximum(longest, len_ref[row, s])
        return (longest + page - 1) // page

    def transfer(row, blk, slot, n_pg, start: bool):
        """Start (or wait for) the DMAs of block ``blk`` of ``row`` into
        ``slot``: one per live frame and stream (a loop, not unrolled:
        the kernel is traced in every step program)."""
        def one(p, carry):
            frame = pt_ref[row, blk * P + p]
            for i in range(n_streams):
                cp = pltpu.make_async_copy(hbm[i].at[frame],
                                           bufs[i].at[slot, p],
                                           sems.at[i, slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pg - blk * P, 0, P), one, 0)

    @pl.when(b == 0)
    def _():
        # frames never copied keep what the buffer held: make that
        # finite, since a masked column still meets V in the p @ v dot
        for buf in bufs[1:]:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        transfer(0, 0, 0, n_pages(0), True)

    slot0 = slot_ref[0]
    n_pg = n_pages(b)
    n_blk = (n_pg + P - 1) // P

    @pl.when((n_blk == 0) & has_next)
    def _():
        transfer(b + 1, 0, slot0, n_pages(b + 1), True)

    # per row: the verify row's length; per column: its token in the
    # block where its head is the row's, else past any length (lim - blk *
    # bt reaches the whole row's length on block 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (RR, 1), 0)
    lim = jnp.full((RR, 1), len_ref[b, 0], jnp.int32)
    for s in range(1, S):
        lim = jnp.where((row % R) // G == s, len_ref[b, s], lim)
    col = jax.lax.broadcasted_iota(jnp.int32, (RR, C), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (RR, C), 0) // R
    if head_major:
        col_head = col // page % Hkv
        col_token = col // (page * Hkv) * page + col % page
    else:
        col_head, col_token = col % Hkv, col // Hkv
    key = jnp.where(col_head == head, col_token, jnp.iinfo(jnp.int32).max)
    q = q_ref[0].astype(jnp.float32) * scale                 # (RR, D)

    def frames(i, slot):
        """Block ``slot``'s stream ``i`` as f32 (C, D) rows, or, for a
        scale stream, as one (1, C) row of per-column scales."""
        if i < 2:
            return bufs[i][slot].astype(jnp.float32).reshape(C, D)
        return jnp.concatenate([bufs[i][slot, p][:, :page * Hkv]
                                for p in range(P)], axis=-1)

    def fold(blk, carry):
        slot, m_prev, l_prev, acc = carry
        nxt = 1 - slot

        @pl.when(blk + 1 < n_blk)
        def _():
            transfer(b, blk + 1, nxt, n_pg, True)

        @pl.when((blk + 1 == n_blk) & has_next)
        def _():
            transfer(b + 1, 0, nxt, n_pages(b + 1), True)

        transfer(b, blk, slot, n_pg, False)
        s = jax.lax.dot_general(q, frames(0, slot),
                                (((1,), (1,)), ((), ())))    # (RR, C)
        if quant:
            s = s * frames(2, slot)
        s = jnp.where(key < lim - blk * bt, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1, keepdims=True)
        if quant:
            p = p * frames(3, slot)
        acc = acc * corr + jax.lax.dot(p, frames(1, slot))
        return nxt, m_new, l_new, acc

    carry = (slot0, jnp.full((RR, 1), NEG_INF, jnp.float32),
             jnp.zeros((RR, 1), jnp.float32), jnp.zeros((RR, D), jnp.float32))
    slot, _, l, acc = jax.lax.fori_loop(0, n_blk, fold, carry)
    slot_ref[0] = slot
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_multi_query(qg, k_pages, v_pages, page_table, lengths, k_scales,
                       v_scales, *, interpret: bool):
    """pallas_call over ``qg`` (B, Hkv, S*G, D) with ``lengths`` (B, S)."""
    B, Hkv, R, D = qg.shape
    S = lengths.shape[1]
    quant = k_scales is not None
    N, page = k_pages.shape[:2]
    head_major = heads_major(Hkv, k_pages.dtype.itemsize)
    if head_major:
        # the pool's own layout there: the swap costs no copy
        k_pages, v_pages = (jnp.swapaxes(a, 1, 2) for a in (k_pages, v_pages))
    P = block_pages(page, page_table.shape[1], Hkv, D,
                    k_pages.dtype.itemsize, Hkv * R, quant)
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    operands = [k_pages, v_pages]
    scratch = [pltpu.VMEM((2, P) + k_pages.shape[1:], k_pages.dtype),
               pltpu.VMEM((2, P) + v_pages.shape[1:], v_pages.dtype)]
    if quant:
        # each frame's per-head scales laid out as its score columns,
        # one (1, width) row per frame
        spread = ((lambda s: jnp.repeat(s, page, axis=1)) if head_major
                  else (lambda s: jnp.tile(s, (1, page))))
        width = _scale_width(page, Hkv)
        operands += [jnp.pad(spread(s), ((0, 0), (0, width - page * Hkv)))
                     .reshape(N, 1, width) for s in (k_scales, v_scales)]
        scratch += [pltpu.VMEM((2, P, 1, width), jnp.float32)] * 2
    n_streams = len(operands)
    scratch += [pltpu.SemaphoreType.DMA((n_streams, 2)),
                pltpu.SMEM((1,), jnp.int32)]
    kernel = functools.partial(_paged_blocks_kernel,
                               scale=1.0 / math.sqrt(D), page=page, P=P,
                               Hkv=Hkv, S=S, G=R // S, quant=quant,
                               head_major=head_major)
    q_spec = pl.BlockSpec((1, Hkv * R, D), lambda b, pt, ln: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[q_spec] + [hbm_spec] * n_streams,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv * R, D), qg.dtype),
        # rows run in order: each prefetches the next row's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg.reshape(B, Hkv * R, D), *operands)
    return out.reshape(B, Hkv, R, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jnp.ndarray,            # (B, H, D)
    k_pages: jnp.ndarray,      # (N, page, Hkv, D) — the device page pool
    v_pages: jnp.ndarray,      # (N, page, Hkv, D)
    page_table: jnp.ndarray,   # (B, pages_per_seq) int32 physical frame ids
    lengths: jnp.ndarray,      # (B,) int32 valid KV length per sequence
    *,
    k_scales: Optional[jnp.ndarray] = None,   # (N, Hkv) f32 — quantized pool
    v_scales: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Decode attention reading the paged KV layout directly.

    Instead of a dense per-slot (B, Skv, Hkv, D) cache, k/v live in the
    ``repro.paging`` pool layout — a flat array of page frames — and
    ``page_table`` holds each sequence's logical→physical frame map.
    The page table is a *scalar-prefetch* operand: the kernel reads a
    row's frame ids from it and issues one DMA per live frame, P frames
    a block, double-buffered so the next block's gather overlaps this
    block's math (the AMU gather pattern at block granularity).  Entries
    past a sequence's last live page are never read.  A row of length 0
    returns zeros.

    Per-sequence ``lengths`` (unlike the dense kernel's static
    ``valid_len``) make one call serve the engine's mixed-depth batch.
    """
    interpret = resolve_interpret(interpret)
    B, H, D = q.shape
    Hkv = k_pages.shape[2]
    qg = q.reshape(B, Hkv, H // Hkv, D)
    out = _paged_multi_query(qg, k_pages, v_pages, page_table,
                             lengths.reshape(B, 1), k_scales, v_scales,
                             interpret=interpret)
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention(
    q: jnp.ndarray,            # (B, S, H, D) — S = K + 1 verify rows
    k_pages: jnp.ndarray,      # (N, page, Hkv, D) — the device page pool
    v_pages: jnp.ndarray,      # (N, page, Hkv, D)
    page_table: jnp.ndarray,   # (B, pages_per_seq) int32 physical frame ids
    lengths: jnp.ndarray,      # (B, S) int32 per-row valid KV length
    *,
    k_scales: Optional[jnp.ndarray] = None,   # (N, Hkv) f32 — quantized pool
    v_scales: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Multi-query paged decode attention for speculative verify-K.

    :func:`paged_decode_attention` with ``S`` query rows per sequence
    sharing one pass over the sequence's pages: the score tile grows
    from Hkv*G to Hkv*S*G rows — like GQA's G, the extra verify rows
    are a free MXU dim, so one aload of a KV block serves S*G consumers
    per head instead of G.  This is the kernel-level payoff of
    self-speculative decode: the page-fetch traffic of ONE decode step
    verifies K+1 tokens (the paper's amortise-per-access-overhead
    lever).

    ``lengths[b, s]`` masks row ``s`` independently (row s's causal view
    includes the draft rows before it).  A sequence whose rows all have
    length 0 returns zeros here; the XLA reference path returns the
    uniform value average instead — callers only consume rows with
    ``lengths >= 1``, where the two agree.
    """
    interpret = resolve_interpret(interpret)
    B, S, H, D = q.shape
    Hkv = k_pages.shape[2]
    G = H // Hkv
    # (B, S, Hkv, G, D) -> (B, Hkv, S*G, D): rows of one KV head stay
    # contiguous, as the kernel's head-match mask expects
    qg = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
         .reshape(B, Hkv, S * G, D)
    out = _paged_multi_query(qg, k_pages, v_pages, page_table, lengths,
                             k_scales, v_scales, interpret=interpret)
    return out.reshape(B, Hkv, S, G, D).transpose(0, 2, 1, 3, 4) \
              .reshape(B, S, H, D)
