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
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (finish_carry, frame_slot,
                                            head_kv, init_carry,
                                            online_softmax_step,
                                            resolve_interpret, scale_rows)

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


def _paged_prefill_kernel(pt_ref, off_ref, len_ref, q_ref, k_ref, v_ref,
                          *rest, scale: float, page: int, Hkv: int, G: int,
                          window: int, bq: int, quant: bool = False):
    # one grid step = one staged frame (every KV head of one page) against
    # one query block of every head; the KV-head loop runs inside, each
    # KV head serving its G grouped query heads
    if quant:
        ks_ref, vs_ref, o_ref, acc, m_s, l_s = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc, m_s, l_s = rest
    b, iq, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_pg = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        init_carry(acc, m_s, l_s)

    offset = off_ref[b]
    kv_valid = offset + len_ref[b]
    first_q = offset + iq * bq
    last_q = first_q + bq - 1
    first_kv = j * page

    # block liveness: this frame holds no position the row's queries may
    # attend (outside the causal wedge / SWA window, or past the row's
    # written extent) -> skip the whole tile
    live = (first_kv < kv_valid) & (first_kv <= last_q)
    if window:
        live &= (first_kv + page - 1) > first_q - window

    @pl.when(live)
    def _():
        q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, (bq, page), 0)
        kv_pos = first_kv + jax.lax.broadcasted_iota(jnp.int32, (bq, page), 1)
        mask = (q_pos >= kv_pos) & (kv_pos < kv_valid)
        if window:
            mask &= kv_pos > q_pos - window
        for h in range(Hkv):
            k, v = head_kv(k_ref, v_ref, ks_ref, vs_ref, h)
            for g in range(G):
                hq = h * G + g
                q = q_ref[0, hq].astype(jnp.float32) * scale     # (bq, D)
                online_softmax_step(q, k, v, mask, acc, m_s, l_s, hq)

    @pl.when(j == n_pg - 1)
    def _():
        for hq in range(Hkv * G):
            finish_carry(o_ref, acc, l_s, hq)


@functools.partial(jax.jit, static_argnames=("window", "bq", "interpret"))
def paged_prefill_flash(
    q: jnp.ndarray,            # (C, H, T, D) — one prompt chunk per row
    k_pages: jnp.ndarray,      # (N, page, Hkv, D) — the device page pool
    v_pages: jnp.ndarray,
    page_rows: jnp.ndarray,    # (C, pages_per_seq) int32 physical frame ids
    offset: jnp.ndarray,       # (C,) int32 absolute position of q[:, :, 0]
    lengths: jnp.ndarray,      # (C,) int32 valid tokens in each chunk row
    *,
    window: int = 0,
    bq: int = 128,
    k_scales: Optional[jnp.ndarray] = None,   # (N, Hkv) f32 — quantized pool
    v_scales: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Chunked-prefill flash attention reading KV through the page table.

    The paged-KV variant of :func:`flash_attention`: each grid row is one
    admitting sequence's prompt chunk, and the KV grid dimension streams
    that sequence's pool *frames* through VMEM — the page-table row is a
    scalar-prefetch operand whose dereference picks the frame each step
    DMAs, exactly like ``decode_attention.paged_decode_attention`` but
    with a (bq, page) score tile per query head instead of one query
    token.  Per-row ``offset``/``lengths`` (also scalar-prefetched)
    shift the causal wedge to each row's absolute position, so one call
    serves chunk rows of different sequences at different prefill
    depths.  Frames past the last one a query block may attend re-use
    that frame's block index (no DMA) and are skipped by block liveness.
    """
    interpret = resolve_interpret(interpret)
    C, H, T, D = q.shape
    N, page, Hkv, _ = k_pages.shape
    pages_per_seq = page_rows.shape[1]
    bq = min(bq, T)
    pad_t = (-T) % bq
    if pad_t:
        # pad the chunk axis up to a block multiple; padded queries
        # produce don't-care rows that are sliced off below (callers
        # only read positions below each row's valid length anyway)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        T = T + pad_t

    def frame(c, i, j, pt, off, ln):
        # the last position query block i may attend: its own last query,
        # or the row's written extent if that ends first
        reach = jnp.minimum(off[c] + ln[c], off[c] + (i + 1) * bq)
        return pt[c, frame_slot(j, reach, page)]

    quant = k_scales is not None
    kernel = functools.partial(_paged_prefill_kernel,
                               scale=1.0 / math.sqrt(D), page=page, Hkv=Hkv,
                               G=H // Hkv, window=window, bq=bq, quant=quant)
    in_specs = [
        pl.BlockSpec((1, H, bq, D),
                     lambda c, i, j, pt, off, ln: (c, 0, i, 0)),
        pl.BlockSpec((1, page, Hkv, D),
                     lambda c, i, j, pt, off, ln:
                     (frame(c, i, j, pt, off, ln), 0, 0, 0)),
        pl.BlockSpec((1, page, Hkv, D),
                     lambda c, i, j, pt, off, ln:
                     (frame(c, i, j, pt, off, ln), 0, 0, 0)),
    ]
    operands = [q, k_pages, v_pages]
    if quant:
        spec = pl.BlockSpec((1, 1, Hkv),
                            lambda c, i, j, pt, off, ln:
                            (frame(c, i, j, pt, off, ln), 0, 0))
        in_specs += [spec, spec]
        operands += [scale_rows(k_scales), scale_rows(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(C, T // bq, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, bq, D),
                               lambda c, i, j, pt, off, ln: (c, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, bq, D), jnp.float32),
            pltpu.VMEM((H, bq, _LANE), jnp.float32),
            pltpu.VMEM((H, bq, _LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(page_rows.astype(jnp.int32), offset.astype(jnp.int32),
      lengths.astype(jnp.int32), *operands)
    return out[:, :, :T - pad_t] if pad_t else out
