"""Decode attention: one query token vs a long KV cache (paged layout).

The decode path is the purest far-memory case in the paper's sense: the
KV cache is huge (up to 500k tokens), cold, and read-once per step —
exactly the access profile the AMU targets.  The cache stays in HBM and
pages of ``bkv`` positions stream through VMEM (compiler-pipelined);
online softmax state is carried in scratch across the sequential page
grid dimension, so the kernel is O(1) in VMEM regardless of context
length.

Layout: q (B, H, D); k/v (B, Skv, Hkv, D); valid_len masks the tail.
GQA is handled by computing all G = H/Hkv query heads of one KV head
together: q is pre-reshaped to (B, Hkv, G, D) and a (G, bkv) score tile
is produced per page — G is a free MXU dim, so grouped queries ride
along for free (the variable-granularity argument: one aload of a KV
page serves G consumers).
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
# Blocking: Mosaic needs the last two dims of every block divisible by
# (8, 128) or equal to the array's own.  The pool is (N, page, Hkv, D),
# so each grid step stages one WHOLE frame (1, page, Hkv, D) — every KV
# head of one page — and the kernel loops over the heads inside.  A
# per-head (1, page, 1, D) block would cut one head out of the
# second-to-last axis, which the TPU compiler refuses.  A quantized
# pool's (N, Hkv) f32 scales are read through their (N, 1, Hkv) view,
# one frame's (1, 1, Hkv) row per step: again the array's own last two
# dims.


def frame_slot(j, valid_len, page: int):
    """Page slot grid step ``j`` stages: steps past the sequence's last
    live page re-point at that page, so the pipeline sees an unchanged
    block index and issues no DMA for them (their compute is skipped by
    the length mask anyway)."""
    return jnp.minimum(j, jnp.maximum(valid_len - 1, 0) // page)


def scale_rows(scales: jnp.ndarray) -> jnp.ndarray:
    """(N, Hkv) frame scales -> the (N, 1, Hkv) view the kernels block."""
    return scales.reshape(scales.shape[0], 1, scales.shape[1])


def head_kv(k_ref, v_ref, ks_ref, vs_ref, h: int):
    """KV head ``h`` of the staged frame as f32 (page, D) — dequantized
    when the pool is quantized (the multiply fuses into the upcast)."""
    k = k_ref[0, :, h, :].astype(jnp.float32)
    v = v_ref[0, :, h, :].astype(jnp.float32)
    if ks_ref is not None:
        k = k * ks_ref[0, :, h:h + 1]
        v = v * vs_ref[0, :, h:h + 1]
    return k, v


def init_carry(acc, m_s, l_s) -> None:
    acc[...] = jnp.zeros_like(acc)
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)


def online_softmax_step(q, k, v, mask, acc, m_s, l_s, i: int) -> None:
    """Fold one page into carry row-group ``i``: ``q`` (R, D) f32
    pre-scaled, ``k``/``v`` (page, D) f32, ``mask`` (R, page); ``acc``
    (n, R, D) and ``m_s``/``l_s`` (n, R, lanes) are VMEM scratch."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (R, page)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_s[i, :, :1]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_s[i, :, :1] = l_s[i, :, :1] * corr + p.sum(-1, keepdims=True)
    m_s[i, :, :1] = m_new
    acc[i] = acc[i] * corr + jax.lax.dot(p, v)


def finish_carry(o_ref, acc, l_s, i: int) -> None:
    o_ref[0, i] = (acc[i] / jnp.maximum(l_s[i, :, :1], 1e-30)) \
        .astype(o_ref.dtype)


def _paged_multi_query_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                              scale: float, page: int, Hkv: int, G: int,
                              S: int, quant: bool):
    """Shared body of the decode (S = 1) and verify (S = K + 1) kernels.

    Grid ``(B, pages_per_seq)``: one sequence's frames stream through
    VMEM; q is one (Hkv, S*G, D) block per sequence, the rows of KV head
    ``h`` being its S verify rows × G grouped query heads."""
    if quant:
        ks_ref, vs_ref, o_ref, acc, m_s, l_s = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc, m_s, l_s = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_pg = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        init_carry(acc, m_s, l_s)

    # per-row valid lengths: verify row s sees s more KV positions than
    # row 0 (its own token plus every draft before it).  S is small and
    # static, so the SMEM reads unroll at trace time.
    vals = [len_ref[b, s] for s in range(S)]
    valid_max = vals[0]
    for vl in vals[1:]:
        valid_max = jnp.maximum(valid_max, vl)
    first_kv = j * page

    @pl.when(first_kv < valid_max)
    def _():
        kv_pos = first_kv + jax.lax.broadcasted_iota(
            jnp.int32, (S * G, page), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (S * G, page), 0) // G
        valid_rows = jnp.full((S * G, page), vals[0], jnp.int32)
        for s in range(1, S):
            valid_rows = jnp.where(row == s, vals[s], valid_rows)
        mask = kv_pos < valid_rows
        for h in range(Hkv):
            q = q_ref[0, h].astype(jnp.float32) * scale        # (S*G, D)
            k, v = head_kv(k_ref, v_ref, ks_ref, vs_ref, h)
            online_softmax_step(q, k, v, mask, acc, m_s, l_s, h)

    @pl.when(j == n_pg - 1)
    def _():
        for h in range(Hkv):
            finish_carry(o_ref, acc, l_s, h)


def _paged_multi_query(qg, k_pages, v_pages, page_table, lengths, k_scales,
                       v_scales, *, interpret: bool):
    """pallas_call over ``qg`` (B, Hkv, S*G, D) with ``lengths`` (B, S)."""
    B, Hkv, R, D = qg.shape
    N, page, _, _ = k_pages.shape
    S = lengths.shape[1]
    G = R // S
    pages_per_seq = page_table.shape[1]
    quant = k_scales is not None

    def frame(b, j, pt, ln):
        longest = ln[b, 0]
        for s in range(1, S):
            longest = jnp.maximum(longest, ln[b, s])
        return pt[b, frame_slot(j, longest, page)]

    in_specs = [
        pl.BlockSpec((1, Hkv, R, D), lambda b, j, pt, ln: (b, 0, 0, 0)),
        pl.BlockSpec((1, page, Hkv, D),
                     lambda b, j, pt, ln: (frame(b, j, pt, ln), 0, 0, 0)),
        pl.BlockSpec((1, page, Hkv, D),
                     lambda b, j, pt, ln: (frame(b, j, pt, ln), 0, 0, 0)),
    ]
    operands = [qg, k_pages, v_pages]
    if quant:
        # one f32 scale per (frame, head): the same page-table
        # dereference the frame DMA uses picks the scale row
        spec = pl.BlockSpec((1, 1, Hkv),
                            lambda b, j, pt, ln: (frame(b, j, pt, ln), 0, 0))
        in_specs += [spec, spec]
        operands += [scale_rows(k_scales), scale_rows(v_scales)]
    kernel = functools.partial(_paged_multi_query_kernel,
                               scale=1.0 / math.sqrt(D), page=page, Hkv=Hkv,
                               G=G, S=S, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, R, D),
                               lambda b, j, pt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, R, D), jnp.float32),
            pltpu.VMEM((Hkv, R, _LANE), jnp.float32),
            pltpu.VMEM((Hkv, R, _LANE), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), qg.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), *operands)


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
    The page-table row is a *scalar-prefetch* operand: the k/v index
    maps dereference it to pick which frame each grid step streams
    through VMEM, so the gather rides the compiler-pipelined DMA for
    free (the AMU gather pattern at page granularity — same scheme as
    ``moe_gather.gather_blocks``).  Entries past a sequence's last page
    must still hold in-bounds frame ids (0 is fine); those steps re-use
    the last live frame and are skipped by the per-sequence mask.

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
    from (G, page) to (S*G, page) — like GQA's G, the extra verify rows
    are a free MXU dim, so one aload of a KV page serves S*G consumers
    instead of G.  This is the kernel-level payoff of self-speculative
    decode: the page-fetch traffic of ONE decode step verifies K+1
    tokens (the paper's amortise-per-access-overhead lever).

    ``lengths[b, s]`` masks row ``s`` independently (row s's causal view
    includes the draft rows before it).  A fully-masked row (``lengths[b, s] == 0``) returns zeros here; the
    XLA reference path returns the uniform value average instead —
    callers only consume rows with ``lengths >= 1``, where the two agree.
    """
    interpret = resolve_interpret(interpret)
    B, S, H, D = q.shape
    Hkv = k_pages.shape[2]
    G = H // Hkv
    # (B, S, Hkv, G, D) -> (B, Hkv, S*G, D): rows of one KV head stay
    # contiguous so the kernel's (S*G, page) tile covers all verify rows
    qg = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
         .reshape(B, Hkv, S * G, D)
    out = _paged_multi_query(qg, k_pages, v_pages, page_table, lengths,
                             k_scales, v_scales, interpret=interpret)
    return out.reshape(B, Hkv, S, G, D).transpose(0, 2, 1, 3, 4) \
              .reshape(B, S, H, D)
