"""Attention blocks: GQA / SWA / cross attention + KV caches.

Two execution paths exist for the core softmax-attention compute:

  * :func:`repro.kernels.ops.flash_attention` — the Pallas AMU kernel
    (TPU target, interpret-validated),
  * the chunked online-softmax implementation here (`_chunked_attention`)
    — pure jnp, O(S·C) peak memory, the XLA lowering and the CPU
    execution path.  Both agree with ``kernels/ref.py``.

The chunk loop is a ``lax.scan`` over KV blocks: exactly the AMU stream
pattern (fetch KV chunk → accumulate online softmax → next), so what the
Pallas kernel does with explicit DMA the XLA path does with scan.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.kv_quant import (KVQuantConfig, quant_scatter_multi,
                                    quant_scatter_token)
from repro.models.layers import dense, dense_init, rms_norm, rms_norm_init, rope, mrope

Params = Dict[str, jnp.ndarray]

__all__ = [
    "attn_init", "attention_block", "decode_attention_block",
    "paged_decode_attention_block", "paged_prefill_block",
    "paged_verify_block", "one_token_attention", "multi_token_attention",
    "init_kv_cache", "init_paged_kv_cache",
    "chunked_attention", "NEG_INF",
]

NEG_INF = -1e30


# -- parameter init -------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    p = {
        "q": dense_init(kq, d, cfg.num_heads * hd, dtype=dtype),
        "k": dense_init(kk, d, cfg.num_kv_heads * hd, dtype=dtype),
        "v": dense_init(kv, d, cfg.num_kv_heads * hd, dtype=dtype),
        "o": dense_init(ko, cfg.num_heads * hd, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(hd, dtype)
        p["k_norm"] = rms_norm_init(hd, dtype)
    return p


# -- chunked online-softmax attention ----------------------------------------------

def chunked_attention(
    q: jnp.ndarray,            # (B, Sq, H, D)
    k: jnp.ndarray,            # (B, Skv, Hkv, D)
    v: jnp.ndarray,            # (B, Skv, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,           # SWA: attend to [i-window+1, i]
    q_offset=0,                # absolute position of q[0]: int, or (B,) array
    chunk: int = 1024,
    kv_valid_len: Optional[jnp.ndarray] = None,   # mask KV beyond this
) -> jnp.ndarray:
    """Numerically-stable blockwise attention, peak memory O(Sq·chunk).

    ``q_offset`` and ``kv_valid_len`` accept per-row ``(B,)`` arrays in
    addition to scalars — the chunked paged-prefill path mixes prompt
    chunks of different sequences (each at its own absolute offset) in
    one batch.  The scalar path traces exactly the same graph as before
    the per-row variant existed, so dense prefill stays bit-identical.

    Two execution modes, selected by :mod:`repro.dist.act_sharding`:

    * baseline (paper-faithful run): GQA-grouped einsums with f32 operand
      upcast; activation placement left to GSPMD;
    * optimized (``--opt``): operands stay in their native dtype with f32
      accumulation, and the layout is constrained explicitly — head-
      sharded when H divides the model axis (K/V repeated to H locally),
      else query-sequence-sharded — so no collective ever appears inside
      the KV-chunk loop.
    """
    from repro.dist import act_sharding as acts

    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    pol = acts.current()
    plan = acts.attn_plan(H, Hkv, Sq)
    dp = acts.dp_spec_prefix()

    if plan is not None and plan[0] == "heads":
        ax = plan[1]
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        spec = P(dp, None, ax, None)
        q = acts.constrain(q, spec)
        k = acts.constrain(k, spec)
        v = acts.constrain(v, spec)
        out = _chunked_core(q, k, v, grouped=False, causal=causal,
                            window=window, q_offset=q_offset, chunk=chunk,
                            kv_valid_len=kv_valid_len,
                            native_dtype=pol.native_dtype)
        return acts.constrain(out, spec)

    if plan is not None and plan[0] == "seq":
        ax = plan[1]
        q = acts.constrain(q, P(dp, ax, None, None))
        k = acts.constrain(k, P(dp, None, None, None))
        v = acts.constrain(v, P(dp, None, None, None))
        out = _chunked_core(q, k, v, grouped=True, causal=causal,
                            window=window, q_offset=q_offset, chunk=chunk,
                            kv_valid_len=kv_valid_len,
                            native_dtype=pol.native_dtype)
        return acts.constrain(out, P(dp, ax, None, None))

    return _chunked_core(q, k, v, grouped=True, causal=causal,
                         window=window, q_offset=q_offset, chunk=chunk,
                         kv_valid_len=kv_valid_len,
                         native_dtype=pol.native_dtype)


def _chunked_core(q, k, v, *, grouped: bool, causal, window, q_offset,
                  chunk, kv_valid_len, native_dtype: bool):
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = 1.0 / math.sqrt(D)
    orig_dtype = q.dtype
    opd = orig_dtype if native_dtype else jnp.float32   # einsum operand dtype

    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # reshape kv to (n_chunks, B, chunk, Hkv, D) for scan
    kc = k.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)

    if grouped:
        qs = q.astype(opd).reshape(B, Sq, Hkv, g, D)
        s_eq, pv_eq = "bqhgd,bkhd->bqhgk", "bqhgk,bkhd->bqhgd"
        acc_shape, red_shape = (B, Sq, Hkv, g, D), (B, Sq, Hkv, g)
    else:
        qs = q.astype(opd)
        s_eq, pv_eq = "bqhd,bkhd->bqhk", "bqhk,bkhd->bqhd"
        acc_shape, red_shape = (B, Sq, H, D), (B, Sq, H)
    # per-row offsets / valid lengths get a (B, Sq, chunk) mask; the
    # scalar path keeps its original (Sq, chunk) mask (and graph)
    per_row = (getattr(q_offset, "ndim", 0) > 0
               or getattr(kv_valid_len, "ndim", 0) > 0)
    if per_row:
        q_pos = (jnp.asarray(q_offset).reshape(-1, 1)
                 + jnp.arange(Sq))                       # (B or 1, Sq)
    else:
        q_pos = q_offset + jnp.arange(Sq)                # (Sq,)

    def body(carry, xs):
        acc, m, l = carry
        ci, kci, vci = xs
        kv_pos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum(s_eq, qs, kci.astype(opd),
                       preferred_element_type=jnp.float32) * scale
        if per_row:
            mask = jnp.ones((q_pos.shape[0], Sq, chunk), bool)
            if causal:
                mask &= q_pos[..., None] >= kv_pos[None, None, :]
            if window:
                mask &= kv_pos[None, None, :] > q_pos[..., None] - window
            if kv_valid_len is not None:
                vl = jnp.asarray(kv_valid_len).reshape(-1, 1, 1)
                mask = mask & (kv_pos[None, None, :] < vl)
            mask = mask & (kv_pos < Skv)[None, None, :]  # padding chunk tail
            bmask = (mask[:, :, None, None, :] if grouped
                     else mask[:, :, None, :])
        else:
            mask = jnp.ones((Sq, chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= kv_pos[None, :]
            if window:
                mask &= kv_pos[None, :] > q_pos[:, None] - window
            if kv_valid_len is not None:
                mask = mask & (kv_pos[None, :] < kv_valid_len)
            mask = mask & (kv_pos < Skv)[None, :]        # padding chunk tail
            bmask = (mask[None, :, None, None, :] if grouped
                     else mask[None, :, None, :])
        s = jnp.where(bmask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(pv_eq, p.astype(opd), vci.astype(opd),
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (acc, m_new, l), None

    acc0 = jnp.zeros(acc_shape, jnp.float32)
    m0 = jnp.full(red_shape, NEG_INF, jnp.float32)
    l0 = jnp.zeros(red_shape, jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, D).astype(orig_dtype)


# -- full attention block (prefill / train) -------------------------------------------

def _project_qkv(p: Params, cfg: ModelConfig, x: jnp.ndarray, compute_dtype):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = dense(p["q"], x, compute_dtype).reshape(B, S, cfg.num_heads, hd)
    k = dense(p["k"], x, compute_dtype).reshape(B, S, cfg.num_kv_heads, hd)
    v = dense(p["v"], x, compute_dtype).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _position_encode(cfg: ModelConfig, q, k, positions):
    if cfg.attention == "none":
        return q, k
    if cfg.mrope_sections:
        q = mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k


def attention_block(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                       # (B, S, d)
    positions: jnp.ndarray,               # (B, S) or (3, B, S) for mrope
    *,
    causal: bool = True,
    kv: Optional[jnp.ndarray] = None,     # cross-attention source (B, Skv, d)
    compute_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence attention; returns (out, (k, v)) so prefill can cache."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    if kv is None:
        q, k, v = _project_qkv(p, cfg, x, compute_dtype)
        q, k = _position_encode(cfg, q, k, positions)
    else:  # cross attention: k/v from encoder output, no rope on cross path
        q = dense(p["q"], x, compute_dtype).reshape(B, S, cfg.num_heads, hd)
        Skv = kv.shape[1]
        k = dense(p["k"], kv, compute_dtype).reshape(B, Skv, cfg.num_kv_heads, hd)
        v = dense(p["v"], kv, compute_dtype).reshape(B, Skv, cfg.num_kv_heads, hd)
    out = chunked_attention(
        q, k, v,
        causal=causal and kv is None,
        window=cfg.window if cfg.attention == "swa" else 0,
    )
    out = out.reshape(B, S, cfg.num_heads * hd)
    return dense(p["o"], out, compute_dtype), (k, v)


# -- decode path -----------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: Optional[int] = None,
                  dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Stacked-over-layers KV cache.  SWA archs use a ring buffer of
    ``window`` slots (decode cost independent of context length)."""
    L = n_layers if n_layers is not None else cfg.num_layers
    slots = min(max_len, cfg.window) if cfg.attention == "swa" else max_len
    shape = (L, batch, slots, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((), jnp.int32),     # absolute position of next token
        "slots": jnp.asarray(slots, jnp.int32),
    }


def decode_attention_block(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                      # (B, 1, d)
    layer_cache: Tuple[jnp.ndarray, jnp.ndarray],   # k,v (B, slots, Hkv, D)
    pos: jnp.ndarray,                    # (B,) int32: per-sequence position
    *,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """One-token attention against the cache; returns (out, new (k,v)).

    ``pos`` is per sequence so continuous batching can mix requests at
    different depths in one decode step."""
    B = x.shape[0]
    hd = cfg.head_dim
    kc, vc = layer_cache
    slots = kc.shape[1]
    q, k_new, v_new = _project_qkv(p, cfg, x, compute_dtype)
    pos = jnp.broadcast_to(pos, (B,))
    posv = pos[:, None]                              # (B, 1)
    if cfg.mrope_sections:
        pos3 = jnp.broadcast_to(posv, (3, B, 1))
        q, k_new = _position_encode(cfg, q, k_new, pos3)
    else:
        q, k_new = _position_encode(cfg, q, k_new, posv)
    slot = (pos % slots if cfg.attention == "swa"
            else jnp.minimum(pos, slots - 1))        # (B,)
    kc = kc.at[jnp.arange(B), slot].set(k_new[:, 0].astype(kc.dtype))
    vc = vc.at[jnp.arange(B), slot].set(v_new[:, 0].astype(vc.dtype))
    valid = jnp.minimum(pos + 1, slots)              # (B,)
    out = _one_token_attention(cfg, q, kc, vc, valid)
    out = out.astype(compute_dtype)
    return dense(p["o"], out, compute_dtype), (kc, vc)


def one_token_attention(q, kc, vc, valid, num_kv_heads: int):
    """One-query-token attention over a dense (B, Skv, Hkv, D) cache.

    THE XLA reference for decode attention: shared by the dense decode
    block and (through ``kernels.ops.paged_decode_attention``'s gather)
    the paged block.  It is :func:`multi_token_attention` with one query
    row, so a decode step and row ``s`` of a speculative verify step run
    the same expression chain: spelling the two out separately let
    XLA's CPU backend order their reductions differently (1-ulp f32
    differences).  ``q``: (B, H, D); ``valid``: (B,) masks KV positions
    at/past it.  Returns f32 (B, 1, H * D).
    """
    return multi_token_attention(q[:, None], kc, vc, valid[:, None],
                                 num_kv_heads)


def _one_token_attention(cfg: ModelConfig, q, kc, vc, valid):
    """cfg-typed wrapper: ``q`` is the block's (B, 1, H, D) projection."""
    B = q.shape[0]
    return one_token_attention(q.reshape(B, cfg.num_heads, cfg.head_dim),
                               kc, vc, valid, cfg.num_kv_heads)


def multi_token_attention(q, kc, vc, valid, num_kv_heads: int):
    """S-query-row attention over a dense (B, Skv, Hkv, D) cache.

    The XLA reference for speculative verify-K decode, and (with
    ``S = 1``) for one-token decode: token-exactness of speculative
    decode rests on row ``s`` here being bit-equal to what a sequential
    one-token step at position ``pos + s`` computes, which holds because
    both are this one expression chain (scale before the score einsum,
    mask, softmax, then the value einsum).  Online-softmax variants
    (``_chunked_core``) are NOT bit-compatible — they normalise after
    the value product.

    ``q``: (B, S, H, D); ``valid``: (B, S) masks KV positions at/past it
    independently per row (row ``s`` of a verify step may see ``s`` more
    tokens than row 0).  Returns f32 (B, S, H * D).
    """
    B, S, H, hd = q.shape
    slots = kc.shape[1]
    qf = (q.astype(jnp.float32) * (1.0 / math.sqrt(hd)))
    qf = qf.reshape(B, S, num_kv_heads, H // num_kv_heads, hd)
    s = jnp.einsum("bshgd,bkhd->bshgk", qf, kc.astype(jnp.float32))
    kv_idx = jnp.arange(slots)
    s = jnp.where((kv_idx[None, None, :]
                   < valid[:, :, None])[:, :, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bshgk,bkhd->bshgd", w, vc.astype(jnp.float32))
    return out.reshape(B, S, H * hd)


# -- paged decode path (repro.paging pool layout) ---------------------------------


def _unpack_layer_pages(layer_pages):
    """The paged blocks take one layer's pool slice as ``(k, v)``; with
    quantization on, each element is itself a ``(pages, scales)`` pytree
    tuple (what the layer scan hands through).  Returns
    ``(quant, kp, ks, vp, vs)`` with ``ks``/``vs`` None when not quant."""
    kp, vp = layer_pages
    if isinstance(kp, tuple):
        (kp, ks), (vp, vs) = kp, vp
        return True, kp, ks, vp, vs
    return False, kp, None, vp, None


def init_paged_kv_cache(cfg: ModelConfig, n_frames: int, page_size: int,
                        batch: int, max_len: int,
                        n_layers: Optional[int] = None,
                        dtype=jnp.bfloat16,
                        quant: Optional[KVQuantConfig] = None,
                        shardings: Optional[Dict[str, Any]] = None,
                        ) -> Dict[str, jnp.ndarray]:
    """KV cache in the ``repro.paging`` device-pool layout.

    Instead of one dense per-slot buffer, k/v live in ``n_frames``
    physical page frames of ``page_size`` token positions, stacked over
    layers — one frame holds a page's K or V *for every layer*, matching
    the engine's transfer unit.  ``page_table`` maps each decode slot's
    logical pages to frames; rows are initialised to ``n_frames - 1``,
    which callers should reserve as the trash frame (writes from empty
    slots land there, reads are masked by per-sequence lengths).

    The per-sequence token capacity must be an exact multiple of
    ``page_size`` so the gathered view of a sequence is shape-identical
    to the dense cache (bit-exactness depends on it).

    With ``quant`` enabled the frames store int8/fp8 payloads and the
    dict grows ``k_scales``/``v_scales``: per-(frame, KV-head) f32
    absmax scales, ``(L, n_frames, Hkv)``.  The keys are *absent* in
    ``none`` mode so the unquantized cache pytree — and everything
    traced over it — is byte-identical to before quantization existed.

    ``shardings`` (by array name) creates each named array directly in
    its shards: a pool sharded over a mesh is never whole on one device
    (Mistral-Nemo's 4096-page pool is 10.7 GB whole, 2.68 GB a chip).
    """
    L = n_layers if n_layers is not None else cfg.num_layers
    slots = min(max_len, cfg.window) if cfg.attention == "swa" else max_len
    if slots % page_size:
        raise ValueError(
            f"page_size {page_size} must divide the per-sequence token "
            f"capacity {slots} for the paged decode layout")
    pages_per_seq = slots // page_size
    quant = KVQuantConfig.from_name(quant)
    if quant.enabled:
        dtype = quant.dtype
    put = (shardings or {}).get
    shape = (L, n_frames, page_size, cfg.num_kv_heads, cfg.head_dim)
    out = {
        "k_pages": jnp.zeros(shape, dtype, device=put("k_pages")),
        "v_pages": jnp.zeros(shape, dtype, device=put("v_pages")),
        "page_table": jnp.full((batch, pages_per_seq), n_frames - 1,
                               jnp.int32, device=put("page_table")),
    }
    if quant.enabled:
        sshape = (L, n_frames, cfg.num_kv_heads)
        out["k_scales"] = jnp.zeros(sshape, jnp.float32,
                                    device=put("k_scales"))
        out["v_scales"] = jnp.zeros(sshape, jnp.float32,
                                    device=put("v_scales"))
    return out


def paged_decode_attention_block(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                      # (B, 1, d)
    layer_pages: Tuple[jnp.ndarray, jnp.ndarray],  # k,v (N, page, Hkv, D)
    page_table: jnp.ndarray,             # (B, pages_per_seq) int32 frame ids
    pos: jnp.ndarray,                    # (B,) int32: per-sequence position
    *,
    compute_dtype=jnp.bfloat16,
    impl: str = "auto",
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """One-token attention computing directly on the paged KV layout.

    The paged counterpart of :func:`decode_attention_block`: the new
    token's K/V is scattered straight into its page-table-mapped pool
    frame (no dense per-slot cache exists at all), and attention reads
    the pool through the page table — the Pallas scalar-prefetch gather
    kernel on TPU, a ``jnp.take`` gather under XLA.  The XLA path's
    gathered view is sliced to the exact dense-cache shape and fed
    through the same expressions as the dense block, so outputs are
    bit-exact with an uninterrupted dense decode.
    """
    from repro.kernels import ops

    B = x.shape[0]
    hd = cfg.head_dim
    quant, kp, ks, vp, vs = _unpack_layer_pages(layer_pages)
    page = kp.shape[1]
    slots = page_table.shape[1] * page           # token capacity per sequence
    q, k_new, v_new = _project_qkv(p, cfg, x, compute_dtype)
    pos = jnp.broadcast_to(pos, (B,))
    posv = pos[:, None]                              # (B, 1)
    if cfg.mrope_sections:
        pos3 = jnp.broadcast_to(posv, (3, B, 1))
        q, k_new = _position_encode(cfg, q, k_new, pos3)
    else:
        q, k_new = _position_encode(cfg, q, k_new, posv)
    slot = (pos % slots if cfg.attention == "swa"
            else jnp.minimum(pos, slots - 1))        # (B,)
    frame = page_table[jnp.arange(B), slot // page]  # (B,) physical frames
    row = slot % page
    if quant:
        qcfg = KVQuantConfig.from_dtype(kp.dtype)
        kp, ks = quant_scatter_token(kp, ks, k_new[:, 0], frame, row, qcfg)
        vp, vs = quant_scatter_token(vp, vs, v_new[:, 0], frame, row, qcfg)
    else:
        kp = kp.at[frame, row].set(k_new[:, 0].astype(kp.dtype))
        vp = vp.at[frame, row].set(v_new[:, 0].astype(vp.dtype))
    valid = jnp.minimum(pos + 1, slots)              # (B,)

    # one dispatcher for every backend: ops' XLA fallback gathers the
    # dense view and runs one_token_attention — the same expressions as
    # the dense block, so paged-vs-dense stays bit-exact
    out = ops.paged_decode_attention(
        q[:, 0], kp, vp, page_table, valid, impl=impl,
        k_scales=ks, v_scales=vs)
    out = out.reshape(B, 1, cfg.num_heads * hd).astype(compute_dtype)
    if quant:
        return dense(p["o"], out, compute_dtype), ((kp, ks), (vp, vs))
    return dense(p["o"], out, compute_dtype), (kp, vp)


def paged_verify_block(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                      # (B, S, d)
    layer_pages: Tuple[jnp.ndarray, jnp.ndarray],  # k,v (N, page, Hkv, D)
    page_table: jnp.ndarray,             # (B, pages_per_seq) int32 frame ids
    pos: jnp.ndarray,                    # (B,) int32: position of x[:, 0]
    length: jnp.ndarray,                 # (B,) valid rows in x (0 = inert)
    *,
    compute_dtype=jnp.bfloat16,
    impl: str = "auto",
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Verify-K attention for self-speculative decode on the paged pool.

    :func:`paged_decode_attention_block` generalised to ``S = K + 1``
    query rows per slot: row 0 carries the last committed token, rows
    1..K the drafted continuation.  All valid rows' K/V scatter into the
    page-table-mapped frames exactly as ``S`` sequential decode steps
    would (row ``s`` lands at position ``pos + s``); rows at/past
    ``length`` scatter to the trash frame, so a slot whose draft was
    capped (or an empty slot, ``length == 0``) never dirties real
    frames.  Attention then reads the pool with a per-row valid length
    ``min(pos + s + 1, slots)`` — row ``s`` sees its own K/V and every
    draft row before it, the causal view a sequential decode would have.

    Bit-exactness contract: for any row ``s < length`` whose prefix
    d_1..d_s matches greedy decode, the returned logits row is
    bit-equal to the logits of the s-th sequential
    :func:`paged_decode_attention_block` step — the XLA path defers to
    :func:`multi_token_attention`, the one-token reference's exact
    expressions.  Callers must ensure ``pos + length <= slots``; this
    block has no SWA ring semantics (speculation is gated off for SWA).
    """
    from repro.kernels import ops

    B, S, _ = x.shape
    hd = cfg.head_dim
    quant, kp, ks, vp, vs = _unpack_layer_pages(layer_pages)
    page = kp.shape[1]
    pages_per_seq = page_table.shape[1]
    slots = pages_per_seq * page                 # token capacity per sequence
    trash = kp.shape[0] - 1
    q, k_new, v_new = _project_qkv(p, cfg, x, compute_dtype)
    pos = jnp.broadcast_to(pos, (B,))
    abs_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # (B, S)
    if cfg.mrope_sections:
        pos3 = jnp.broadcast_to(abs_pos, (3, B, S))
        q, k_new = _position_encode(cfg, q, k_new, pos3)
    else:
        q, k_new = _position_encode(cfg, q, k_new, abs_pos)

    # scatter like paged_prefill_block: draft row s of slot b lands at
    # absolute position pos[b] + s -> (frame, row-in-page); rows past
    # `length` go to the trash frame (the rejected-tail scatter target)
    in_draft = jnp.arange(S, dtype=jnp.int32)[None, :] < length[:, None]
    ok = in_draft & (abs_pos < slots)
    page_idx = jnp.clip(abs_pos // page, 0, pages_per_seq - 1)
    frame = jnp.where(ok, jnp.take_along_axis(page_table, page_idx, axis=1),
                      trash)                     # (B, S)
    row = abs_pos % page
    if quant:
        qcfg = KVQuantConfig.from_dtype(kp.dtype)
        kp, ks = quant_scatter_multi(kp, ks, k_new, page_table, page_idx,
                                     row, ok, frame, qcfg)
        vp, vs = quant_scatter_multi(vp, vs, v_new, page_table, page_idx,
                                     row, ok, frame, qcfg)
    else:
        kp = kp.at[frame, row].set(k_new.astype(kp.dtype))
        vp = vp.at[frame, row].set(v_new.astype(vp.dtype))
    valid = jnp.minimum(abs_pos + 1, slots)      # (B, S) per-row causal view

    out = ops.paged_verify_attention(
        q, kp, vp, page_table, valid, impl=impl,
        k_scales=ks, v_scales=vs)
    out = out.reshape(B, S, cfg.num_heads * hd).astype(compute_dtype)
    if quant:
        return dense(p["o"], out, compute_dtype), ((kp, ks), (vp, vs))
    return dense(p["o"], out, compute_dtype), (kp, vp)


# -- chunked paged prefill (prompt chunks computed on the pool layout) -----------


def paged_prefill_block(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                      # (C, T, d) pre-normed chunk hidden
    layer_pages: Tuple[jnp.ndarray, jnp.ndarray],  # k,v (N, page, Hkv, D)
    page_rows: jnp.ndarray,              # (C, pages_per_seq) int32 frame ids
    offset: jnp.ndarray,                 # (C,) absolute position of x[:, 0]
    length: jnp.ndarray,                 # (C,) valid tokens in this chunk
    positions: jnp.ndarray,              # (C, T) or (3, C, T) absolute pos
    *,
    compute_dtype=jnp.bfloat16,
    impl: str = "auto",
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """One prompt-chunk attention computing directly on the paged layout.

    The prefill counterpart of :func:`paged_decode_attention_block` and
    the kernel-level heart of chunked paged prefill (the follow-up AMU
    paper's massive-MLP serving pattern, 2404.11044 §4): each row of
    ``x`` is one admitting sequence's next prompt chunk, flash-attended
    against that sequence's pool-resident KV prefix *while its own K/V
    is scattered straight into the mapped pool frames* — no dense
    per-sequence KV buffer ever exists, not even during prefill.

    Chunk rows are independent sequences at independent depths:
    ``offset`` gives each row's absolute start position (RoPE and the
    causal mask both honour it) and ``length`` its valid token count —
    tail padding beyond ``length`` writes to the trash frame
    (``n_frames - 1``, same convention as empty decode slots) and its
    outputs are discarded by the caller.  The XLA path gathers the
    page-table view and runs the same ``chunked_attention`` expressions
    as dense prefill, so a chunked prefill's tokens match an
    uninterrupted dense prefill's.
    """
    from repro.kernels import ops

    C, T, _ = x.shape
    hd = cfg.head_dim
    quant, kp, ks, vp, vs = _unpack_layer_pages(layer_pages)
    page = kp.shape[1]
    pages_per_seq = page_rows.shape[1]
    slots = pages_per_seq * page                 # token capacity per sequence
    trash = kp.shape[0] - 1
    q, k_new, v_new = _project_qkv(p, cfg, x, compute_dtype)
    q, k_new = _position_encode(cfg, q, k_new, positions)

    # scatter the chunk's K/V into its mapped pool frames: token t of row
    # c lands at absolute position offset[c] + t -> (frame, row-in-page)
    abs_pos = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    in_chunk = jnp.arange(T, dtype=jnp.int32)[None, :] < length[:, None]
    ok = in_chunk & (abs_pos < slots)
    page_idx = jnp.clip(abs_pos // page, 0, pages_per_seq - 1)
    frame = jnp.where(ok, jnp.take_along_axis(page_rows, page_idx, axis=1),
                      trash)                     # (C, T)
    row = abs_pos % page
    if quant:
        qcfg = KVQuantConfig.from_dtype(kp.dtype)
        kp, ks = quant_scatter_multi(kp, ks, k_new, page_rows, page_idx,
                                     row, ok, frame, qcfg)
        vp, vs = quant_scatter_multi(vp, vs, v_new, page_rows, page_idx,
                                     row, ok, frame, qcfg)
    else:
        kp = kp.at[frame, row].set(k_new.astype(kp.dtype))
        vp = vp.at[frame, row].set(v_new.astype(vp.dtype))

    out = ops.paged_prefill_attention(
        q, kp, vp, page_rows, offset, length,
        window=cfg.window if cfg.attention == "swa" else 0, impl=impl,
        k_scales=ks, v_scales=vs)
    out = out.reshape(C, T, cfg.num_heads * hd).astype(compute_dtype)
    if quant:
        return dense(p["o"], out, compute_dtype), ((kp, ks), (vp, vs))
    return dense(p["o"], out, compute_dtype), (kp, vp)
