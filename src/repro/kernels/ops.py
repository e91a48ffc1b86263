"""Dispatch wrappers: one public op per kernel, backend-selected.

``impl``:
  * ``"pallas"``    — compiled Pallas kernel (TPU target),
  * ``"interpret"`` — Pallas kernel body interpreted on CPU (correctness),
  * ``"xla"``       — the pure-jnp path (oracle-equivalent, what a CPU
    backend lowers, since Mosaic cannot target it),
  * ``"auto"``      — pallas on TPU, xla elsewhere.

Model code calls these wrappers; tests sweep ``interpret`` vs ``xla``.

Mosaic kernels cannot be partitioned by GSPMD, so under a mesh whose
``model`` axis spans several devices the three paged-attention kernels
run inside ``shard_map`` over the KV-head axis — the axis the pool
frames are sharded on (``dist.steps.paged_cache_specs``): each device
attends its own KV heads and their grouped query heads, with the page
table and lengths replicated.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels.amu_matmul import amu_matmul as _amu_matmul
from repro.kernels.decode_attention import decode_attention as _decode_attn
from repro.kernels.decode_attention import default_interpret
from repro.kernels.decode_attention import \
    paged_decode_attention as _paged_decode_attn
from repro.kernels.decode_attention import \
    paged_verify_attention as _paged_verify_attn
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_attention import \
    paged_prefill_flash as _paged_prefill_flash
from repro.kernels.mamba2 import ssd as _ssd
from repro.kernels.moe_gather import gather_rows as _gather_rows
from repro.kernels.rwkv6 import wkv6 as _wkv6

__all__ = ["matmul", "flash_attention", "decode_attention",
           "paged_decode_attention", "paged_verify_attention",
           "paged_prefill_attention", "wkv6",
           "ssd", "gather_rows", "on_tpu", "resolve_impl",
           "default_interpret"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if on_tpu() else "xla"


def matmul(x, w, *, impl: str = "auto", **block_kw):
    impl = resolve_impl(impl)
    if impl == "xla":
        return _ref.matmul_ref(x, w)
    return _amu_matmul(x, w, interpret=(impl == "interpret"), **block_kw)


def flash_attention(q, k, v, *, causal=True, window=0, impl: str = "auto",
                    **kw):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D) — model layout."""
    impl = resolve_impl(impl)
    if impl == "xla":
        from repro.models.attention import chunked_attention
        return chunked_attention(q, k, v, causal=causal, window=window)
    qT, kT, vT = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    out = _flash(qT, kT, vT, causal=causal, window=window,
                 interpret=(impl == "interpret"), **kw)
    return out.transpose(0, 2, 1, 3)


def decode_attention(q, k, v, *, valid_len=None, impl: str = "auto", **kw):
    impl = resolve_impl(impl)
    if impl == "xla":
        vl = q.shape[0] if valid_len is None else valid_len
        return _ref.decode_attention_ref(q, k, v, k.shape[1]
                                         if valid_len is None else valid_len)
    return _decode_attn(q, k, v, valid_len=valid_len,
                        interpret=(impl == "interpret"), **kw)


def _gathered_view(k_pages, v_pages, rows, k_scales, v_scales):
    """The XLA paths' dense (R, pages_per_seq * page, Hkv, D) view of
    each row's frames, dequantized when the pool is quantized (so the
    interpret==xla anchors compare like with like)."""
    R = rows.shape[0]
    _, page, Hkv, D = k_pages.shape
    k = jnp.take(k_pages, rows, axis=0)            # (R, pps, page, Hkv, D)
    v = jnp.take(v_pages, rows, axis=0)
    if k_scales is not None:
        ks = jnp.take(k_scales, rows, axis=0)      # (R, pps, Hkv)
        vs = jnp.take(v_scales, rows, axis=0)
        k = k.astype(jnp.float32) * ks[:, :, None, :, None]
        v = v.astype(jnp.float32) * vs[:, :, None, :, None]
    Skv = rows.shape[1] * page
    return k.reshape(R, Skv, Hkv, D), v.reshape(R, Skv, Hkv, D)


def _paged_kernel(kernel, q, q_heads: int, k_pages, v_pages, control,
                  k_scales, v_scales, **kw):
    """Call a page-gather kernel as it must run under the active mesh.

    Without a multi-device mesh this is the plain call.  Otherwise the
    call runs in ``shard_map``: the query's head axis (``q_heads``), the
    pool frames' KV-head axis and the scales' head axis split over
    ``model`` when the KV heads divide it (each device attends its own
    KV heads and their grouped query heads; contiguous blocks keep the
    GQA grouping), and are replicated otherwise.  The ``control``
    operands (page table, offsets, lengths) are always replicated."""
    from repro.dist.act_sharding import _current_mesh

    quant = k_scales is not None
    n_ctl = len(control)

    def call(q, kp, vp, *rest):
        ks, vs = rest[n_ctl:] if quant else (None, None)
        return kernel(q, kp, vp, *rest[:n_ctl], k_scales=ks, v_scales=vs,
                      **kw)

    operands = (q, k_pages, v_pages, *control) + \
        ((k_scales, v_scales) if quant else ())
    mesh = _current_mesh()
    if mesh is None or mesh.size == 1:
        return call(*operands)
    model = mesh.shape.get("model", 1)
    ax = "model" if model > 1 and k_pages.shape[2] % model == 0 else None
    q_spec = P(*(ax if d == q_heads else None for d in range(q.ndim)))
    pool = P(None, None, ax, None)
    in_specs = (q_spec, pool, pool) + (P(),) * n_ctl + \
        ((P(None, ax),) * 2 if quant else ())
    return jax.shard_map(call, mesh=mesh, in_specs=in_specs,
                         out_specs=q_spec, check_vma=False)(*operands)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           impl: str = "auto", k_scales=None, v_scales=None,
                           **kw):
    """q: (B, H, D); k/v_pages: (N, page, Hkv, D) pool layout;
    page_table: (B, pages_per_seq) frame ids; lengths: (B,) valid KV.

    ``k_scales``/``v_scales`` (N, Hkv) f32 mark a quantized pool: the
    XLA path dequantizes the gathered view, the Pallas path fuses the
    per-(frame, head) multiply into the gather kernel."""
    impl = resolve_impl(impl)
    if impl == "xla":
        # gather the dense view and defer to the shared one-token
        # reference (the dense decode block's exact expressions) so the
        # paged and dense decode paths stay bit-exact
        from repro.models.attention import one_token_attention
        B, H, D = q.shape
        k, v = _gathered_view(k_pages, v_pages, page_table, k_scales,
                              v_scales)
        out = one_token_attention(q, k, v, lengths, k_pages.shape[2])
        return out.reshape(B, H, D).astype(q.dtype)
    return _paged_kernel(_paged_decode_attn, q, 1, k_pages, v_pages,
                         (page_table, lengths), k_scales, v_scales,
                         interpret=(impl == "interpret"), **kw)


def paged_verify_attention(q, k_pages, v_pages, page_table, lengths, *,
                           impl: str = "auto", k_scales=None, v_scales=None,
                           **kw):
    """q: (B, S, H, D) — S = K + 1 speculative verify rows; k/v_pages:
    (N, page, Hkv, D) pool layout; page_table: (B, pages_per_seq) frame
    ids; lengths: (B, S) per-row valid KV.

    The XLA path gathers the dense view once and defers to the shared
    ``multi_token_attention`` reference — the one-token decode
    expressions with an S axis — so verify-row s stays bit-exact with
    the sequential decode step it replaces (the property speculative
    token-exactness rests on).
    """
    impl = resolve_impl(impl)
    if impl == "xla":
        from repro.models.attention import multi_token_attention
        B, S, H, D = q.shape
        k, v = _gathered_view(k_pages, v_pages, page_table, k_scales,
                              v_scales)
        out = multi_token_attention(q, k, v, lengths, k_pages.shape[2])
        return out.reshape(B, S, H, D).astype(q.dtype)
    return _paged_kernel(_paged_verify_attn, q, 2, k_pages, v_pages,
                         (page_table, lengths), k_scales, v_scales,
                         interpret=(impl == "interpret"), **kw)


def paged_prefill_attention(q, k_pages, v_pages, page_rows, offset, lengths,
                            *, window: int = 0, impl: str = "auto",
                            k_scales=None, v_scales=None, **kw):
    """Prompt-chunk attention over the paged KV pool (chunked prefill).

    q: (C, T, H, D) — one prompt chunk per row, model layout;
    k/v_pages: (N, page, Hkv, D) pool layout; page_rows: (C, pages_per_seq)
    frame ids; offset/lengths: (C,) absolute start + valid tokens per row.

    The XLA path gathers each row's page-table view and runs the exact
    ``chunked_attention`` expressions dense prefill uses (per-row
    ``q_offset`` shifts the causal wedge), which is what keeps a chunked
    prefill's generated tokens equal to an uninterrupted dense prefill's.
    The pallas/interpret path is the blocked flash kernel
    (``flash_attention.paged_prefill_flash``): each query block gathers
    only the KV blocks it can reach, P frames a block by DMA through the
    page table, and reads a quantized pool with its scales fused.
    Positions past a row's length are don't-care in both paths.
    """
    impl = resolve_impl(impl)
    if impl == "xla":
        from repro.models.attention import chunked_attention
        k, v = _gathered_view(k_pages, v_pages, page_rows, k_scales,
                              v_scales)
        return chunked_attention(q, k, v, causal=True, window=window,
                                 q_offset=offset)
    out = _paged_kernel(_paged_prefill_flash, q.transpose(0, 2, 1, 3), 1,
                        k_pages, v_pages, (page_rows, offset, lengths),
                        k_scales, v_scales, window=window,
                        interpret=(impl == "interpret"), **kw)
    return out.transpose(0, 2, 1, 3)


def wkv6(r, k, v, w, u, *, impl: str = "auto", chunk: int = 64):
    impl = resolve_impl(impl)
    if impl == "xla":
        from repro.models.ssm import wkv6_chunked
        return wkv6_chunked(r, k, v, w, u, chunk=chunk)
    return _wkv6(r, k, v, w, u, chunk=chunk, interpret=(impl == "interpret"))


def ssd(x, dt, A, B, C, D, *, impl: str = "auto", chunk: int = 128):
    impl = resolve_impl(impl)
    if impl == "xla":
        from repro.models.ssm import ssd_chunked
        return ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
    return _ssd(x, dt, A, B, C, D, chunk=chunk,
                interpret=(impl == "interpret"))


def gather_rows(src, idx, *, impl: str = "auto", **kw):
    impl = resolve_impl(impl)
    if impl == "xla":
        return _ref.gather_rows_ref(src, idx)
    return _gather_rows(src, idx, interpret=(impl == "interpret"), **kw)
