"""Composable model builder: one init/apply pair per architecture family.

Families (``cfg.family``):
  * ``dense`` / ``moe`` (and VLM backbones) — decoder-only transformer;
    MoE layers placed every ``cfg.moe_every`` layers, scanned in groups,
  * ``ssm``    — RWKV6 stack (attention-free),
  * ``hybrid`` — Mamba2 stack with a *shared* attention block every
    ``cfg.shared_attn_every`` layers (Zamba2),
  * ``encdec`` — encoder (bidirectional) + decoder (causal + cross-attn),
    with a stub frontend providing precomputed frame/patch embeddings.

All layer stacks are ``lax.scan`` over stacked params (compile time stays
flat in depth); remat is applied to the scan body per ``remat`` policy.
The LM loss streams over sequence chunks so full-vocab logits are never
materialised (vocabs here reach 256k).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import (attention_block, attn_init,
                                    chunked_attention, decode_attention_block,
                                    init_kv_cache, init_paged_kv_cache,
                                    paged_decode_attention_block,
                                    paged_prefill_block, paged_verify_block)
from repro.models.layers import (embed, embed_init, rms_norm, rms_norm_init,
                                 swiglu, swiglu_init, unembed)
from repro.models.moe import moe_block, moe_init

Params = Dict[str, Any]

__all__ = [
    "init_params", "train_loss", "prefill", "decode_step", "verify_step",
    "init_cache", "PagedCache", "init_paged_cache", "prefill_chunk",
    "encode_cross", "chunked_cross_entropy", "count_params",
]


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ===========================================================================
# Layer init/apply per family
# ===========================================================================

def _dense_layer_init(key, cfg: ModelConfig, *, moe: bool, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": rms_norm_init(cfg.d_model, dtype),
        "attn": attn_init(k1, cfg, dtype),
        "mlp_norm": rms_norm_init(cfg.d_model, dtype),
        "mlp": (moe_init(k2, cfg, dtype) if moe
                else swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype)),
    }


def _dense_layer_apply(p, cfg: ModelConfig, x, positions, *, moe: bool,
                       causal=True, compute_dtype=jnp.bfloat16):
    from repro.dist import act_sharding as acts
    # Megatron-SP: residual stream sequence-sharded over model between
    # layers (row-parallel outputs reduce-scatter instead of all-reduce).
    # MoE layers need the full sequence per row for sort-based dispatch;
    # gated off for families where it regressed in the §Perf sweep:
    # MoE-every-layer (no dense stretch to amortise the reshard) and
    # hybrid (mamba blocks would ping-pong with the shared attn block).
    eligible = (cfg.family in ("dense", "moe")
                and not (moe and cfg.moe_every == 1))
    rspec = acts.residual_spec(x.shape[1], gather=moe) if eligible else None
    if rspec is not None:
        x = acts.constrain(x, rspec)
    with acts.residual_layout(rspec is not None and not moe):
        a, _ = attention_block(p["attn"], cfg,
                               rms_norm(p["attn_norm"], x, cfg.norm_eps),
                               positions, causal=causal,
                               compute_dtype=compute_dtype)
        x = x + a
        h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
        if moe:
            m, aux = moe_block(p["mlp"], cfg, h, compute_dtype=compute_dtype)
        else:
            m, aux = (swiglu(p["mlp"], h, compute_dtype),
                      jnp.zeros((), jnp.float32))
        x = x + m
    if rspec is not None:
        x = acts.constrain(x, rspec)
    return x, aux


def _encdec_dec_layer_init(key, cfg: ModelConfig, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "self_norm": rms_norm_init(cfg.d_model, dtype),
        "self_attn": attn_init(k1, cfg, dtype),
        "cross_norm": rms_norm_init(cfg.d_model, dtype),
        "cross_attn": attn_init(k2, cfg, dtype),
        "mlp_norm": rms_norm_init(cfg.d_model, dtype),
        "mlp": swiglu_init(k3, cfg.d_model, cfg.d_ff, dtype),
    }


# ===========================================================================
# init_params
# ===========================================================================

def init_params(cfg: ModelConfig, key) -> Params:
    """Build the full parameter pytree (layer stacks stacked on axis 0)."""
    dtype = _pdtype(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": rms_norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[1], cfg.padded_vocab, cfg.d_model, dtype)

    fam = cfg.family
    if fam in ("dense", "moe"):
        if cfg.num_experts and cfg.moe_every > 1:
            n_groups = cfg.num_layers // cfg.moe_every
            gk = jax.random.split(keys[2], n_groups)

            def group_init(k):
                kd, km = jax.random.split(k)
                dks = jax.random.split(kd, cfg.moe_every - 1)
                return {
                    "dense": jax.vmap(lambda kk: _dense_layer_init(
                        kk, cfg, moe=False, dtype=dtype))(dks),
                    "moe": _dense_layer_init(km, cfg, moe=True, dtype=dtype),
                }

            params["groups"] = jax.vmap(group_init)(gk)
        else:
            lk = jax.random.split(keys[2], cfg.num_layers)
            params["layers"] = jax.vmap(lambda k: _dense_layer_init(
                k, cfg, moe=bool(cfg.num_experts), dtype=dtype))(lk)
    elif fam == "ssm":
        lk = jax.random.split(keys[2], cfg.num_layers)
        params["layers"] = jax.vmap(lambda k: ssm_mod.rwkv6_init(k, cfg, dtype))(lk)
    elif fam == "hybrid":
        every = cfg.shared_attn_every or cfg.num_layers
        n_groups = cfg.num_layers // every
        tail = cfg.num_layers - n_groups * every
        gk = jax.random.split(keys[2], max(n_groups, 1))
        params["mamba_groups"] = jax.vmap(
            lambda k: jax.vmap(lambda kk: ssm_mod.mamba2_init(kk, cfg, dtype))(
                jax.random.split(k, every)))(gk)
        if tail:
            tk = jax.random.split(keys[3], tail)
            params["mamba_tail"] = jax.vmap(
                lambda k: ssm_mod.mamba2_init(k, cfg, dtype))(tk)
        params["shared_attn"] = _dense_layer_init(keys[4], cfg, moe=False,
                                                  dtype=dtype)
    elif fam == "encdec":
        ek = jax.random.split(keys[2], cfg.encoder_layers)
        params["encoder"] = jax.vmap(lambda k: _dense_layer_init(
            k, cfg, moe=False, dtype=dtype))(ek)
        dk = jax.random.split(keys[3], cfg.num_layers)
        params["decoder"] = jax.vmap(lambda k: _encdec_dec_layer_init(
            k, cfg, dtype))(dk)
        params["enc_norm"] = rms_norm_init(cfg.d_model, dtype)
    else:
        raise ValueError(f"unknown family {fam!r}")
    return params


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ===========================================================================
# forward passes (full sequence)
# ===========================================================================

def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "block":
        return jax.checkpoint(fn, prevent_cse=False)
    if remat == "dots":
        return jax.checkpoint(
            fn, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(f"unknown remat policy {remat!r}")


def _decoder_stack(params, cfg: ModelConfig, x, positions, *, remat="block",
                   causal=True):
    """Run the layer stack for dense/moe/ssm/hybrid; returns (x, aux)."""
    cdt = _cdtype(cfg)
    fam = cfg.family

    if fam in ("dense", "moe"):
        is_moe = bool(cfg.num_experts)
        if is_moe and cfg.moe_every > 1:
            def group_body(carry, gp):
                x = carry
                def dense_body(x, lp):
                    y, _ = _dense_layer_apply(lp, cfg, x, positions, moe=False,
                                              causal=causal, compute_dtype=cdt)
                    return y, None
                x, _ = jax.lax.scan(_maybe_remat(dense_body, remat), x, gp["dense"])
                x, aux = _maybe_remat(
                    lambda x, p: _dense_layer_apply(p, cfg, x, positions, moe=True,
                                                    causal=causal, compute_dtype=cdt),
                    remat)(x, gp["moe"])
                return x, aux
            x, auxs = jax.lax.scan(group_body, x, params["groups"])
            return x, auxs.sum()
        def body(x, lp):
            y, aux = _dense_layer_apply(lp, cfg, x, positions, moe=is_moe,
                                        causal=causal, compute_dtype=cdt)
            return y, aux
        x, auxs = jax.lax.scan(_maybe_remat(body, remat), x, params["layers"])
        return x, auxs.sum()

    if fam == "ssm":
        def body(x, lp):
            return ssm_mod.rwkv6_block(lp, cfg, x, compute_dtype=cdt), None
        x, _ = jax.lax.scan(_maybe_remat(body, remat), x, params["layers"])
        return x, jnp.zeros((), jnp.float32)

    if fam == "hybrid":
        shared = params["shared_attn"]
        def group_body(x, gp):
            def mamba_body(x, lp):
                return ssm_mod.mamba2_block(lp, cfg, x, compute_dtype=cdt), None
            x, _ = jax.lax.scan(_maybe_remat(mamba_body, remat), x, gp)
            y, _ = _maybe_remat(
                lambda x, p: _dense_layer_apply(p, cfg, x, positions, moe=False,
                                                causal=causal, compute_dtype=cdt),
                remat)(x, shared)
            return y, None
        x, _ = jax.lax.scan(group_body, x, params["mamba_groups"])
        if "mamba_tail" in params:
            def mamba_body(x, lp):
                return ssm_mod.mamba2_block(lp, cfg, x, compute_dtype=cdt), None
            x, _ = jax.lax.scan(_maybe_remat(mamba_body, remat), x,
                                params["mamba_tail"])
        return x, jnp.zeros((), jnp.float32)

    raise ValueError(f"_decoder_stack: bad family {fam}")


def _encode(params, cfg: ModelConfig, src_embeds, *, remat="block"):
    """Bidirectional encoder over stub frontend embeddings (B, S, d)."""
    cdt = _cdtype(cfg)
    x = src_embeds.astype(cdt)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), x.shape[:2])

    def body(x, lp):
        y, _ = _dense_layer_apply(lp, cfg, x, positions, moe=False,
                                  causal=False, compute_dtype=cdt)
        return y, None

    x, _ = jax.lax.scan(_maybe_remat(body, remat), x, params["encoder"])
    return rms_norm(params["enc_norm"], x, cfg.norm_eps)


def _decode_stack_encdec(params, cfg: ModelConfig, x, positions, enc_out, *,
                         remat="block"):
    cdt = _cdtype(cfg)

    def body(x, lp):
        a, _ = attention_block(lp["self_attn"], cfg,
                               rms_norm(lp["self_norm"], x, cfg.norm_eps),
                               positions, causal=True, compute_dtype=cdt)
        x = x + a
        c, _ = attention_block(lp["cross_attn"], cfg,
                               rms_norm(lp["cross_norm"], x, cfg.norm_eps),
                               positions, kv=enc_out, compute_dtype=cdt)
        x = x + c
        h = rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        return x + swiglu(lp["mlp"], h, cdt), None

    x, _ = jax.lax.scan(_maybe_remat(body, remat), x, params["decoder"])
    return x


def forward(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
            remat: str = "block") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward to final hidden states.  Returns (x, aux)."""
    cdt = _cdtype(cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed(params["embed"], tokens, cdt)
    if cfg.mrope_sections:
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (3, B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if cfg.family == "encdec":
        enc_out = _encode(params, cfg, batch["src_embeds"], remat=remat)
        x = _decode_stack_encdec(params, cfg, x, positions, enc_out, remat=remat)
        aux = jnp.zeros((), jnp.float32)
    else:
        x, aux = _decoder_stack(params, cfg, x, positions, remat=remat)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


# ===========================================================================
# loss (chunked over sequence — never materialises (B, S, V) logits)
# ===========================================================================

def chunked_cross_entropy(x, table, labels, *, logit_scale=1.0,
                          chunk: int = 512, z_coef: float = 0.0):
    """Mean next-token xent.  x: (B,S,d) hidden; labels: (B,S) int32,
    -1 = ignore.  Streams over S in chunks of ``chunk``."""
    B, S, d = x.shape
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = (S + pad) // c
    xc = x.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, c).transpose(1, 0, 2)

    def body(carry, xs):
        tot, cnt, zacc = carry
        xb, lb = xs
        logits = (xb.astype(jnp.bfloat16) @ table.astype(jnp.bfloat16).T)
        logits = (logits * logit_scale).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[..., None],
                                   axis=-1)[..., 0]
        valid = (lb >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((lse - gold) * valid)
        zacc = zacc + jnp.sum(jnp.square(lse) * valid)
        cnt = cnt + valid.sum()
        return (tot, cnt, zacc), None

    (tot, cnt, zacc), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
               jnp.zeros((), jnp.float32)), (xc, lc))
    cnt = jnp.maximum(cnt, 1.0)
    return tot / cnt + z_coef * zacc / cnt


def train_loss(params, cfg: ModelConfig, batch, *, remat: str = "block",
               z_coef: float = 0.0):
    """Scalar loss + metrics dict."""
    x, aux = forward(params, cfg, batch, remat=remat)
    table = params["embed"]["table"] if cfg.tie_embeddings else \
        params["lm_head"]["table"]
    xent = chunked_cross_entropy(x, table, batch["labels"],
                                 logit_scale=cfg.logit_scale, z_coef=z_coef)
    loss = xent + aux
    return loss, {"loss": loss, "xent": xent, "aux": aux}


# ===========================================================================
# inference: prefill + decode_step
# ===========================================================================

class Cache(NamedTuple):
    """Decode-time state for any family (unused fields are empty dicts)."""
    kv: Dict[str, jnp.ndarray]         # attention KV (stacked over layers)
    ssm: Any                           # RWKVState/MambaState stacked or ()
    cross: Dict[str, jnp.ndarray]      # encdec: cross-attn KV + enc_out
    pos: jnp.ndarray                   # next absolute position (scalar)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               src_len: Optional[int] = None) -> Cache:
    kv, ssm_state, cross = {}, (), {}
    fam = cfg.family
    if fam in ("dense", "moe", "encdec"):
        kv = init_kv_cache(cfg, batch, max_len)
    if fam == "ssm":
        s = ssm_mod.rwkv6_state_init(cfg, batch)
        ssm_state = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape), s)
    if fam == "hybrid":
        every = cfg.shared_attn_every or cfg.num_layers
        n_groups = cfg.num_layers // every
        s = ssm_mod.mamba2_state_init(cfg, batch)
        ssm_state = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape), s)
        kv = init_kv_cache(cfg, batch, max_len, n_layers=n_groups)
    if fam == "encdec":
        Ssrc = max_len if src_len is None else src_len
        cdt = _cdtype(cfg)
        cross = {
            "k": jnp.zeros((cfg.num_layers, batch, Ssrc, cfg.num_kv_heads,
                            cfg.head_dim), cdt),
            "v": jnp.zeros((cfg.num_layers, batch, Ssrc, cfg.num_kv_heads,
                            cfg.head_dim), cdt),
            "enc_out": jnp.zeros((batch, Ssrc, cfg.d_model), cdt),
        }
    return Cache(kv=kv, ssm=ssm_state, cross=cross,
                 pos=jnp.zeros((batch,), jnp.int32))


class PagedCache(NamedTuple):
    """Decode-time state with attention KV in the paged pool layout.

    ``kv`` holds ``k_pages`` / ``v_pages`` of shape
    ``(L, n_frames, page, Hkv, D)`` — the device :class:`repro.paging.
    PagePool`'s frames, stacked over layers — plus the per-slot
    ``page_table`` (B, pages_per_seq) of physical frame ids.  Non-KV
    state (SSM, cross-attn, positions) keeps the dense per-slot layout:
    it is tiny relative to the KV and is never paged.
    """

    kv: Dict[str, jnp.ndarray]         # k_pages / v_pages / page_table
    ssm: Any                           # RWKVState/MambaState stacked or ()
    cross: Dict[str, jnp.ndarray]      # encdec: cross-attn KV + enc_out
    pos: jnp.ndarray                   # next absolute position, (B,)


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     n_frames: int, page_size: int,
                     src_len: Optional[int] = None,
                     quant=None, shardings=None) -> PagedCache:
    """Like :func:`init_cache` but with the KV in pool-frame layout.

    Frame ``n_frames - 1`` is the *trash frame*: unmapped page-table
    entries (and every entry of an empty decode slot) point there, so
    garbage decode writes never corrupt a live sequence's page.

    ``quant`` (a :class:`~repro.kernels.kv_quant.KVQuantConfig` or mode
    name) switches the pool frames to int8/fp8 payloads with
    ``k_scales``/``v_scales`` carried alongside; ``None``/"none" keeps
    the bf16 layout with no extra keys (bit-identical pytree).
    ``shardings`` places the pool arrays (see
    :func:`~repro.models.attention.init_paged_kv_cache`).
    """
    # the non-KV state (SSM, cross-attn, positions) of the dense cache;
    # built under jit so its dense KV — (L, batch, max_len, Hkv, D), as
    # large as the model's weights at serving sizes — is never allocated
    base = jax.jit(lambda: init_cache(cfg, batch, max_len,
                                      src_len=src_len)._replace(kv={}))()
    fam = cfg.family
    if fam not in ("dense", "moe", "encdec", "hybrid"):
        raise ValueError(f"family {fam!r} has no KV to page")
    n_layers = None
    if fam == "hybrid":
        every = cfg.shared_attn_every or cfg.num_layers
        n_layers = cfg.num_layers // every
    kv = init_paged_kv_cache(cfg, n_frames, page_size, batch, max_len,
                             n_layers=n_layers, quant=quant,
                             shardings=shardings)
    return PagedCache(kv=kv, ssm=base.ssm, cross=base.cross, pos=base.pos)


def decode_step(params, cfg: ModelConfig, cache: Cache,
                tokens: jnp.ndarray,
                src_embeds: Optional[jnp.ndarray] = None,
                *, impl: str = "auto") -> Tuple[jnp.ndarray, Cache]:
    """One-token decode.  tokens: (B, 1) int32.  Returns (logits (B, V), cache).

    Accepts either a dense :class:`Cache` or a :class:`PagedCache`; for
    the latter, attention computes directly on the paged pool layout
    (``impl`` selects the paged-gather backend: the Pallas kernel on
    TPU, the XLA gather elsewhere).
    """
    cdt = _cdtype(cfg)
    pos = cache.pos
    x = embed(params["embed"], tokens, cdt)
    fam = cfg.family
    paged = isinstance(cache, PagedCache)

    if paged:
        if fam == "ssm":
            raise ValueError("family 'ssm' has no KV to page")
        pt = cache.kv["page_table"]
        kkey, vkey = "k_pages", "v_pages"

        def attn(p, h, kl, vl):
            return paged_decode_attention_block(
                p, cfg, h, (kl, vl), pt, pos, compute_dtype=cdt, impl=impl)
    else:
        kkey, vkey = "k", "v"

        def attn(p, h, kl, vl):
            return decode_attention_block(p, cfg, h, (kl, vl), pos,
                                          compute_dtype=cdt)

    if fam == "ssm":
        def body(carry, xs):
            x = carry
            lp, st = xs
            y, st2 = ssm_mod.rwkv6_step(lp, cfg, x, ssm_mod.RWKVState(*st),
                                        compute_dtype=cdt)
            return y, tuple(st2)
        x, new_state = jax.lax.scan(body, x, (params["layers"],
                                              tuple(cache.ssm)))
        kv = cache.kv
        cache = cache._replace(ssm=ssm_mod.RWKVState(*new_state))
    else:
        ks_in, vs_in = cache.kv[kkey], cache.kv[vkey]
        if paged and "k_scales" in cache.kv:
            # quantized pool: thread (pages, scales) pytree tuples
            # through the layer scan; the blocks unpack them
            ks_in = (ks_in, cache.kv["k_scales"])
            vs_in = (vs_in, cache.kv["v_scales"])
        x, kn, vn, new_state = _decode_families(
            params, cfg, x, cache, ks_in, vs_in, attn, cdt)
        if isinstance(kn, tuple):
            kv = dict(cache.kv, **{kkey: kn[0], vkey: vn[0],
                                   "k_scales": kn[1], "v_scales": vn[1]})
        else:
            kv = dict(cache.kv, **{kkey: kn, vkey: vn})
        if new_state is not None:
            cache = cache._replace(ssm=new_state)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    table = params["embed"]["table"] if cfg.tie_embeddings else \
        params["lm_head"]["table"]
    logits = unembed({"table": table}, x, logit_scale=cfg.logit_scale,
                     compute_dtype=cdt)[:, 0]
    new_cache = cache._replace(kv=kv, pos=pos + 1)
    return logits.astype(jnp.float32), new_cache


def verify_step(params, cfg: ModelConfig, cache: Cache,
                tokens: jnp.ndarray, length: jnp.ndarray,
                *, impl: str = "auto") -> Tuple[jnp.ndarray, Cache]:
    """Speculative verify-K decode: score S = K + 1 tokens per slot in
    one step.  tokens: (B, S) int32 — row 0 the last committed token,
    rows 1..K the drafted continuation; length: (B,) valid rows per slot
    (0 marks an inert slot, whose K/V all scatter to the trash frame).
    Returns (logits (B, S, V) f32, cache).

    Logits row ``s`` predicts the token at position ``pos + s + 1``;
    for any draft prefix that matches greedy decode, the rows are
    bit-equal to the sequential :func:`decode_step` logits they replace
    (same layer structure via ``_decode_families``, same attention
    expressions via ``paged_verify_block``).  ``cache.pos`` is NOT
    advanced — acceptance length is decided host-side after the argmax
    comparison, and the engine writes the rewound ``pos`` back.

    Paged KV only, dense/moe families only, no SWA — the engine gates
    speculation accordingly.
    """
    cdt = _cdtype(cfg)
    if not isinstance(cache, PagedCache):
        raise ValueError("verify_step requires a PagedCache")
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"speculative verify not supported for family {cfg.family!r}")
    if cfg.attention == "swa":
        raise ValueError("speculative verify has no SWA ring semantics")
    pos = cache.pos
    x = embed(params["embed"], tokens, cdt)
    pt = cache.kv["page_table"]

    def attn(p, h, kl, vl):
        return paged_verify_block(p, cfg, h, (kl, vl), pt, pos, length,
                                  compute_dtype=cdt, impl=impl)

    ks_in, vs_in = cache.kv["k_pages"], cache.kv["v_pages"]
    if "k_scales" in cache.kv:
        ks_in = (ks_in, cache.kv["k_scales"])
        vs_in = (vs_in, cache.kv["v_scales"])
    x, kn, vn, _ = _decode_families(
        params, cfg, x, cache, ks_in, vs_in, attn, cdt)
    if isinstance(kn, tuple):
        kv = dict(cache.kv, k_pages=kn[0], v_pages=vn[0],
                  k_scales=kn[1], v_scales=vn[1])
    else:
        kv = dict(cache.kv, k_pages=kn, v_pages=vn)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    table = params["embed"]["table"] if cfg.tie_embeddings else \
        params["lm_head"]["table"]
    logits = unembed({"table": table}, x, logit_scale=cfg.logit_scale,
                     compute_dtype=cdt)
    return logits.astype(jnp.float32), cache._replace(kv=kv)


def _decode_families(params, cfg: ModelConfig, x, cache, ks, vs, attn,
                     cdt):
    """One-token decode through the family layer stacks, parameterized
    over the attention callback and the KV arrays — the dense per-slot
    cache and the paged pool frames share every line of layer structure,
    which is what keeps the two layouts bit-exact by construction.

    ``attn(p, h, kl, vl) -> (out, (kn, vn))`` runs one attention block
    on the pre-normed hidden ``h``; ``ks``/``vs`` are the stacked-over-
    layers KV arrays (axis 0 scanned per layer/group).  Returns
    ``(x, k_new, v_new, new_ssm_state_or_None)``.
    """
    fam = cfg.family
    if fam in ("dense", "moe"):
        is_moe = bool(cfg.num_experts)
        if is_moe and cfg.moe_every > 1:
            x, kn, vn = _decode_grouped_moe(params, cfg, x, ks, vs, attn,
                                            cdt)
        else:
            def body(carry, xs):
                x = carry
                lp, kl, vl = xs
                a, (kn, vn) = attn(lp["attn"],
                                   rms_norm(lp["attn_norm"], x, cfg.norm_eps),
                                   kl, vl)
                x = x + a
                h = rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
                if is_moe:
                    m, _ = moe_block(lp["mlp"], cfg, h, compute_dtype=cdt)
                else:
                    m = swiglu(lp["mlp"], h, cdt)
                return x + m, (kn, vn)
            x, (kn, vn) = jax.lax.scan(body, x, (params["layers"], ks, vs))
        return x, kn, vn, None
    if fam == "hybrid":
        return _decode_hybrid(params, cfg, x, cache, ks, vs, attn, cdt)
    if fam == "encdec":
        x, kn, vn = _decode_encdec(params, cfg, x, cache, ks, vs, attn, cdt)
        return x, kn, vn, None
    raise ValueError(f"_decode_families: bad family {fam}")


def _decode_grouped_moe(params, cfg, x, ks, vs, attn, cdt):
    """Decode path for moe_every>1 (llama4): scan groups, inner dense scan.

    ``ks``/``vs`` may be plain pool arrays or ``(pages, scales)`` pytree
    tuples (quantized pool), so all the reshape/index/concat plumbing
    goes through ``tree_map`` — identical graphs for the plain case."""
    tmap = jax.tree_util.tree_map
    n_groups = cfg.num_layers // cfg.moe_every
    d_per = cfg.moe_every - 1
    # cache layout: layer l -> group g = l // moe_every, slot = l % moe_every
    k = tmap(lambda a: a.reshape((n_groups, cfg.moe_every) + a.shape[1:]), ks)
    v = tmap(lambda a: a.reshape((n_groups, cfg.moe_every) + a.shape[1:]), vs)

    def group_body(x, xs):
        gp, kg, vg = xs
        def dense_body(x, ys):
            lp, kl, vl = ys
            a, (kn, vn) = attn(lp["attn"],
                               rms_norm(lp["attn_norm"], x, cfg.norm_eps),
                               kl, vl)
            x = x + a
            m = swiglu(lp["mlp"], rms_norm(lp["mlp_norm"], x, cfg.norm_eps),
                       cdt)
            return x + m, (kn, vn)
        x, (kd, vd) = jax.lax.scan(dense_body, x,
                                   (gp["dense"], tmap(lambda a: a[:d_per], kg),
                                    tmap(lambda a: a[:d_per], vg)))
        lp = gp["moe"]
        a, (km, vm) = attn(lp["attn"],
                           rms_norm(lp["attn_norm"], x, cfg.norm_eps),
                           tmap(lambda a: a[d_per], kg),
                           tmap(lambda a: a[d_per], vg))
        x = x + a
        m, _ = moe_block(lp["mlp"], cfg,
                         rms_norm(lp["mlp_norm"], x, cfg.norm_eps),
                         compute_dtype=cdt)
        x = x + m
        kout = tmap(lambda d, m_: jnp.concatenate([d, m_[None]], axis=0),
                    kd, km)
        vout = tmap(lambda d, m_: jnp.concatenate([d, m_[None]], axis=0),
                    vd, vm)
        return x, (kout, vout)

    x, (kn, vn) = jax.lax.scan(group_body, x, (params["groups"], k, v))
    kn = tmap(lambda a, r: a.reshape(r.shape), kn, ks)
    vn = tmap(lambda a, r: a.reshape(r.shape), vn, vs)
    return x, kn, vn


def _decode_hybrid(params, cfg, x, cache, ks, vs, attn, cdt):
    every = cfg.shared_attn_every or cfg.num_layers
    n_groups = cfg.num_layers // every
    tail = cfg.num_layers - n_groups * every
    sg = jax.tree_util.tree_map(lambda a: a[:n_groups * every].reshape(
        (n_groups, every) + a.shape[1:]), cache.ssm)
    shared = params["shared_attn"]

    def mamba_body(x, ys):
        lp, st = ys
        y, st2 = ssm_mod.mamba2_step(lp, cfg, x, ssm_mod.MambaState(*st),
                                     compute_dtype=cdt)
        return y, tuple(st2)

    def group_body(carry, xs):
        x = carry
        gp, st_g, kl, vl = xs
        x, st_new = jax.lax.scan(mamba_body, x, (gp, tuple(st_g)))
        a, (kn, vn) = attn(shared["attn"],
                           rms_norm(shared["attn_norm"], x, cfg.norm_eps),
                           kl, vl)
        x = x + a
        x = x + swiglu(shared["mlp"], rms_norm(shared["mlp_norm"], x,
                                               cfg.norm_eps), cdt)
        return x, (st_new, kn, vn)

    x, (st_new, kn, vn) = jax.lax.scan(
        group_body, x, (params["mamba_groups"], tuple(sg), ks, vs))
    st_new = ssm_mod.MambaState(*st_new)
    st_flat = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups * every,) + a.shape[2:]), st_new)
    if tail:
        st_tail = jax.tree_util.tree_map(lambda a: a[n_groups * every:],
                                         cache.ssm)
        x, st_tail_new = jax.lax.scan(mamba_body, x,
                                      (params["mamba_tail"], tuple(st_tail)))
        st_flat = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            st_flat, ssm_mod.MambaState(*st_tail_new))
    return x, kn, vn, st_flat


def _decode_encdec(params, cfg, x, cache, ks, vs, attn, cdt, *,
                   cross=None, cross_valid=None):
    """Enc-dec decoder stack shared by one-token decode and the chunked
    paged-prefill path: ``x`` may be (B, 1, d) or a (C, T, d) prompt
    chunk.  ``cross`` overrides the cache's cross-KV trees (the chunk
    path gathers per-slot rows) and ``cross_valid`` masks encoder
    positions past each row's true source length — decode passes
    neither, so its traced graph is unchanged."""
    cross = cache.cross if cross is None else cross

    def body(carry, xs):
        x = carry
        lp, kl, vl, ck, cv = xs
        a, (kn, vn) = attn(lp["self_attn"],
                           rms_norm(lp["self_norm"], x, cfg.norm_eps),
                           kl, vl)
        x = x + a
        # cross attention against precomputed cross KV (no rope, not causal)
        from repro.models.layers import dense
        B, S = x.shape[:2]
        hd = cfg.head_dim
        xq = rms_norm(lp["cross_norm"], x, cfg.norm_eps)
        q = dense(lp["cross_attn"]["q"], xq, cdt).reshape(B, S, cfg.num_heads, hd)
        c = chunked_attention(q, ck, cv, causal=False,
                              kv_valid_len=cross_valid)
        c = dense(lp["cross_attn"]["o"], c.reshape(B, S, cfg.num_heads * hd), cdt)
        x = x + c
        x = x + swiglu(lp["mlp"], rms_norm(lp["mlp_norm"], x, cfg.norm_eps), cdt)
        return x, (kn, vn)

    x, (kn, vn) = jax.lax.scan(
        body, x, (params["decoder"], ks, vs, cross["k"], cross["v"]))
    return x, kn, vn


def prefill(params, cfg: ModelConfig, batch, *, max_len: Optional[int] = None,
            last_pos: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Cache]:
    """Encode the prompt, build the cache, return last-token logits.

    For attention families this materialises the KV cache from the full
    forward; for SSM/hybrid families it runs the chunked form with
    ``return_state`` and keeps only the state (O(1) memory in S).

    ``last_pos`` (a per-row ``(B,)`` index, default ``S - 1``) selects
    which position's logits are returned — the serving engine passes the
    prompt's true last token so the first sampled token never depends on
    the padded bucket tail (and so bucketed dense prefill and chunked
    paged prefill agree on it).
    """
    cdt = _cdtype(cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_len = max_len or S
    cache = init_cache(cfg, B, max_len)
    fam = cfg.family
    x = embed(params["embed"], tokens, cdt)
    positions = (jnp.broadcast_to(jnp.arange(S), (3, B, S))
                 if cfg.mrope_sections else
                 jnp.broadcast_to(jnp.arange(S), (B, S)))

    if fam in ("dense", "moe"):
        kv = cache.kv
        slots = int(kv["k"].shape[2])
        if cfg.num_experts and cfg.moe_every > 1:
            x, kn, vn = _prefill_grouped_moe(params, cfg, x, positions, slots, cdt)
        else:
            is_moe = bool(cfg.num_experts)
            def body(x, lp):
                h = rms_norm(lp["attn_norm"], x, cfg.norm_eps)
                a, (k, v) = attention_block(lp["attn"], cfg, h, positions,
                                            compute_dtype=cdt)
                x = x + a
                h2 = rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
                m = (moe_block(lp["mlp"], cfg, h2, compute_dtype=cdt)[0]
                     if is_moe else swiglu(lp["mlp"], h2, cdt))
                return x + m, _cache_fit(k, v, slots)
            x, (kn, vn) = jax.lax.scan(body, x, params["layers"])
        cache = cache._replace(kv=dict(kv, k=kn.astype(kv["k"].dtype),
                                       v=vn.astype(kv["v"].dtype)))
    elif fam == "ssm":
        x, states = _prefill_rwkv(params, cfg, x, cdt)
        cache = cache._replace(ssm=states)
    elif fam == "hybrid":
        x, states, kv = _prefill_hybrid(params, cfg, x, positions, cache, cdt)
        cache = cache._replace(ssm=states, kv=kv)
    elif fam == "encdec":
        enc_out = _encode(params, cfg, batch["src_embeds"], remat="none")
        x, kv, cross = _prefill_encdec(params, cfg, x, positions, enc_out, cdt)
        cache = cache._replace(kv=dict(cache.kv, **kv), cross=cross)
    if last_pos is None:
        x_last = x[:, -1:]
    else:
        idx = jnp.clip(jnp.asarray(last_pos, jnp.int32), 0, S - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    x = rms_norm(params["final_norm"], x_last, cfg.norm_eps)
    table = params["embed"]["table"] if cfg.tie_embeddings else \
        params["lm_head"]["table"]
    logits = unembed({"table": table}, x, logit_scale=cfg.logit_scale,
                     compute_dtype=cdt)[:, 0]
    cache = cache._replace(pos=jnp.full((B,), S, jnp.int32))
    return logits.astype(jnp.float32), cache


def _cache_fit(k, v, slots):
    """Keep the last ``slots`` positions, rolled so absolute position ``p``
    lands in ring slot ``p % slots`` (decode overwrites the oldest entry)."""
    S = k.shape[1]
    if S <= slots:
        return k, v
    shift = S % slots
    return (jnp.roll(k[:, -slots:], shift, axis=1),
            jnp.roll(v[:, -slots:], shift, axis=1))


def _prefill_grouped_moe(params, cfg, x, positions, slots, cdt):
    def group_body(x, gp):
        def dense_body(x, lp):
            h = rms_norm(lp["attn_norm"], x, cfg.norm_eps)
            a, (k, v) = attention_block(lp["attn"], cfg, h, positions,
                                        compute_dtype=cdt)
            x = x + a
            m = swiglu(lp["mlp"], rms_norm(lp["mlp_norm"], x, cfg.norm_eps), cdt)
            return x + m, _cache_fit(k, v, slots)
        x, (kd, vd) = jax.lax.scan(dense_body, x, gp["dense"])
        lp = gp["moe"]
        h = rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        a, (k, v) = attention_block(lp["attn"], cfg, h, positions,
                                    compute_dtype=cdt)
        x = x + a
        m, _ = moe_block(lp["mlp"], cfg,
                         rms_norm(lp["mlp_norm"], x, cfg.norm_eps),
                         compute_dtype=cdt)
        x = x + m
        kf, vf = _cache_fit(k, v, slots)
        return x, (jnp.concatenate([kd, kf[None]], 0),
                   jnp.concatenate([vd, vf[None]], 0))
    x, (kg, vg) = jax.lax.scan(group_body, x, params["groups"])
    L = cfg.num_layers
    kn = kg.reshape((L,) + kg.shape[2:])
    vn = vg.reshape((L,) + vg.shape[2:])
    return x, kn, vn


def _prefill_rwkv(params, cfg, x, cdt):
    def body(x, lp):
        xn = x
        from repro.models.layers import layer_norm
        h = layer_norm(lp["ln1"], xn, cfg.norm_eps)
        tm, S = ssm_mod.rwkv6_time_mix(lp, cfg, h, return_state=True,
                                       compute_dtype=cdt)
        x = x + tm
        h2 = layer_norm(lp["ln2"], x, cfg.norm_eps)
        x = x + ssm_mod.rwkv6_channel_mix(lp, cfg, h2, compute_dtype=cdt)
        st = ssm_mod.RWKVState(S=S, tm_prev=h[:, -1].astype(jnp.float32),
                               cm_prev=h2[:, -1].astype(jnp.float32))
        return x, tuple(st)
    x, states = jax.lax.scan(body, x, params["layers"])
    return x, ssm_mod.RWKVState(*states)


def _prefill_hybrid(params, cfg, x, positions, cache, cdt):
    every = cfg.shared_attn_every or cfg.num_layers
    n_groups = cfg.num_layers // every
    tail = cfg.num_layers - n_groups * every
    shared = params["shared_attn"]
    slots = int(cache.kv["k"].shape[2])

    def mamba_state_body(x, lp):
        from repro.models.layers import rms_norm as rn
        xn = rn(lp["norm"], x, cfg.norm_eps)
        z, xbc, dt_raw = ssm_mod._mamba2_project(lp, cfg, xn, cdt)
        xbc_conv, conv_ctx = ssm_mod._causal_conv(xbc, lp["conv_w"], lp["conv_b"])
        xh, dt, Bs, Cs = ssm_mod._mamba2_ssm_inputs(lp, cfg, xbc_conv, dt_raw)
        A = jnp.exp(lp["A_log"].astype(jnp.float32))
        y, S = ssm_mod.ssd_chunked(xh, dt, A, Bs, Cs, lp["D"],
                                   return_state=True)
        y = y.reshape(x.shape[0], x.shape[1], cfg.d_inner)
        y = rn(lp["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
        from repro.models.layers import dense
        out = x + dense(lp["out_proj"], y.astype(cdt), cdt)
        st = ssm_mod.MambaState(S=S, conv=xbc[:, -(cfg.ssm_conv - 1):]
                                .astype(jnp.float32))
        return out, tuple(st)

    def group_body(x, xs):
        gp = xs
        x, st = jax.lax.scan(mamba_state_body, x, gp)
        h = rms_norm(shared["attn_norm"], x, cfg.norm_eps)
        a, (k, v) = attention_block(shared["attn"], cfg, h, positions,
                                    compute_dtype=cdt)
        x = x + a
        x = x + swiglu(shared["mlp"], rms_norm(shared["mlp_norm"], x,
                                               cfg.norm_eps), cdt)
        kf, vf = _cache_fit(k, v, slots)
        return x, (st, kf, vf)

    x, (st_g, kn, vn) = jax.lax.scan(group_body, x, params["mamba_groups"])
    st_flat = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups * every,) + a.shape[2:]),
        ssm_mod.MambaState(*st_g))
    if tail:
        x, st_t = jax.lax.scan(mamba_state_body, x, params["mamba_tail"])
        st_flat = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], 0), st_flat,
            ssm_mod.MambaState(*st_t))
    kv = dict(cache.kv, k=kn.astype(cache.kv["k"].dtype),
              v=vn.astype(cache.kv["v"].dtype))
    return x, st_flat, kv


# ===========================================================================
# chunked paged prefill: prompt chunks computed directly on the pool layout
# ===========================================================================


def encode_cross(params, cfg: ModelConfig, src_embeds) -> Dict[str, Any]:
    """Run the encoder once and project the per-layer cross-attention KV.

    The chunked-prefill admission path for enc-dec: the encoder (and the
    cross K/V projections) run once when a request is admitted, their
    rows are installed into the batched cache's ``cross`` tree, and every
    subsequent prompt chunk / decode token reads them from there.  The
    projections are exactly the ones dense prefill's cross
    ``attention_block`` computes, so chunked and dense prefill agree.
    """
    cdt = _cdtype(cfg)
    enc_out = _encode(params, cfg, src_embeds, remat="none")
    B, Skv, _ = enc_out.shape
    hd = cfg.head_dim

    def body(carry, lp):
        from repro.models.layers import dense
        k = dense(lp["cross_attn"]["k"], enc_out, cdt).reshape(
            B, Skv, cfg.num_kv_heads, hd)
        v = dense(lp["cross_attn"]["v"], enc_out, cdt).reshape(
            B, Skv, cfg.num_kv_heads, hd)
        return carry, (k, v)

    _, (ck, cv) = jax.lax.scan(body, 0, params["decoder"])
    return {"k": ck, "v": cv, "enc_out": enc_out}


def _chunk_hybrid(params, cfg: ModelConfig, x, carry, ks, vs, attn, cdt):
    """Chunked-prefill layer stack for the hybrid family: the group
    structure of :func:`_decode_hybrid` with a *chunked* Mamba2 body that
    consumes and emits explicit per-layer state (``carry``: a
    ``MambaState`` with leaves stacked ``(num_layers, C, ...)``), so a
    prompt can be prefilled across several engine steps with the SSM
    state carried host-side between chunks."""
    from repro.models.layers import dense as dense_proj
    every = cfg.shared_attn_every or cfg.num_layers
    n_groups = cfg.num_layers // every
    tail = cfg.num_layers - n_groups * every
    B, T, _ = x.shape
    sg = jax.tree_util.tree_map(lambda a: a[:n_groups * every].reshape(
        (n_groups, every) + a.shape[1:]), carry)
    shared = params["shared_attn"]

    def mamba_body(x, ys):
        lp, st = ys
        st = ssm_mod.MambaState(*st)
        xn = rms_norm(lp["norm"], x, cfg.norm_eps)
        z, xbc, dt_raw = ssm_mod._mamba2_project(lp, cfg, xn, cdt)
        xbc_conv, new_conv = ssm_mod._causal_conv(
            xbc, lp["conv_w"], lp["conv_b"], conv_state=st.conv)
        xh, dt, Bs, Cs = ssm_mod._mamba2_ssm_inputs(lp, cfg, xbc_conv, dt_raw)
        A = jnp.exp(lp["A_log"].astype(jnp.float32))
        y, S = ssm_mod.ssd_chunked(xh, dt, A, Bs, Cs, lp["D"],
                                   initial_state=st.S, return_state=True)
        y = y.reshape(B, T, cfg.d_inner)
        y = rms_norm(lp["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
        out = x + dense_proj(lp["out_proj"], y.astype(cdt), cdt)
        st2 = ssm_mod.MambaState(S=S, conv=new_conv.astype(st.conv.dtype))
        return out, tuple(st2)

    def group_body(carry_x, xs):
        x = carry_x
        gp, st_g, kl, vl = xs
        x, st_new = jax.lax.scan(mamba_body, x, (gp, tuple(st_g)))
        a, (kn, vn) = attn(shared["attn"],
                           rms_norm(shared["attn_norm"], x, cfg.norm_eps),
                           kl, vl)
        x = x + a
        x = x + swiglu(shared["mlp"], rms_norm(shared["mlp_norm"], x,
                                               cfg.norm_eps), cdt)
        return x, (st_new, kn, vn)

    x, (st_new, kn, vn) = jax.lax.scan(
        group_body, x, (params["mamba_groups"], tuple(sg), ks, vs))
    st_new = ssm_mod.MambaState(*st_new)
    st_flat = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups * every,) + a.shape[2:]), st_new)
    if tail:
        st_tail = jax.tree_util.tree_map(lambda a: a[n_groups * every:],
                                         carry)
        x, st_tail_new = jax.lax.scan(mamba_body, x,
                                      (params["mamba_tail"], tuple(st_tail)))
        st_flat = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            st_flat, ssm_mod.MambaState(*st_tail_new))
    return x, kn, vn, st_flat


def prefill_chunk(params, cfg: ModelConfig, cache: PagedCache,
                  chunk: Dict[str, Any], *, impl: str = "auto"
                  ) -> Tuple[jnp.ndarray, PagedCache, Any]:
    """Run one prompt chunk for up to C admitting slots on the pool layout.

    The compute half of chunked paged prefill (the serving pattern the
    follow-up AMU paper, 2404.11044, builds its massive-parallelism case
    on): instead of densely prefilling a whole prompt in one bubble, the
    engine feeds prompts through in chunks that flash-attend against the
    sequence's pool-resident prefix while scattering their own K/V into
    the mapped frames (:func:`~repro.models.attention.
    paged_prefill_block`) — so prefill and decode share one fused step
    and dense KV never exists, not even transiently.

    ``chunk`` keys (C = chunk rows, T = chunk token capacity):

    * ``tokens`` (C, T) int32 — prompt chunk token ids, zero-padded,
    * ``offset`` / ``length`` (C,) int32 — each row's absolute start
      position and valid token count (``length == 0`` rows are inert:
      their K/V writes land in the trash frame),
    * ``page_rows`` (C, pages_per_seq) int32 — pool frame ids covering
      ``[0, offset + length)`` for each row (trash id beyond),
    * ``slots`` (C,) int32 — the decode slot each row occupies (used to
      gather enc-dec cross-KV rows),
    * ``src_len`` (C,) int32 — enc-dec only: true encoder length,
    * ``ssm`` — hybrid only: ``MambaState`` carry with leaves stacked
      ``(num_layers, C, ...)``.

    Returns ``(logits, cache, carry)``: logits at each row's last valid
    token (the first sampled token when the row just finished its
    prompt), the cache with the pool frames updated in place, and the
    state carry to thread into the row's next chunk (hybrid; else None).

    Layer structure is shared with the decode path (the same
    ``_decode_families`` bodies run with a multi-token ``x`` and the
    paged-prefill attention callback), which is what keeps chunked
    prefill + paged decode bit-compatible with a dense run.
    """
    cdt = _cdtype(cfg)
    fam = cfg.family
    if fam == "ssm":
        raise ValueError("family 'ssm' has no KV to page")
    toks = chunk["tokens"]
    C, T = toks.shape
    offset = jnp.asarray(chunk["offset"], jnp.int32)
    length = jnp.asarray(chunk["length"], jnp.int32)
    page_rows = jnp.asarray(chunk["page_rows"], jnp.int32)
    x = embed(params["embed"], toks, cdt)
    pos2 = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    positions = (jnp.broadcast_to(pos2, (3, C, T)) if cfg.mrope_sections
                 else pos2)
    kv = cache.kv
    kp, vp = kv["k_pages"], kv["v_pages"]
    if "k_scales" in kv:
        kp = (kp, kv["k_scales"])
        vp = (vp, kv["v_scales"])

    def attn(p, h, kl, vl):
        return paged_prefill_block(p, cfg, h, (kl, vl), page_rows, offset,
                                   length, positions, compute_dtype=cdt,
                                   impl=impl)

    carry_out = None
    if fam in ("dense", "moe"):
        x, kn, vn, _ = _decode_families(params, cfg, x, cache, kp, vp,
                                        attn, cdt)
    elif fam == "hybrid":
        x, kn, vn, carry_out = _chunk_hybrid(params, cfg, x, chunk["ssm"],
                                             kp, vp, attn, cdt)
    elif fam == "encdec":
        slots_ix = jnp.asarray(chunk["slots"], jnp.int32)
        cross = {"k": cache.cross["k"][:, slots_ix],
                 "v": cache.cross["v"][:, slots_ix]}
        x, kn, vn = _decode_encdec(params, cfg, x, cache, kp, vp, attn, cdt,
                                   cross=cross,
                                   cross_valid=jnp.asarray(chunk["src_len"],
                                                           jnp.int32))
    else:
        raise ValueError(f"prefill_chunk: bad family {fam}")

    idx = jnp.clip(length - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    x_last = rms_norm(params["final_norm"], x_last, cfg.norm_eps)
    table = params["embed"]["table"] if cfg.tie_embeddings else \
        params["lm_head"]["table"]
    logits = unembed({"table": table}, x_last, logit_scale=cfg.logit_scale,
                     compute_dtype=cdt)[:, 0]
    if isinstance(kn, tuple):
        new_cache = cache._replace(kv=dict(kv, k_pages=kn[0], v_pages=vn[0],
                                           k_scales=kn[1], v_scales=vn[1]))
    else:
        new_cache = cache._replace(kv=dict(kv, k_pages=kn, v_pages=vn))
    return logits.astype(jnp.float32), new_cache, carry_out


def _prefill_encdec(params, cfg, x, positions, enc_out, cdt):
    def body(x, lp):
        h = rms_norm(lp["self_norm"], x, cfg.norm_eps)
        a, (k, v) = attention_block(lp["self_attn"], cfg, h, positions,
                                    compute_dtype=cdt)
        x = x + a
        hc = rms_norm(lp["cross_norm"], x, cfg.norm_eps)
        c, (ck, cv) = attention_block(lp["cross_attn"], cfg, hc, positions,
                                      kv=enc_out, compute_dtype=cdt)
        x = x + c
        x = x + swiglu(lp["mlp"], rms_norm(lp["mlp_norm"], x, cfg.norm_eps), cdt)
        return x, (k, v, ck, cv)
    x, (kn, vn, ckn, cvn) = jax.lax.scan(body, x, params["decoder"])
    kv = {"k": kn, "v": vn}
    cross = {"k": ckn, "v": cvn, "enc_out": enc_out}
    return x, kv, cross
