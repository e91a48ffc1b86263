"""Chunked WKV6 (RWKV-6 "Finch") Pallas kernel.

The recurrent state S (K x V per head) is the AMU's resident SPM working
set; token chunks of length ``c`` stream through VMEM.  Grid is
(B, H, T/c) with the chunk dimension sequential — S lives in VMEM
scratch across chunk steps, so HBM traffic is O(T) in the inputs and
O(1) in state (the whole point of a linear-recurrence kernel on far
memory: one stream in, one stream out, no S x S attention matrix).

Math (per head, log-decay w <= 0, bonus u):
  o_t = S_{t-1}^T r_t + (r_t . (u*k_t)) v_t
  S_t = diag(e^{w_t}) S_{t-1} + k_t v_t^T
Chunked: intra-chunk pairwise decay P[t,s] = e^{W_{t-1} - W_s} (s < t,
always <= 0 so exp never overflows), inter-chunk via the carried S.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import resolve_interpret

__all__ = ["wkv6"]


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, S, *, c: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        S[...] = jnp.zeros_like(S)

    r = r_ref[0, 0].astype(jnp.float32)        # (c, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)        # (c, V)
    w = w_ref[0, 0].astype(jnp.float32)        # (c, K) log decay
    u = u_ref[0].astype(jnp.float32)           # (1, K)

    Wc = jnp.cumsum(w, axis=0)                 # inclusive
    Wprev = Wc - w                             # W_{t-1}

    # inter-chunk: o_t += (r_t * e^{W_{t-1}}) @ S_in
    o = jax.lax.dot(r * jnp.exp(Wprev), S[...])            # (c, V)

    # intra-chunk: att[t,s] = sum_k r_t e^{W_{t-1}-W_s} k_s  (s < t)
    # factor the pairwise tensor through the K dim in c x c tiles:
    # att = (r * e^{Wprev}) @ (k * e^{-Wc})^T is unstable; instead compute
    # per-pair exponents relative to the chunk via one (c, c, K) einsum —
    # c is small (<=64) so the tile fits VMEM.
    pair = Wprev[:, None, :] - Wc[None, :, :]              # (c, c, K)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    pdec = jnp.exp(jnp.minimum(pair, 0.0)) * tri[..., None]
    att = jnp.einsum("tk,tsk,sk->ts", r, pdec, k,
                     preferred_element_type=jnp.float32)
    o = o + jax.lax.dot(att, v)

    # bonus diagonal
    o = o + jnp.sum(r * (u * k), axis=-1, keepdims=True) * v

    # state update: S_out = e^{W_last} S + sum_s (k_s e^{W_last - W_s}) v_s^T
    Wl = Wc[-1:, :]                                        # (1, K)
    kdec = k * jnp.exp(Wl - Wc)                            # (c, K)
    S[...] = jnp.exp(Wl).T * S[...] + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())))                 # (K, V)

    o_ref[0, 0] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(
    r: jnp.ndarray,            # (B, T, H, K)
    k: jnp.ndarray,
    v: jnp.ndarray,            # (B, T, H, V)
    w: jnp.ndarray,            # (B, T, H, K) log decay (<= 0)
    u: jnp.ndarray,            # (H, K)
    *,
    chunk: int = 64,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    B, T, H, K = r.shape
    V = v.shape[-1]
    c = min(chunk, T)
    assert T % c == 0, (T, c)

    # kernel layout: (B, H, T, K)
    rT, kT, vT, wT = (a.transpose(0, 2, 1, 3) for a in (r, k, v, w))
    kernel = functools.partial(_wkv6_kernel, c=c)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, T // c),
        in_specs=[
            pl.BlockSpec((1, 1, c, K), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, c, K), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, c, V), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, c, K), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, K), lambda b, h, j: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, V), lambda b, h, j: (b, h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, V), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rT, kT, vT, wT, u)
    return out.transpose(0, 2, 1, 3)
