"""Chunked Mamba2 SSD Pallas kernel.

Same streaming structure as the WKV6 kernel, but the decay is a *scalar
per head per step* (Mamba2's restriction — what makes SSD hardware
friendly): the intra-chunk pairwise decay is a (c, c) matrix instead of
(c, c, K), so the whole chunk update is three small matmuls — ideal MXU
shape.  State (N x P per head) is the resident SPM working set carried
across the sequential chunk grid dimension.

Per head h:
  S_t = e^{-A_h dt_t} S_{t-1} + dt_t B_t x_t^T     (S: N x P)
  y_t = C_t^T S_t + D_h x_t
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import resolve_interpret

__all__ = ["ssd"]


def _ssd_kernel(x_ref, dt_ref, B_ref, C_ref, A_ref, D_ref, o_ref, S, *,
                c: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        S[...] = jnp.zeros_like(S)

    x = x_ref[0, 0].astype(jnp.float32)        # (c, P)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (c, 1) -> (c,)
    Bm = B_ref[0].astype(jnp.float32)          # (c, N)
    Cm = C_ref[0].astype(jnp.float32)          # (c, N)
    A = A_ref[0, 0].astype(jnp.float32)        # scalar (1, 1)
    Dh = D_ref[0, 0].astype(jnp.float32)

    dA = -A * dt                               # (c, 1) log decay <= 0
    L = jnp.cumsum(dA, axis=0)                 # inclusive (c, 1)

    # inter-chunk: y_t += e^{L_t} C_t @ S_in
    y = jnp.exp(L) * jax.lax.dot(Cm, S[...])   # (c, P)

    # intra-chunk: G[t,s] = e^{L_t - L_s} dt_s (C_t . B_s)  (s <= t)
    pair = L - L.T                             # (c, c) L_t - L_s
    tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    G = jnp.exp(jnp.minimum(pair, 0.0)) * tri
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # (c, c)
    M = CB * G * dt.T                          # dt_s broadcast over rows
    y = y + jax.lax.dot(M, x)

    # skip connection
    y = y + Dh * x

    # state update: S_out = e^{L_last} S + sum_s e^{L_last - L_s} dt_s B_s x_s^T
    Ll = L[-1:, :]                             # (1, 1)
    kdec = Bm * (jnp.exp(Ll - L) * dt)         # (c, N)
    S[...] = jnp.exp(Ll) * S[...] + jax.lax.dot_general(
        kdec, x, (((0,), (0,)), ((), ())))     # (N, P)

    o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: jnp.ndarray,            # (B, T, H, P)
    dt: jnp.ndarray,           # (B, T, H)  (post-softplus)
    A: jnp.ndarray,            # (H,)
    Bm: jnp.ndarray,           # (B, T, N)
    Cm: jnp.ndarray,           # (B, T, N)
    D: jnp.ndarray,            # (H,)
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    c = min(chunk, T)
    assert T % c == 0, (T, c)

    xT = x.transpose(0, 2, 1, 3)               # (B, H, T, P)
    dtT = dt.transpose(0, 2, 1)[..., None]     # (B, H, T, 1)
    A2 = A.reshape(H, 1, 1)
    D2 = D.reshape(H, 1, 1)

    kernel = functools.partial(_ssd_kernel, c=c)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, T // c),
        in_specs=[
            pl.BlockSpec((1, 1, c, P), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, c, 1), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, c, N), lambda b, h, j: (b, j, 0)),
            pl.BlockSpec((1, c, N), lambda b, h, j: (b, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, j: (h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, j: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, P), lambda b, h, j: (b, h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xT, dtT, Bm, Cm, A2, D2)
    return out.transpose(0, 2, 1, 3)
