"""The plain reference of the dense decoder, and its lower-precision
control.

Straightforward ``jax.numpy`` in float32 at ``HIGHEST`` matmul
precision: no kernels, no cache, no batching.  It imports nothing of
the program under test and takes nothing the program made: it makes its
own weights, one layer at a time, from the seed with ``bench.weights``.

The equations are those of the repository's dense family, which departs
from the published models in ways each configuration file lists under
``assumed`` (rotary embedding over the whole head, no rope scaling, a
vocabulary padded to 256); the output table is the embedding's where the
file ties them:

    x_0 = E[t]
    h = rms(x) * g_attn;  q, k, v = h Wq, h Wk, h Wv
    q, k = rope(q), rope(k)       # halves rotated, theta = rope_theta
    a = softmax(q k^T / sqrt(D) + causal) v     # head h reads KV h // (H/Hkv)
    x = x + a Wo
    h = rms(x) * g_mlp;  x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x_L) * g_final) W_out^T

The control is the same forward with both operands of every weight
matmul rounded to float8 (e4m3, one scale per row of the activations and
per column of the weights), the step below the served bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
BUCKET = 1024         # sequence lengths are padded to this (fewer programs)
ROW_BLOCK = 256       # logits rows computed at once


def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) \
        / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "fp8"))
def _layer(x, w, *, z, eps, theta, fp8):
    z = dict(z)
    S = x.shape[0]
    H, Hkv, D = z["H"], z["Hkv"], z["D"]
    g = H // Hkv
    h = _rms(x, w["attn_norm/scale"], eps)
    q = _rope(_mm(h, w["attn/q/w"], fp8).reshape(S, H, D), theta)
    k = _rope(_mm(h, w["attn/k/w"], fp8).reshape(S, Hkv, D), theta)
    v = _mm(h, w["attn/v/w"], fp8).reshape(S, Hkv, D)
    s = jnp.einsum("qhgd,khd->hgqk", q.reshape(S, Hkv, g, D), k,
                   precision=HI) / math.sqrt(D)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI).reshape(S, H * D)
    x = x + _mm(a, w["attn/o/w"], fp8)
    h = _rms(x, w["mlp_norm/scale"], eps)
    m = jax.nn.silu(_mm(h, w["mlp/gate/w"], fp8)) * _mm(h, w["mlp/up/w"], fp8)
    return x + _mm(m, w["mlp/down/w"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _layer_weights(key, l, *, cfg_items):
    return {k: v.astype(jnp.float32)
            for k, v in W.layer(dict(cfg_items), key, l).items()}


@jax.jit
def _logit_rows(h, table, served, ref_h):
    """Per row: the reference's best logit, the reference logit of the
    served token, and (given the control's hidden rows ``h``) the
    reference logit of the token the control puts first."""
    ref = jnp.matmul(ref_h, table.T, precision=HI)
    best = ref.max(-1)
    at_served = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if h is None:
        return best, at_served, at_served
    ctrl = _mm(h, table.T, fp8=True)
    pick = jnp.argmax(ctrl, -1)
    at_pick = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return best, at_served, at_pick


def logit_gaps(cfg: Dict[str, Any], seed: int,
               seqs: Sequence[Tuple[np.ndarray, int]],
               control: bool = False) -> List[Dict[str, np.ndarray]]:
    """For each ``(tokens, n_prompt)`` — a prompt and the tokens served
    after it — the gap by which each served token's reference logit lies
    below the reference's best at its position; with ``control``, also
    the gap of the token the float8 control puts first there."""
    z = W.dims(cfg)
    eps = float(cfg.get("rms_norm_eps", 1e-5))
    theta = float(cfg.get("rope_theta", 10000.0))
    if float(cfg.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("the reference rotates whole heads only")
    key = W.base_key(seed)
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, bool, str))))
    top = {k: v for k, v in jax.jit(lambda k: W.top(cfg, k))(key).items()}
    table = top["embed/table"]
    zi = tuple(sorted(z.items()))
    # each sequence is padded to a whole number of buckets, so runs reuse
    # a few compiled programs
    xs, xc = [], []
    for toks, _ in seqs:
        inp = np.asarray(toks[:-1], np.int32)
        inp = np.pad(inp, (0, -(-len(inp) // BUCKET) * BUCKET - len(inp)))
        x = jnp.take(table, jnp.asarray(inp), axis=0).astype(jnp.float32)
        xs.append(x)
        xc.append(x)
    for l in range(z["L"]):
        w = _layer_weights(key, l, cfg_items=cfg_items)
        xs = [_layer(x, w, z=zi, eps=eps, theta=theta, fp8=False)
              for x in xs]
        if control:
            xc = [_layer(x, w, z=zi, eps=eps, theta=theta, fp8=True)
                  for x in xc]
        del w
    out_table = (top["lm_head/table"] if "lm_head/table" in top
                 else table).astype(jnp.float32)
    g_final = top["final_norm/scale"].astype(jnp.float32)
    results = []
    for i, (toks, n_prompt) in enumerate(seqs):
        toks = np.asarray(toks, np.int32)
        n = len(toks) - n_prompt
        m = -(-n // ROW_BLOCK) * ROW_BLOCK     # whole blocks: fixed shapes
        rows = np.pad(np.arange(n_prompt - 1, len(toks) - 1), (0, m - n))
        served = np.pad(toks[n_prompt:], (0, m - n))
        ref_h = _rms(xs[i][rows], g_final, eps)
        ctl_h = _rms(xc[i][rows], g_final, eps) if control else None
        best, at_s, at_p = [], [], []
        for b in range(0, m, ROW_BLOCK):
            sl = slice(b, b + ROW_BLOCK)
            r = _logit_rows(None if ctl_h is None else ctl_h[sl], out_table,
                            jnp.asarray(served[sl]), ref_h[sl])
            best.append(np.asarray(r[0]))
            at_s.append(np.asarray(r[1]))
            at_p.append(np.asarray(r[2]))
        best, at_s, at_p = (np.concatenate(a)[:n]
                            for a in (best, at_s, at_p))
        res = {"served_gap": best - at_s}
        if control:
            res["control_gap"] = best - at_p
        results.append(res)
    return results
