"""Per-kernel correctness sweeps: Pallas interpret mode vs jnp oracle.

Every kernel is swept over shapes and dtypes; tolerances are relative
(f32 accumulation order differs between chunked kernels and sequential
oracles)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.amu_matmul import amu_matmul
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2 import ssd
from repro.kernels.moe_gather import gather_blocks, gather_rows
from repro.kernels.rwkv6 import wkv6

rng = np.random.default_rng(42)


def _rel_err(out, ref_val):
    out = np.asarray(out, np.float32)
    ref_val = np.asarray(ref_val, np.float32)
    denom = max(1e-6, float(np.abs(ref_val).max()))
    return float(np.abs(out - ref_val).max()) / denom


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# amu_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N,bm,bk,bn", [
    (128, 128, 128, 128, 128, 128),       # single tile, n_k == 1
    (256, 256, 256, 128, 128, 128),       # n_k == 2 (both slots, no refill)
    (256, 512, 384, 128, 128, 128),       # deep pipeline, refills
    (384, 768, 128, 128, 256, 128),       # non-square blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_amu_matmul(M, K, N, bm, bk, bn, dtype):
    x, w = _rand((M, K), dtype), _rand((K, N), dtype)
    out = amu_matmul(x, w, bm=bm, bk=bk, bn=bn)
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    assert _rel_err(out, ref.matmul_ref(x, w)) < tol


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,Sq,Skv,D,causal,window", [
    (2, 4, 2, 128, 128, 64, True, 0),
    (1, 4, 4, 256, 256, 32, True, 0),
    (2, 4, 2, 128, 128, 64, True, 32),     # SWA
    (1, 2, 2, 128, 256, 64, False, 0),     # cross (non-causal, Skv != Sq)
    (1, 8, 2, 192, 192, 128, True, 48),    # GQA 4:1 + SWA + full lane D
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, Hkv, Sq, Skv, D, causal, window, dtype):
    q = _rand((B, Sq, H, D), dtype)
    k = _rand((B, Skv, Hkv, D), dtype)
    v = _rand((B, Skv, Hkv, D), dtype)
    qT, kT, vT = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    out = flash_attention(qT, kT, vT, causal=causal, window=window,
                          bq=64, bkv=64).transpose(0, 2, 1, 3)
    expected = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 5e-6 if dtype == jnp.float32 else 3e-2
    assert _rel_err(out, expected) < tol


def test_flash_matches_model_chunked_attention():
    """Both execution paths (kernel / XLA scan) agree with each other."""
    from repro.models.attention import chunked_attention
    q = _rand((2, 128, 4, 64))
    k = _rand((2, 128, 2, 64))
    v = _rand((2, 128, 2, 64))
    a = chunked_attention(q, k, v, causal=True, chunk=32)
    b = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True,
                        bq=64, bkv=64).transpose(0, 2, 1, 3)
    assert _rel_err(b, a) < 5e-6


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,S,D,valid,bkv", [
    (2, 8, 2, 512, 64, 512, 128),
    (1, 4, 4, 256, 128, 200, 128),
    (2, 16, 4, 256, 64, 33, 64),           # short valid prefix
    (1, 8, 8, 1024, 64, 1000, 256),        # MHA long cache
])
def test_decode_attention(B, H, Hkv, S, D, valid, bkv):
    q = _rand((B, H, D))
    k = _rand((B, S, Hkv, D))
    v = _rand((B, S, Hkv, D))
    out = decode_attention(q, k, v, valid_len=valid, bkv=bkv)
    assert _rel_err(out, ref.decode_attention_ref(q, k, v, valid)) < 5e-6


# ---------------------------------------------------------------------------
# wkv6 / ssd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H,K,chunk", [
    (2, 128, 2, 32, 32),
    (1, 96, 4, 64, 32),
    (2, 64, 2, 128, 16),
    (1, 256, 1, 64, 64),
])
def test_wkv6(B, T, H, K, chunk):
    r, k, v = _rand((B, T, H, K)), _rand((B, T, H, K)), _rand((B, T, H, K))
    w = -jnp.exp(_rand((B, T, H, K)) - 2)
    u = _rand((H, K)) * 0.1
    out = wkv6(r, k, v, w, u, chunk=chunk)
    assert _rel_err(out, ref.wkv6_ref(r, k, v, w, u)) < 1e-4


@pytest.mark.parametrize("B,T,H,P,N,chunk", [
    (2, 128, 2, 32, 16, 32),
    (1, 96, 4, 64, 32, 48),
    (1, 256, 2, 64, 64, 64),
])
def test_ssd(B, T, H, P, N, chunk):
    x = _rand((B, T, H, P))
    dt = jax.nn.softplus(_rand((B, T, H)))
    A = jnp.linspace(0.5, 4.0, H)
    D = _rand((H,))
    Bm, Cm = _rand((B, T, N)), _rand((B, T, N))
    out = ssd(x, dt, A, Bm, Cm, D, chunk=chunk)
    assert _rel_err(out, ref.ssd_ref(x, dt, A, Bm, Cm, D)) < 1e-4


def test_kernels_match_model_chunked_forms():
    """Pallas kernels agree with the models' XLA chunked forms (the
    exact functions the XLA path lowers)."""
    from repro.models.ssm import ssd_chunked, wkv6_chunked
    B, T, H, K = 1, 128, 2, 32
    r, k, v = _rand((B, T, H, K)), _rand((B, T, H, K)), _rand((B, T, H, K))
    w = -jnp.exp(_rand((B, T, H, K)) - 2)
    u = _rand((H, K)) * 0.1
    assert _rel_err(wkv6(r, k, v, w, u, chunk=32),
                    wkv6_chunked(r, k, v, w, u, chunk=32)) < 1e-5

    P = N = 32
    x = _rand((B, T, H, P))
    dt = jax.nn.softplus(_rand((B, T, H)))
    A = jnp.linspace(0.5, 4.0, H)
    D = _rand((H,))
    Bm, Cm = _rand((B, T, N)), _rand((B, T, N))
    assert _rel_err(ssd(x, dt, A, Bm, Cm, D, chunk=32),
                    ssd_chunked(x, dt, A, Bm, Cm, D, chunk=32)) < 1e-5


# ---------------------------------------------------------------------------
# gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,d,M,rpb", [
    (64, 128, 32, 8),
    (128, 256, 64, 16),
    (32, 128, 8, 8),
])
def test_gather_rows(N, d, M, rpb):
    src = _rand((N, d))
    idx = jnp.asarray(rng.integers(0, N, M), jnp.int32)
    out = gather_rows(src, idx, rows_per_block=rpb)
    assert _rel_err(out, ref.gather_rows_ref(src, idx)) == 0.0


def test_gather_blocks():
    src = _rand((64, 128))
    bidx = jnp.asarray(rng.integers(0, 8, 6), jnp.int32)
    out = gather_blocks(src, bidx, block_rows=8)
    expected = jnp.concatenate([src[int(i) * 8:(int(i) + 1) * 8]
                                for i in bidx], axis=0)
    assert _rel_err(out, expected) == 0.0
