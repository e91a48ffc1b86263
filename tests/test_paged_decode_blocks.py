"""Blocked paged decode/verify attention (``kernels.decode_attention``).

The decode (S = 1) and verify (S = K + 1) kernels gather P frames a
block through the page table and visit only a row's live blocks.  These
tests run the kernel body in interpret mode against the XLA path
(``kernels.ops``, ``impl="xla"``) at shapes whose page size makes the
block rule pick a P that splits ``pages_per_seq`` into several blocks
with a ragged last one, at the lengths where blocks begin and end, with
a shuffled page table whose dead entries name a trash frame, for bf16
and int8 pools whose frames lie token-major or head-major.  Rows of length 0 must come back as zeros (the XLA path
returns the uniform value average there, so it is no reference for
them).
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.kv_quant import KVQuantConfig, quantize

da = importlib.import_module("repro.kernels.decode_attention")

# (page, pages_per_seq, Hkv, G): the block rule's P (512 tokens a block)
# is 16, 8 and 4 frames, so 19, 20 and 11 pages end in a ragged block.
# Frames are token-major in both pools at 4 KV heads, head-major in the
# int8 pool at 2 and in both at 1 (``heads_major``).
LAYOUTS = {"page32": (32, 19, 4, 2), "page64": (64, 20, 2, 3),
           "page128": (128, 11, 1, 4)}
D = 16


def _block(page, pps, Hkv, S, G):
    return da.block_pages(page, pps, Hkv, D, 4, Hkv * S * G, False)


def _case(layout, S, pool, seed=0):
    page, pps, Hkv, G = LAYOUTS[layout]
    H = Hkv * G
    P = _block(page, pps, Hkv, S, G)
    assert 1 < P < pps and pps % P, "layout must span a ragged last block"
    bt, full = P * page, pps * page
    # lengths: one token, exactly one block, one past it, the whole row;
    # verify rows see s more tokens than row 0, capped at the row
    base = np.array([1, bt, bt + 1, full - S + 1, 0], np.int32)
    B = base.size
    lengths = np.minimum(base[:, None] + np.arange(S)[None, :], full)
    lengths[base == 0] = 0
    rng = np.random.default_rng(seed)
    N = B * pps + 1
    trash = N - 1
    pt = np.full((B, pps), trash, np.int32)
    frames = rng.permutation(N - 1)
    for b in range(B):
        live = -(-int(lengths[b].max()) // page)
        pt[b, :live] = frames[b * pps:b * pps + live]
    kf = rng.standard_normal((N, page, Hkv, D)).astype(np.float32)
    vf = rng.standard_normal((N, page, Hkv, D)).astype(np.float32)
    # a trash frame the kernel must never fold in: far larger than live KV
    kf[trash] = 300.0
    vf[trash] = 1e4
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    kw = {}
    if pool == "int8":
        qcfg = KVQuantConfig("int8")
        ks = jnp.max(jnp.abs(jnp.asarray(kf)), axis=(1, 3)) / qcfg.qmax
        vs = jnp.max(jnp.abs(jnp.asarray(vf)), axis=(1, 3)) / qcfg.qmax
        kp = quantize(jnp.asarray(kf), ks[:, None, :, None], qcfg)
        vp = quantize(jnp.asarray(vf), vs[:, None, :, None], qcfg)
        kw = {"k_scales": ks, "v_scales": vs}
    else:
        kp = jnp.asarray(kf, jnp.bfloat16)
        vp = jnp.asarray(vf, jnp.bfloat16)
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths), base, kw


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify"])
def test_blocked_kernel_matches_xla(S, layout, pool):
    q, kp, vp, pt, lengths, base, kw = _case(layout, S, pool)
    if S == 1:
        args = (q[:, 0], kp, vp, pt, lengths[:, 0])
        fn = ops.paged_decode_attention
    else:
        args = (q, kp, vp, pt, lengths)
        fn = ops.paged_verify_attention
    got = np.asarray(fn(*args, impl="interpret", **kw)).reshape(q.shape)
    want = np.asarray(fn(*args, impl="xla", **kw)).reshape(q.shape)
    live = base > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(got[~live], 0.0)


@pytest.mark.parametrize("page,pps,S,quant,want", [
    (16, 256, 1, False, 32),    # phi4-mini decode: 512-token blocks
    (16, 250, 1, False, 32),    # max_len 4000: ragged last block
    (16, 256, 4, False, 16),    # verify K = 3: past the VMEM budget at 32
    (16, 256, 16, False, 8),    # verify K = 15: past it at 16
    (16, 256, 1, True, 32),     # int8 pool
    (16, 8, 1, False, 8),       # a short table is one block
])
def test_block_rule(page, pps, S, quant, want):
    """P is the largest that keeps a block within 512 tokens, the row's
    table and the VMEM budget, at phi4-mini's serving widths (8 KV heads
    of 128, G = 3)."""
    itemsize = 1 if quant else 2
    P = da.block_pages(page, pps, 8, 128, itemsize, 8 * S * 3, quant)
    assert P == want


@pytest.mark.parametrize("Hkv,itemsize,want", [
    (8, 2, False), (2, 2, False), (1, 2, True), (6, 2, True),
    (8, 1, False), (4, 1, False), (2, 1, True), (1, 1, True),
    (3, 1, True), (12, 1, True), (16, 1, False), (24, 1, False),
    (1, 4, False), (3, 4, True)])
def test_heads_major_rule(Hkv, itemsize, want):
    """Frames lie head-major where Hkv is no whole row tile of the pool
    dtype (as XLA lays out a TPU pool; ``test_tpu_compile`` checks that
    the kernel then reads the pool with no copy)."""
    assert da.heads_major(Hkv, itemsize) is want
