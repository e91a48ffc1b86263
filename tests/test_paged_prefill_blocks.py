"""Blocked chunked-prefill attention (``kernels.flash_attention``).

The prefill kernel gathers P frames a block through the page table and
visits, for each query block, only the blocks its queries can reach.
These tests run the kernel body in interpret mode against the XLA path
(``kernels.ops``, ``impl="xla"``) for bf16 and int8 pools whose frames
lie token-major or head-major, with one and several query heads per KV
head, with and without a sliding window.  Each call holds chunk rows
that cross a block boundary, reach a ragged last block, end short of
the chunk, reach different block counts from their two query blocks,
or hold no token at all; the table's dead entries name a trash frame
far larger than live KV, which must never be folded in.  Positions past
a row's length are don't-care, except that a query block holding none
of the row's tokens (all of a length-0 row) comes back as zeros.
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.kv_quant import KVQuantConfig, quantize

fa = importlib.import_module("repro.kernels.flash_attention")

# (page, pages_per_seq, Hkv): the block rule's P (512 tokens a block) is
# 16, 8 and 4 frames, so 19, 20 and 11 pages end in a ragged block.
# Frames are token-major in both pools at 4 KV heads, head-major in both
# at 1 and in the int8 pool at 2 (``heads_major``).
LAYOUTS = {"page32-4kv": (32, 19, 4), "page64-1kv": (64, 20, 1),
           "page128-2kv": (128, 11, 2)}
# windows: one within a query block; one past a block, where a block
# below the causal diagonal still crosses the window's edge
D, T, WINDOWS = 16, 256, (48, 560)


def _rule(page, pps, Hkv, G, pool):
    return fa.prefill_blocks(T, page, pps, Hkv, G, D,
                             1 if pool == "int8" else 2, pool == "int8")


def _case(layout, G, pool, seed=0):
    page, pps, Hkv = LAYOUTS[layout]
    bq, P = _rule(page, pps, Hkv, G, pool)
    bt, full = P * page, pps * page
    assert bq < T and 1 < P < pps and pps % P, \
        "layout must span two query blocks and a ragged last block"
    # rows: crossing a block boundary, its two query blocks reaching one
    # and two blocks; short of the chunk (its second query block holds
    # none of its tokens); reaching the ragged last block (under a
    # window from a first block past 0: both windows at page 128, the
    # short one at 64); empty
    offset = np.array([bt - bq - 72, 40, full - T, 100], np.int32)
    lengths = np.array([T, bq - 28, T, 0], np.int32)
    C = offset.size
    rng = np.random.default_rng(seed)
    N = C * pps + 1
    trash = N - 1
    pt = np.full((C, pps), trash, np.int32)
    frames = rng.permutation(N - 1)
    for c in range(C):
        live = -(-int(offset[c] + lengths[c]) // page) if lengths[c] else 0
        pt[c, :live] = frames[c * pps:c * pps + live]
    kf = rng.standard_normal((N, page, Hkv, D)).astype(np.float32)
    vf = rng.standard_normal((N, page, Hkv, D)).astype(np.float32)
    # a trash frame the kernel must never fold in: far larger than live KV
    kf[trash] = 300.0
    vf[trash] = 1e4
    q = jnp.asarray(rng.standard_normal((C, T, Hkv * G, D)), jnp.float32)
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
    return (q, kp, vp, jnp.asarray(pt), jnp.asarray(offset),
            jnp.asarray(lengths), kw, bq)


@pytest.mark.parametrize("window", (0,) + WINDOWS,
                         ids=["causal", "swa-short", "swa-long"])
@pytest.mark.parametrize("G", [1, 3])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_blocked_prefill_matches_xla(layout, pool, G, window):
    q, kp, vp, pt, offset, lengths, kw, bq = _case(layout, G, pool)
    args = (q, kp, vp, pt, offset, lengths)
    got = np.asarray(ops.paged_prefill_attention(
        *args, window=window, impl="interpret", **kw))
    want = np.asarray(ops.paged_prefill_attention(
        *args, window=window, impl="xla", **kw))
    pos = np.arange(T)[None, :]
    valid = pos < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(got[valid], want[valid], rtol=2e-5, atol=2e-5)
    # query blocks that hold none of a row's tokens write zeros
    dead = pos >= -(-np.asarray(lengths)[:, None] // bq) * bq
    assert dead.any()
    np.testing.assert_array_equal(got[dead], 0.0)


@pytest.mark.parametrize("Hkv,G,itemsize,want", [
    (8, 3, 2, (128, 32)),       # phi4-mini on one chip, bf16 pool
    (8, 3, 1, (128, 32)),       # its int8 pool
    (2, 4, 2, (128, 32)),       # mistral-nemo per chip of four, bf16
    (1, 4, 2, (128, 32)),       # a head-major narrow pool
])
def test_prefill_block_rule(Hkv, G, itemsize, want):
    """(bq, P) at the serving shapes (chunk 512, page 16, head_dim 128,
    256 pages a row): the largest blocks within ``_Q_BLOCK`` query tokens,
    512 KV tokens and the VMEM budget."""
    got = fa.prefill_blocks(512, 16, 256, Hkv, G, 128, itemsize,
                            itemsize == 1)
    assert got == want


def test_prefill_block_rule_fits_budget():
    """A wide head group and a long chunk shrink the blocks to the
    budget: P first, then bq."""
    bq, P = fa.prefill_blocks(2048, 16, 256, 16, 8, 256, 2, False)
    assert (bq, P) != (128, 32)
    assert bq * P >= 8
