"""The traffic generator: every seed sends one schedule (lengths and
arrivals), with its own prompt tokens; each block holds one set of
sizes; distributions where stated."""

import numpy as np
import pytest

from bench import spec, traffic

SEED = 2**31 + 4321          # more than 32 signed bits


@pytest.mark.parametrize("name", ["conv-overrun", "code-open"])
def test_same_seed_same_plan(name):
    t = spec.load_json(spec.BENCH / "traffic" / f"{name}.json")
    a = traffic.make_plan(t, 200064, SEED, n=256)
    b = traffic.make_plan(t, 200064, SEED, n=256)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.output_len, b.output_len)
    assert np.array_equal(a.prompt(7), b.prompt(7))
    if a.offsets is not None:
        assert np.array_equal(a.offsets, b.offsets)
    # another seed: the same schedule, other prompt tokens
    c = traffic.make_plan(t, 200064, SEED + 1, n=256)
    assert np.array_equal(a.prompt_len, c.prompt_len)
    assert np.array_equal(a.output_len, c.output_len)
    if a.offsets is not None:
        assert np.array_equal(a.offsets, c.offsets)
    assert not np.array_equal(a.prompt(7), c.prompt(7))


@pytest.mark.parametrize("name", ["conv-overrun", "code-open"])
def test_blocks_hold_one_set_of_sizes(name):
    t = spec.load_json(spec.BENCH / "traffic" / f"{name}.json")
    k = int(t["strata"])
    a = traffic.make_plan(t, 1000, 1, n=4 * k)
    for blk in range(1, 4):
        sl, first = slice(blk * k, (blk + 1) * k), slice(0, k)
        assert sorted(a.prompt_len[sl]) == sorted(a.prompt_len[first])
        assert sorted(a.output_len[sl]) == sorted(a.output_len[first])
        if a.offsets is not None:
            g = np.diff(np.concatenate([[0.0], a.offsets]))
            assert np.allclose(sorted(g[sl]), sorted(g[first]))
            assert g[sl].mean() == pytest.approx(1.0 / t["rate"])
    other = traffic.make_plan(dict(t, schedule_seed=1), 1000, 1, n=4 * k)
    assert not np.array_equal(a.prompt_len, other.prompt_len)


@pytest.mark.parametrize("name", ["conv-overrun", "code-open"])
def test_medians_are_the_cited_ones(name):
    """Each lognormal length keeps the median its file cites."""
    t = spec.load_json(spec.BENCH / "traffic" / f"{name}.json")
    p = traffic.make_plan(t, 1000, 1, n=int(t["strata"]) * 8)
    for key, lens in (("prompt", p.prompt_len), ("output", p.output_len)):
        if t[key]["dist"] == "lognormal":
            assert str(t[key]["median"]) in t["about"]
            assert np.median(lens) == pytest.approx(t[key]["median"],
                                                    rel=0.02, abs=1)


def test_distributions_and_bounds():
    q = (np.arange(16) + 0.5) / 16
    uni = traffic.inverse_cdf({"dist": "uniform", "lo": 512, "hi": 1024}, q)
    assert uni.min() >= 512 and uni.max() <= 1024
    assert np.median(uni) == pytest.approx(768, abs=20)
    ln = traffic.inverse_cdf({"dist": "lognormal", "median": 1024,
                              "sigma": 0.8, "lo": 128, "hi": 3072}, q)
    assert ln.min() >= 128 and ln.max() <= 3072
    assert np.median(ln) == pytest.approx(1024, rel=0.1)
    g = traffic.inverse_cdf({"dist": "gamma", "cv": 2.0, "rate": 4.0},
                            np.array([0.5]))
    assert 0 < g[0] < 0.25       # shape 1/4: the median gap is short


def test_open_loop_rate_and_burstiness():
    t = {"loop": "open", "rate": 2.0, "arrivals": {"dist": "gamma", "cv": 2},
         "prompt": {"dist": "uniform", "lo": 1, "hi": 2},
         "output": {"dist": "uniform", "lo": 1, "hi": 2}, "strata": 16}
    p = traffic.make_plan(t, 100, 5, n=1600)
    gaps = np.diff(np.concatenate([[0.0], p.offsets]))
    assert len(p) / p.offsets[-1] == pytest.approx(2.0, rel=1e-9)
    assert gaps.std() / gaps.mean() > 1.3
