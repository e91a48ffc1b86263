"""AMU runtime semantics + property tests (hypothesis).

System invariants under test:
  * every issued request id is returned by getfin/wait EXACTLY once,
  * getfin never blocks and never returns an unfinished id,
  * outstanding never exceeds max_outstanding in flight,
  * QoS ordering: LATENCY issues before BULK when both are queued,
  * FAIL policy rejects (returns FAILURE_CODE) instead of blocking,
  * pattern granule decomposition covers the region exactly once.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.amu import (AMU, AccessConfig, AMUError, DeviceTransferBackend,
                            FAILURE_CODE, QoS, QueueFullPolicy, RequestState,
                            SimBackend)
from repro.core.offload import FarMemoryTier, StreamingPrefetcher
from repro.core.patterns import (GatherPattern, StreamPattern, StridePattern,
                                 coalescing_ratio, granules)


def _amu(max_outstanding=4, **kw):
    return AMU(backend=SimBackend(base_latency=1e-6, bandwidth=10e9),
               max_outstanding=max_outstanding, **kw)


def test_getfin_nonblocking_and_exactly_once():
    amu = _amu()
    rids = [amu.aload(nbytes=64, src=np.zeros(16, np.float32))
            for _ in range(3)]
    assert all(r >= 0 for r in rids)
    amu.backend.advance(1.0)
    seen = set()
    while True:
        r = amu.getfin()
        if r == FAILURE_CODE:
            break
        assert r not in seen
        seen.add(r)
    assert seen == set(rids)
    assert amu.getfin() == FAILURE_CODE     # drained: still non-blocking


def test_wait_specific_and_double_consume_rejected():
    amu = _amu()
    r0 = amu.aload(nbytes=64, src=np.zeros(16, np.float32))
    req = amu.wait(r0)
    assert req.state is RequestState.CONSUMED
    with pytest.raises(AMUError):
        amu.wait(r0)


def test_fail_policy_rejects_when_full():
    amu = _amu(max_outstanding=2, full_policy=QueueFullPolicy.FAIL)
    src = np.zeros(16, np.float32)
    assert amu.aload(src) >= 0
    assert amu.aload(src) >= 0
    assert amu.aload(src) == FAILURE_CODE
    assert amu.stats["rejected"] == 1


def test_qos_ordering():
    amu = _amu(max_outstanding=1)
    src = np.zeros(1024, np.float32)
    bulk = amu.astore(src, config=AccessConfig(qos=QoS.BULK))
    lat = amu.aload(src, config=AccessConfig(qos=QoS.LATENCY))
    # one slot: bulk went in flight first; among queued, LATENCY preempts
    std = amu.aload(src, config=AccessConfig(qos=QoS.STANDARD))
    amu.backend.advance(10.0)
    order = amu.drain()
    assert order.index(lat) < order.index(std)


def test_stats_and_latency_accounting():
    amu = _amu()
    r = amu.aload(nbytes=1 << 20, src=np.zeros(4, np.float32))
    amu.backend.advance(1.0)
    amu.wait(r)
    req = amu.request(r)
    assert req.latency > 0
    assert amu.stats["aload"] == 1 and amu.stats["completed"] == 1


@settings(max_examples=50, deadline=None)
@given(
    n_requests=st.integers(1, 40),
    max_outstanding=st.integers(1, 8),
    sizes=st.lists(st.integers(1, 1 << 16), min_size=1, max_size=40),
)
def test_property_all_complete_exactly_once(n_requests, max_outstanding,
                                            sizes):
    amu = _amu(max_outstanding=max_outstanding)
    rids = []
    for i in range(n_requests):
        nbytes = sizes[i % len(sizes)]
        rids.append(amu.aload(nbytes=nbytes, src=np.zeros(1, np.uint8)))
    amu.backend.advance(1e6)
    done = amu.drain()
    assert sorted(done) == sorted(rids)
    assert amu.outstanding == 0


@settings(max_examples=50, deadline=None)
@given(total=st.integers(1, 1 << 20), gran=st.integers(1, 1 << 16))
def test_property_stream_granules_cover_exactly(total, gran):
    pat = StreamPattern(total_bytes=total)
    ranges = list(pat.granule_ranges(gran))
    assert sum(n for _, n in ranges) == total
    # contiguous, non-overlapping
    pos = 0
    for off, n in ranges:
        assert off == pos and n > 0
        pos += n


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=200),
       st.integers(8, 4096))
def test_property_gather_coalescing_never_loses_elements(indices, gran):
    elem = 8
    pat = GatherPattern(total_bytes=len(indices) * elem,
                        indices=tuple(indices), elem_bytes=elem)
    ranges = list(pat.granule_ranges(gran))
    assert sum(n for _, n in ranges) == len(indices) * elem
    assert coalescing_ratio(indices, elem, gran) >= 1.0


def test_stride_pattern():
    pat = StridePattern(total_bytes=4 * 64, block_bytes=64, stride_bytes=256,
                        count=4)
    ranges = list(pat.granule_ranges(32))
    assert len(ranges) == 8
    assert ranges[0] == (0, 32) and ranges[2] == (256, 32)


def test_far_tier_prefetch_overlap():
    amu = _amu(max_outstanding=8)
    tier = FarMemoryTier(amu)
    for i in range(6):
        tier.offload(f"w{i}", np.full(256, float(i), np.float32))
    pf = StreamingPrefetcher(tier, [f"w{i}" for i in range(6)], depth=3)
    pf.start()
    amu.backend.advance(1e3)
    vals = [pf.step()[0] for _ in range(6)]
    assert vals == [float(i) for i in range(6)]
    assert pf.fetch_overlap_events == 3   # depth kept full while consuming


def test_device_backend_memory_kind_is_honoured_or_refused():
    """A transfer lands in the memory kind asked for; a kind the device
    lacks raises instead of quietly landing somewhere else."""
    backend = DeviceTransferBackend()
    assert backend._sharding("pinned_host").memory_kind == "pinned_host"
    with pytest.raises(ValueError, match="no memory kind"):
        backend._sharding("no_such_memory")
