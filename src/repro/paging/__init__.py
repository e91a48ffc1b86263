"""repro.paging — page-granularity far-memory KV subsystem.

Turns the repo's serving layer from whole-sequence KV offload into a
capacity-oversubscribed paging system, built from three pieces that map
one-to-one onto the source paper's architecture:

  * :mod:`repro.paging.page_table` — the pool of device page frames
    (near tier / SPM) and per-sequence logical→physical maps with
    residency bits (APR-style per-page state),
  * :mod:`repro.paging.pager` — the AMU traffic engine: LATENCY-QoS
    ``aload`` prefetch, BULK-QoS ``astore`` writeback, LRU-with-pinning
    eviction, and per-QoS outstanding windows (MACR QoS at issue),
  * :mod:`repro.paging.events` — the §2.3.2 event-driven model as a
    scheduler: decode ticks, ``getfin`` page arrivals, and free-page-
    watermark admission/preemption decisions,
  * :mod:`repro.paging.prefix_cache` — content-addressed cross-request
    prefix sharing: full prompt pages interned by rolling token-id
    hash, mapped into new requests' page tables as refcounted/COW
    shared frames (device hit) or LATENCY far-tier fetches (far hit),
  * :mod:`repro.paging.sim` — deterministic policy simulations on a
    virtual clock (pager vs blocking fetch, chunked continuous batching
    vs serial dense prefill, SLO-aware vs watermark scheduling), used
    as policy tests.

The serving engine (:mod:`repro.serve.engine`) consumes all of it: both
decode *and* chunked prefill compute directly on the pool layout, so
the page is the unit of transfer, residency, eviction and compute —
see ``docs/ARCHITECTURE.md`` for the paper-to-code map.
"""

from repro.paging.events import (DeadlineQueue, Event, EventKind, EventLoop,
                                 WatermarkPolicy)
from repro.paging.page_table import (NOT_MAPPED, Frame, PagePool, PageState,
                                     PageTable, PagingError, pages_for)
from repro.paging.pager import Pager, QoSWindows
from repro.paging.prefix_cache import PREFIX_SEQ, PrefixCache, page_hashes

__all__ = [
    "DeadlineQueue", "Event", "EventKind", "EventLoop", "WatermarkPolicy",
    "NOT_MAPPED", "Frame", "PagePool", "PageState", "PageTable",
    "PagingError", "pages_for", "Pager", "QoSWindows",
    "PREFIX_SEQ", "PrefixCache", "page_hashes",
]
