"""The transfer role component: every byte the engine moves.

:class:`TransferMixin` owns the park/resume machinery and the
device-pool plumbing — frame reads/lands, pool-frame scatters,
room-making (evict → drain → preempt), page shedding with the
clean-park fast path, resume prefetch + slot re-entry, and the
finished-sequence offload/fetch pair.  It is role-agnostic by
construction: a FUSED engine points park at its own resume; a PREFILL
engine's graduation is the same ``_offload_finished`` park plus a
:meth:`_publish_handoff`; a DECODE engine's handoff admission is the
same resume machinery fed by :meth:`~repro.paging.Pager.fetch_keys`.
The mixin assumes the host class provides the engine state surface
(``page_pool``/``page_table``/``pager``/``cache``/``sched``/…) —
``serve/engine.py`` assembles it.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.kv_quant import KVQuantConfig, quantize
from repro.models.model import Cache
from repro.paging import (NOT_MAPPED, EventKind, PageState, PagingError,
                          pages_for)
from repro.serve.config import EngineRole
from repro.serve.disagg import HandoffRecord
from repro.serve.kv_cache import extract_aux_slot, insert_aux_slot, \
    join_kv_pages
from repro.serve.request import Request

__all__ = ["TransferMixin", "_scatter_seq_pages", "_scatter_seq_pages_q",
           "_scatter_one_page", "_scatter_one_page_q", "_copy_frame",
           "_copy_frame_q"]


# -- jitted pool-frame scatters (module level: one compile per shape) ---------

@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(5,))
def _scatter_seq_pages(k_pages, v_pages, k_single, v_single, frames,
                       n_pg: int):
    """Write one sequence's dense prefill KV into its pool frames.

    ``k_single``/``v_single``: (L, 1, S, Hkv, D) from prefill — S is the
    prefill *bucket*, at most the slot capacity; only the leading
    ``n_pg`` pages (the prompt's — the exact frames admission just
    mapped) are scattered, the tail zero-padded up to a page multiple.
    The pool arrays are donated: the update aliases in place instead of
    copying the whole pool per admission."""
    L, _, S, Hkv, D = k_single.shape
    page = k_pages.shape[2]
    take = min(n_pg * page, S)
    k_single = k_single[:, :, :take]
    v_single = v_single[:, :, :take]
    pad = n_pg * page - take
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
        k_single = jnp.pad(k_single, widths)
        v_single = jnp.pad(v_single, widths)
    ks = k_single[:, 0].reshape(L, n_pg, page, Hkv, D)
    vs = v_single[:, 0].reshape(L, n_pg, page, Hkv, D)
    k_pages = k_pages.at[:, frames].set(ks.astype(k_pages.dtype))
    v_pages = v_pages.at[:, frames].set(vs.astype(v_pages.dtype))
    return k_pages, v_pages


@partial(jax.jit, donate_argnums=(0, 1, 2, 3), static_argnums=(7, 8))
def _scatter_seq_pages_q(k_pages, v_pages, k_scales, v_scales,
                         k_single, v_single, frames, n_pg: int, mode: str):
    """Quantized-pool variant of :func:`_scatter_seq_pages`: the dense
    prefill KV is quantized on the way into its pool frames, one
    per-(layer, head) absmax scale per frame.  Admission writes whole
    frames, so the scale is exact for the written content (a later
    decode append into the last partial page grows it monotonically via
    the in-step scatter's requant discipline)."""
    qcfg = KVQuantConfig(mode)
    L, _, S, Hkv, D = k_single.shape
    page = k_pages.shape[2]
    take = min(n_pg * page, S)
    k_single = k_single[:, :, :take]
    v_single = v_single[:, :, :take]
    pad = n_pg * page - take
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
        k_single = jnp.pad(k_single, widths)
        v_single = jnp.pad(v_single, widths)
    ks = k_single[:, 0].reshape(L, n_pg, page, Hkv, D).astype(jnp.float32)
    vs = v_single[:, 0].reshape(L, n_pg, page, Hkv, D).astype(jnp.float32)
    k_s = jnp.max(jnp.abs(ks), axis=(2, 4)) / qcfg.qmax      # (L, n_pg, Hkv)
    v_s = jnp.max(jnp.abs(vs), axis=(2, 4)) / qcfg.qmax
    k_safe = jnp.where(k_s > 0, k_s, 1.0)[:, :, None, :, None]
    v_safe = jnp.where(v_s > 0, v_s, 1.0)[:, :, None, :, None]
    k_pages = k_pages.at[:, frames].set(quantize(ks, k_safe, qcfg))
    v_pages = v_pages.at[:, frames].set(quantize(vs, v_safe, qcfg))
    k_scales = k_scales.at[:, frames].set(k_s)
    v_scales = v_scales.at[:, frames].set(v_s)
    return k_pages, v_pages, k_scales, v_scales


@partial(jax.jit, donate_argnums=(0, 1))
def _scatter_one_page(k_pages, v_pages, k_data, v_data, phys):
    """Land one far-tier page payload (L, page, Hkv, D) in frame ``phys``
    (pool arrays donated: an in-place page write, not a pool copy)."""
    k_pages = k_pages.at[:, phys].set(k_data.astype(k_pages.dtype))
    v_pages = v_pages.at[:, phys].set(v_data.astype(v_pages.dtype))
    return k_pages, v_pages


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_one_page_q(k_pages, v_pages, k_scales, v_scales,
                        k_data, v_data, ks_data, vs_data, phys):
    """Quantized-pool variant of :func:`_scatter_one_page`: the far-tier
    payload already holds pool-dtype bytes plus the frame's (L, Hkv)
    scale pair — landing is a byte copy, never a requantization."""
    k_pages = k_pages.at[:, phys].set(k_data.astype(k_pages.dtype))
    v_pages = v_pages.at[:, phys].set(v_data.astype(v_pages.dtype))
    k_scales = k_scales.at[:, phys].set(ks_data.astype(jnp.float32))
    v_scales = v_scales.at[:, phys].set(vs_data.astype(jnp.float32))
    return k_pages, v_pages, k_scales, v_scales


@partial(jax.jit, donate_argnums=(0, 1))
def _copy_frame(k_pages, v_pages, src, dst):
    """Device-side page copy (COW break: a sharer about to write a
    prefix-shared frame gets a private duplicate first)."""
    k_pages = k_pages.at[:, dst].set(k_pages[:, src])
    v_pages = v_pages.at[:, dst].set(v_pages[:, src])
    return k_pages, v_pages


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _copy_frame_q(k_pages, v_pages, k_scales, v_scales, src, dst):
    """Quantized-pool COW break: the duplicate keeps the source frame's
    scales, so the copy is byte-exact."""
    k_pages = k_pages.at[:, dst].set(k_pages[:, src])
    v_pages = v_pages.at[:, dst].set(v_pages[:, src])
    k_scales = k_scales.at[:, dst].set(k_scales[:, src])
    v_scales = v_scales.at[:, dst].set(v_scales[:, src])
    return k_pages, v_pages, k_scales, v_scales


class TransferMixin:
    """Park/resume transfer machinery + device-pool plumbing (see the
    module docstring).  Mixed into :class:`~repro.serve.engine.Engine`."""

    # -- paged device-pool plumbing -------------------------------------------
    def _read_frame(self, phys: int) -> Dict[str, np.ndarray]:
        """Pull one frame's content (L, page, Hkv, D) off the device —
        the page-granularity transfer unit the pager's astores move."""
        with self.tracer.span("transfer", "read_frame", phys=phys,
                              nbytes=self.pager.page_nbytes):
            kv = self.cache.kv
            out = {"k": np.asarray(kv["k_pages"][:, phys]),
                   "v": np.asarray(kv["v_pages"][:, phys])}
            if "k_scales" in kv:
                # quantized pool: the (L, Hkv) scale pair rides every page
                # payload — parked pages, far-tier entries, prefix homes and
                # disagg handoffs all move quantized bytes + scales
                out["k_scale"] = np.asarray(kv["k_scales"][:, phys])
                out["v_scale"] = np.asarray(kv["v_scales"][:, phys])
            return out

    def _land_frame(self, phys: int) -> None:
        """If the pool frame holds a far-tier payload that has not been
        scattered into the device pool yet, land it now."""
        frame = self.page_pool.frames[phys]
        if frame.data is None:
            return                       # content already lives in the pool
        with self.tracer.span("transfer", "land_frame", phys=phys,
                              nbytes=self.pager.page_nbytes):
            kv = self.cache.kv
            data = frame.data
            if "k_scales" in kv:
                kp, vp, ks, vs = _scatter_one_page_q(
                    kv["k_pages"], kv["v_pages"],
                    kv["k_scales"], kv["v_scales"],
                    jnp.asarray(data["k"]), jnp.asarray(data["v"]),
                    jnp.asarray(data["k_scale"]),
                    jnp.asarray(data["v_scale"]),
                    jnp.asarray(phys, jnp.int32))
                self.cache = self.cache._replace(kv=dict(
                    kv, k_pages=kp, v_pages=vp, k_scales=ks, v_scales=vs))
            else:
                kp, vp = _scatter_one_page(
                    kv["k_pages"], kv["v_pages"],
                    jnp.asarray(data["k"]), jnp.asarray(data["v"]),
                    jnp.asarray(phys, jnp.int32))
                self.cache = self.cache._replace(
                    kv=dict(kv, k_pages=kp, v_pages=vp))
        frame.data = None

    # -- paging helpers -------------------------------------------------------
    def _make_room(self, need: int, protect: frozenset,
                   preempt: bool = True) -> bool:
        """Bring the pool to at least ``need`` free frames.  Escalation
        order: getfin poll, LRU eviction of unpinned cached pages,
        draining in-flight fetches (their frames become evictable), then
        — for growth, never for fresh admission — preempting a victim."""
        pool = self.page_pool
        if pool.n_free >= need:
            return True
        self.pager.poll()
        while pool.n_free < need:
            if self.pager.evict_lru(need - pool.n_free):
                continue
            if self._resuming:
                for req in list(self._resuming.values()):
                    self.pager.wait_arriving(req.rid)
                if self.pager.evict_lru(need - pool.n_free):
                    continue
            if not preempt or not self._preempt_one(protect):
                return False
        return True

    def _preempt_one(self, protect: frozenset) -> bool:
        """Park the scheduler's chosen victim — a running sequence
        (:meth:`_park`) or a half-prefilled one whose completed chunks
        are parked as-is (:meth:`_park_prefilling`).  The watermark
        policy picks the most recently admitted; the SLO policy picks
        the slot whose SLO is already blown or furthest from its
        deadline, batch tier first."""
        victims = [r for r in list(self.active.values())
                   + list(self.prefilling.values()) if r.rid not in protect]
        if not victims or len(self.active) + len(self.prefilling) <= 1:
            return False
        victim = self.sched.pick_victim(victims, self.clock())
        with self.tracer.span("engine", "park", rid=victim.rid,
                              frames=self.page_table.n_pages(victim.rid)):
            if victim.mid_prefill:
                self._park_prefilling(victim)
            else:
                self._park(victim)
        return True

    def _shed_pages(self, req: Request, valid: int,
                    hot_pages: Optional[int] = None) -> None:
        """Shared parking machinery: keep the hot tail cached in the
        pool (unpinned, LRU-evictable), move cold pages to the far tier
        — BULK astore for dirty ones, for free when the far copy is
        still current (clean-eviction fast path, §2.3 QoS split).

        A far copy is *current* when its stored valid-token tag equals
        the page's live token count (append-only KV never rewrites a
        position, so equal coverage means equal content) — this is what
        lets previously-parked pages, prefix-shared pages and re-fetched
        pages all park for free, while a page that grew since its last
        writeback pays a fresh astore.  SWA rings rewrite pages in place
        on wrap, so they always write back.  Shared frames are released,
        not freed: the prefix cache (or another sharer) keeps them.
        """
        rid = req.rid
        n_pages = pages_for(valid, self.page_size)
        # a frame allocated for the *next* write (pos on a page boundary)
        # holds no content yet — release it; resume growth re-allocates
        self.page_table.truncate(rid, n_pages)
        n_hot = min(self.hot_tail_pages if hot_pages is None else hot_pages,
                    n_pages)
        n_cold = n_pages - n_hot
        for logical in range(n_pages - 1, -1, -1):   # tail first: hot
            pte = self.page_table.entry(rid, logical)
            if pte.state is PageState.PARKED:
                continue                 # already far (and current, by
            self.page_table.unpin_page(rid, logical)  # the park invariant)
            cur = min(self.page_size, valid - logical * self.page_size)
            clean = (self.cfg.attention != "swa"
                     and self.pager.far_tokens(rid, logical) == cur)
            if logical >= n_cold:                    # hot tail: stays pooled
                frame = self.page_pool.frames[pte.phys]
                frame.data = None                    # content is in the pool
                frame.dirty = not clean
                frame.tokens = cur   # LRU eviction keeps the freshness tag
                self.page_pool.touch(pte.phys)
            elif clean:
                self.pager.park_clean(rid, logical)  # far copy current
            else:
                self.pager.writeback(rid, logical,
                                     self._read_frame(pte.phys), tokens=cur,
                                     qos=self.sched.store_qos(req))

    def _park(self, req: Request) -> None:
        """Preempt a running sequence: cold pages → far tier (BULK), hot
        tail stays cached *in the device pool* (unpinned, LRU-evictable),
        slot freed, request back to the head of the queue.  The KV never
        round-trips through a dense slot: cold pages are read
        frame-by-frame off the pool (the page-granularity astore
        payload), hot pages do not move at all."""
        slot = req.slot
        tokens = int(np.asarray(self.cache.pos)[slot])
        self._shed_pages(req, min(tokens, self.slot_tokens))
        req.residue = extract_aux_slot(self.cache, slot, self.max_batch)
        req.parked = True
        req.n_preempts += 1
        req.slot = None
        self._pt_np[slot] = self.trash_frame
        self._pt_dirty = True
        del self.active[slot]
        self.pool.release(slot)
        self.queue.insert(0, req)
        self.stats["preemptions"] += 1
        self._obs_phase(req, "parked")
        self.events.post(EventKind.PREEMPT, req.rid)

    def _park_prefilling(self, req: Request) -> None:
        """Cancel a half-prefilled sequence: its *completed* chunks park
        exactly like a running sequence's pages (hot tail pooled, cold
        written back), and the prompt remainder simply re-enters the
        chunk queue on resume — no prefill work is redone.  The non-KV
        carry (hybrid SSM state between chunks) already lives host-side
        in ``req.chunk_ssm``, so nothing dense is extracted."""
        slot = req.slot
        self._shed_pages(req, req.prefill_pos)
        req.parked = True
        req.n_preempts += 1
        req.slot = None
        req.chunk_rows = None            # rebuilt from the table on resume
        del self.prefilling[slot]
        self.pool.release(slot)
        self.queue.insert(0, req)
        self.stats["preemptions"] += 1
        self.stats["prefill_preempts"] += 1
        self._obs_phase(req, "parked")
        self.events.post(EventKind.PREEMPT, req.rid)

    def _start_resume(self, req: Request) -> bool:
        """Begin bringing a parked request back: prefetch of its parked
        pages (LATENCY QoS for interactive tier, the scheduler may
        demote batch resumes to STANDARD), hot tail first, overlapping
        decode.  A resume is a continuation, not a fresh admission, so
        like growth it is exempt from the low watermark — it only needs
        raw frames."""
        parked = self.page_table.logical_pages(req.rid, PageState.PARKED)
        if self.page_pool.n_free < len(parked) and \
                not self._make_room(len(parked), frozenset({req.rid}),
                                    preempt=False):
            return False
        self.pager.prefetch_seq(req.rid, tail_first=True,
                                qos=self.sched.fetch_qos(req))
        self._resuming[req.rid] = req
        self._obs_phase(req, "resuming")
        return True

    def _try_finish_resumes(self) -> None:
        """Slot in any resuming request whose pages have all arrived.
        Re-entry is a page-table patch: pin the frames, land any payload
        that is still host-side, point the slot's page-table row at the
        frames and restore the tiny aux state.  The KV itself is already
        where decode reads it.  A request parked *mid-prefill* re-enters
        the chunk queue instead of the decode batch: its device
        page-table row stays on the trash frame and its completed-chunk
        frames go back into ``chunk_rows`` for the next chunk to attend
        through."""
        for rid, req in list(self._resuming.items()):
            if not self.page_table.resident(rid):
                # pages evicted again under pressure mid-resume get a
                # fresh prefetch (no-op when all are in flight)
                self.pager.prefetch_seq(rid, tail_first=True,
                                        qos=self.sched.fetch_qos(req))
                continue
            if not self.pool.n_free:
                continue
            slot = self.pool.alloc()
            rows = np.full((self.pages_per_seq,), self.trash_frame, np.int32)
            for logical in range(self.page_table.n_pages(rid)):
                pte = self.page_table.entry(rid, logical)
                self.page_table.pin_page(rid, logical)
                self.page_pool.touch(pte.phys)
                self._land_frame(pte.phys)
                rows[logical] = pte.phys
            req.slot = slot
            req.parked = False
            # a request admitted straight onto far-tier prefix pages —
            # or handed off from a PREFILL-role engine — arrives here
            # having never run: that is an admission, not a resume
            # (preemption/resume stats must stay balanced)
            first_admit = req.admit_seq < 0
            req.admit_seq = next(self._admits)
            if first_admit:
                req.admit_t = self.clock()
            if req.mid_prefill:
                req.chunk_rows = rows
                if self.cfg.family == "encdec":
                    self._install_cross(req)     # cross rows left with the slot
                self.prefilling[slot] = req
            else:
                self._ensure_private_tail(req)
                rows = np.full((self.pages_per_seq,), self.trash_frame,
                               np.int32)
                for logical in range(self.page_table.n_pages(rid)):
                    rows[logical] = self.page_table.entry(rid, logical).phys
                self._pt_np[slot] = rows
                self._pt_dirty = True
                self.cache = insert_aux_slot(self.cache, req.residue,
                                             slot, self.max_batch)
                req.residue = None
                self.active[slot] = req
            del self._resuming[rid]
            self.stats["admitted" if first_admit else "resumes"] += 1
            self._obs_phase(req, "prefill" if req.mid_prefill else "decode")
            self.events.post(EventKind.ADMIT, rid)

    def _alloc_pinned(self, req: Request, n_tokens: int) -> None:
        """Allocate (pin + mark dirty) frames so ``req`` covers
        ``n_tokens`` positions and point its slot's page-table row at
        them — active slots own their pages.  While a request is still
        chunk-prefilling, its frames go into the host-side
        ``chunk_rows`` instead: the *device* row keeps pointing at the
        trash frame so the fused decode half of the mixed step cannot
        scribble on a half-written prompt."""
        mid = req.mid_prefill and req.chunk_rows is not None
        for logical in self.page_table.ensure_capacity(req.rid, n_tokens):
            pte = self.page_table.entry(req.rid, logical)
            self.page_table.pin_page(req.rid, logical)
            self.page_pool.mark_dirty(pte.phys)
            if mid:
                req.chunk_rows[logical] = pte.phys
            else:
                self._pt_np[req.slot, logical] = pte.phys
                self._pt_dirty = True

    def _ensure_private(self, req: Request, logical: int) -> None:
        """COW break: if the frame backing ``(req, logical)`` is a
        prefix-shared (copy-on-write) frame this step is about to write,
        remap the page onto a private duplicate first.  Unreachable on
        the supported sharing families by construction — only *full*
        prompt pages are shared and decode appends strictly after them —
        but the guard keeps the donated in-place pool scatters safe
        against any future schedule that routes a write at a shared
        frame."""
        pte = self.page_table.entry(req.rid, logical)
        if pte.phys == NOT_MAPPED:
            return
        frame = self.page_pool.frames[pte.phys]
        if not frame.cow or frame.refs <= 1:
            return
        old, new = self.page_table.remap_private(req.rid, logical)
        if new == old:
            return
        kv = self.cache.kv
        if "k_scales" in kv:
            kp, vp, ks, vs = _copy_frame_q(
                kv["k_pages"], kv["v_pages"],
                kv["k_scales"], kv["v_scales"],
                jnp.asarray(old, jnp.int32), jnp.asarray(new, jnp.int32))
            self.cache = self.cache._replace(kv=dict(
                kv, k_pages=kp, v_pages=vp, k_scales=ks, v_scales=vs))
        else:
            kp, vp = _copy_frame(kv["k_pages"], kv["v_pages"],
                                 jnp.asarray(old, jnp.int32),
                                 jnp.asarray(new, jnp.int32))
            self.cache = self.cache._replace(
                kv=dict(kv, k_pages=kp, v_pages=vp))
        if req.mid_prefill and req.chunk_rows is not None:
            req.chunk_rows[logical] = new
        elif req.slot is not None:
            self._pt_np[req.slot, logical] = new
            self._pt_dirty = True

    def _ensure_private_tail(self, req: Request) -> None:
        """Guard the page decode writes next (the sequence's last mapped
        page) against COW sharing before the slot goes active."""
        n = self.page_table.n_pages(req.rid)
        if n:
            self._ensure_private(req, n - 1)

    def _ensure_growth(self, drafts: Optional[Dict[int, int]] = None) -> None:
        """Before a decode step: every active sequence about to cross a
        page boundary gets a pinned frame, evicting/preempting under the
        watermark policy when the pool is short.

        ``drafts`` (rid -> drafted tokens) widens a speculating slot's
        write window from one position to ``1 + drafts[rid]`` — the
        verify step scatters K/V at ``[pos, pos + 1 + drafts[rid])``,
        possibly straddling a page boundary, so every touched mapped
        page gets the COW guard and enough frames are pinned up front.
        The speculative extra degrades instead of failing: when the
        pool cannot cover the full draft the entry is clamped in place
        (down to 0 = plain decode) and only the base ``pos + 1`` growth
        keeps the old must-succeed contract."""
        pos_np = np.asarray(self.cache.pos)     # one device sync per step
        for req in list(self.active.values()):
            if req.slot is None or req.slot not in self.active:
                continue                    # preempted by an earlier victim
            pos = int(pos_np[req.slot])
            if pos >= self.slot_tokens:
                continue                    # SWA ring wrapped: no growth
            extra = drafts.get(req.rid, 0) if drafts else 0
            if extra and pos + 1 + extra > self.slot_tokens:
                extra = max(0, self.slot_tokens - pos - 1)
            # COW-guard every mapped page the write range touches (the
            # draft tail can straddle into the next page)
            n_mapped = self.page_table.n_pages(req.rid)
            first_wp = pos // self.page_size
            last_wp = min((pos + extra) // self.page_size, n_mapped - 1)
            for wp in range(first_wp, last_wp + 1):
                self._ensure_private(req, wp)
            while True:
                target = pos + 1 + extra
                need = self.page_table.pages_needed(req.rid, target)
                if not need:
                    break
                if self._make_room(need, frozenset({req.rid})):
                    break
                if extra == 0:
                    raise PagingError(
                        f"cannot grow request {req.rid}: pool of "
                        f"{self.page_pool.n_pages} pages exhausted")
                extra -= 1              # shed draft positions, not the slot
            if drafts is not None and req.rid in drafts:
                drafts[req.rid] = extra
            if need:
                self._alloc_pinned(req, target)

    # -- finished-sequence offload + cross-engine handoff ---------------------
    def _offload_finished(self, req: Request) -> None:
        """Park a finished sequence page-by-page into THE far tier — the
        same BULK writeback / clean-park machinery preemption uses, no
        sequence-granularity side store.  The tiny aux residue (SSM
        state, cross KV, positions) and the page count ride along as one
        more far-tier entry; :meth:`fetch_finished` reassembles — or,
        under a PREFILL role, a DECODE-role engine's
        :meth:`~repro.serve.admission.AdmissionMixin.admit_handoff`."""
        slot = req.slot
        rid = req.rid
        tokens = min(int(np.asarray(self.cache.pos)[slot]), self.slot_tokens)
        aux = extract_aux_slot(self.cache, slot, self.max_batch)
        self.far_tier.offload(
            (rid, "aux"),
            {"aux": aux, "tokens": tokens,
             "pages": pages_for(tokens, self.page_size)})
        # every page goes far (hot_pages=0): the sequence is leaving the
        # device; shared prefix pages park for free via their aliases
        self._shed_pages(req, tokens, hot_pages=0)

    def _publish_handoff(self, req: Request) -> None:
        """PREFILL-role graduation, control-plane half: the data plane
        (pages + aux residue) is already in the shared tier courtesy of
        :meth:`_offload_finished`; publish the identity/SLO record the
        decode engine admits by.  Published strictly *after* every tier
        entry exists — the handoff-record invariant the disagg property
        tests pin (a record must never dangle)."""
        meta = self.far_tier.home((req.rid, "aux"))
        rec = HandoffRecord(
            rid=req.rid, prompt=np.asarray(req.prompt),
            max_new_tokens=req.max_new_tokens, eos_id=req.eos_id,
            n_tokens=meta["tokens"], n_pages=meta["pages"],
            generated=list(req.generated), token_ts=list(req.token_ts),
            tier=req.tier, ttft_slo=req.ttft_slo, tpot_slo=req.tpot_slo,
            arrival_t=req.arrival_t, submitted_t=req.submitted_t,
            first_token_t=req.first_token_t, done=req.done,
            src_len=req.src_len)
        self.handoff.publish(rec)
        self.stats["handoffs"] += 1
        if self.tracer.enabled:
            self.tracer.instant(
                "requests", f"req{req.rid}", "handoff",
                {"n_pages": rec.n_pages, "n_tokens": rec.n_tokens,
                 "done": rec.done})
        self.events.post(EventKind.HANDOFF, req.rid)

    def fetch_finished(self, rid: int) -> Cache:
        """Reassemble a finished, offloaded request's dense single-
        sequence cache from its far-tier pages (LATENCY aloads, all
        issued before the first wait so the transfers overlap — the
        pager's fault-safe :meth:`~repro.paging.Pager.fetch_keys`
        helper, shared with the cross-engine handoff fetch).

        Fault-safe: entries are discarded only after *every* transfer
        has verifiably landed — a fault mid-fetch raises, but the far
        copies survive and a retry re-issues the lost aloads (the PR 3
        pager fault discipline applied to the reuse path)."""
        if not self.offload_finished:
            raise PagingError("engine was not built with offload_finished")
        tier = self.far_tier
        meta = tier.get((rid, "aux"))
        n_pages, tokens = meta["pages"], meta["tokens"]
        keys = [(rid, logical) for logical in range(n_pages)]
        # overlapped fetch; discards only after every payload landed
        payloads = self.pager.fetch_keys(keys, discard_after=True)
        kv = self.cache.kv
        quant = "k_scales" in kv
        L, _, page, Hkv, D = kv["k_pages"].shape
        pages = []
        for logical, key in enumerate(keys):
            data = payloads[key]
            take = min(page, tokens - logical * page)
            if take <= 0:
                break
            k = np.asarray(data["k"])
            v = np.asarray(data["v"])
            if quant:
                # the reassembled dense cache is unquantized: dequantize
                # each page payload with its own (L, Hkv) scale pair
                k = k.astype(np.float32) \
                    * np.asarray(data["k_scale"])[:, None, :, None]
                v = v.astype(np.float32) \
                    * np.asarray(data["v_scale"])[:, None, :, None]
            pages.append({"k": k[:, None, :take], "v": v[:, None, :take]})
        tier.discard((rid, "aux"))
        aux = meta["aux"]
        kdt = np.dtype(jnp.bfloat16) if quant \
            else np.dtype(kv["k_pages"].dtype)
        residue = Cache(
            kv={"k": np.zeros((L, 1, 0, Hkv, D), kdt),
                "v": np.zeros((L, 1, 0, Hkv, D), kdt),
                "pos": np.zeros((), np.int32),
                "slots": np.asarray(self.slot_tokens, np.int32)},
            ssm=aux["ssm"], cross=aux["cross"], pos=aux["pos"])
        return join_kv_pages(residue, pages, self.slot_tokens)
