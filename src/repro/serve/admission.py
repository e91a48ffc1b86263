"""The admission role component: how requests enter compute.

:class:`AdmissionMixin` owns everything between the queue and a live
slot — dense whole-prompt prefill, chunk-queue admission with the mixed
prefill/decode scheduling, prefix-cache mapping, watermark/SLO
admission gating, and (new with disaggregation) :meth:`admit_handoff`,
the DECODE-role entry point that adopts a PREFILL-role engine's
graduated request straight from the shared far tier.  A handoff
admission is deliberately *not* a new code path: it rebuilds the
request parked (pages registered PARKED against the shared tier's
entries, aux residue fetched fault-safe) and lets the ordinary resume
machinery in :class:`~repro.serve.transfer.TransferMixin` slot it in.
The mixin assumes the host class provides the engine state surface —
``serve/engine.py`` assembles it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.amu import QoS
from repro.models.model import encode_cross, prefill
from repro.paging import EventKind, PageState, PagingError, pages_for
from repro.serve.config import EngineRole, Tier
from repro.serve.disagg import HandoffRecord
from repro.serve.kv_cache import insert_aux_slot, insert_slot
from repro.serve.request import Request
from repro.serve.transfer import _scatter_seq_pages, _scatter_seq_pages_q

__all__ = ["AdmissionMixin"]


class AdmissionMixin:
    """Admission + chunk-queue scheduling (see the module docstring).
    Mixed into :class:`~repro.serve.engine.Engine`."""

    # -- prefill --------------------------------------------------------------
    def _bucket(self, plen: int) -> int:
        # SSM/hybrid state is corrupted by pad tokens, so exact lengths
        # there; attention families pad to the next bucket (cache entries
        # beyond plen are never attended: pos starts at plen).
        if self.cfg.family in ("ssm", "hybrid"):
            return plen
        for b in self.buckets:
            if plen <= b:
                return b
        return self.max_len

    def _prefill_one(self, req: Request):
        plen = len(req.prompt)
        bucket = self._bucket(plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = req.prompt
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "encdec":
            se = req.src_embeds
            if se is None:
                se = np.zeros((bucket, self.cfg.d_model), np.float32)
            src = np.zeros((1, bucket, self.cfg.d_model), np.float32)
            src[0, :se.shape[0]] = se[:bucket]
            batch["src_embeds"] = jnp.asarray(src)
        if self.cfg.mrope_sections:
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(bucket, dtype=jnp.int32), (3, 1, bucket))
        key = (bucket, self.cfg.family)
        if key not in self._prefills:
            cfg = self.cfg
            self._prefills[key] = jax.jit(
                lambda p, b, lp: prefill(p, cfg, b, max_len=self.max_len,
                                         last_pos=lp))
        # logits come from the prompt's true last token (plen - 1), never
        # from the padded bucket tail — the first sampled token must not
        # depend on pad embeddings, and the chunked-prefill path (which
        # never materialises the pad tail) must agree with this one
        logits, single = self._prefills[key](
            self.params, batch, jnp.asarray([plen - 1], jnp.int32))
        self.stats["prefills"] += 1
        # true position is plen (ignore pad tail): set pos = plen
        single = single._replace(pos=jnp.full((1,), plen, jnp.int32))
        return logits, single

    def _install_sequence(self, req: Request, single) -> None:
        """Admission on the paged layout: scatter the prefilled KV pages
        into their pool frames and install the slot's page-table row +
        aux state.  No dense batched KV exists to insert into."""
        slot = req.slot
        kv = self.cache.kv
        # only the prompt's pages — exactly the frames _alloc_pinned just
        # mapped; the bucket tail beyond them is zeros, never attended
        n_pg = pages_for(min(len(req.prompt), self.slot_tokens),
                         self.page_size)
        frames = jnp.asarray(self._pt_np[slot, :n_pg])
        if "k_scales" in kv:
            # quantized pool: the dense prefill KV quantizes on the way
            # into its frames, one absmax scale per (frame, head)
            kp, vp, ks, vs = _scatter_seq_pages_q(
                kv["k_pages"], kv["v_pages"],
                kv["k_scales"], kv["v_scales"],
                single.kv["k"], single.kv["v"], frames, n_pg,
                self.kv_quant.mode)
            cache = self.cache._replace(kv=dict(
                kv, k_pages=kp, v_pages=vp, k_scales=ks, v_scales=vs))
        else:
            kp, vp = _scatter_seq_pages(
                kv["k_pages"], kv["v_pages"],
                single.kv["k"], single.kv["v"], frames, n_pg)
            cache = self.cache._replace(
                kv=dict(kv, k_pages=kp, v_pages=vp))
        aux = {"ssm": single.ssm, "cross": single.cross, "pos": single.pos}
        self.cache = insert_aux_slot(cache, aux, slot, self.max_batch)

    def _install_cross(self, req: Request) -> None:
        """Enc-dec chunk-queue admission: run the encoder once and park
        its cross-attention KV in the slot's rows of ``cache.cross`` —
        every later prompt chunk and decode token reads it from there
        (the decode path never writes cross state, so the rows survive
        the whole prefill).  The projections are the exact ones dense
        prefill computes, so chunked and dense agree bit-for-bit."""
        plen = len(req.prompt)
        bucket = self._bucket(plen)
        se = req.src_embeds
        if se is None:
            se = np.zeros((bucket, self.cfg.d_model), np.float32)
        src = np.zeros((1, bucket, self.cfg.d_model), np.float32)
        src[0, :se.shape[0]] = se[:bucket]
        key = ("cross", bucket)
        if key not in self._prefills:
            cfg = self.cfg
            self._prefills[key] = jax.jit(
                lambda p, s: encode_cross(p, cfg, s))
        cross = self._prefills[key](self.params, jnp.asarray(src))
        slot = req.slot
        new_cross = {}
        for name, dst in self.cache.cross.items():
            src_rows = cross[name]
            # slot axis by leaf name: k/v are (L, B, Ssrc, ...), enc_out
            # is (B, Ssrc, d) — a shape heuristic misfires when Ssrc
            # happens to equal max_batch
            axis = 1 if name in ("k", "v") else 0
            new_cross[name] = jax.lax.dynamic_update_slice_in_dim(
                dst, src_rows.astype(dst.dtype), slot, axis=axis)
        self.cache = self.cache._replace(cross=new_cross)
        req.src_len = bucket

    # -- scheduling ------------------------------------------------------------
    def _chunkable(self, req: Request) -> bool:
        """Chunk-queue admission requires the whole prompt to fit the
        slot's token capacity (an SWA ring that wraps mid-prompt would
        rewrite pages the chunk path still attends); longer prompts fall
        back to the legacy dense-prefill admission."""
        return (self.chunking and len(req.prompt) > 0
                and len(req.prompt) <= self.slot_tokens)

    def _admit_prefix(self, req: Request, hits: List[int]) -> bool:
        """Map prefix-cache hits onto the request's fresh page-table row.

        Device-resident hits are refcount-shared in place (zero traffic,
        zero compute); hits whose shared page lives only in the far tier
        make the request start *parked* — it rides the ordinary resume
        machinery (LATENCY prefetch of a private copy, including the
        resume-while-ARRIVING paths) before its first chunk.  Either
        way ``prefill_pos`` starts past the shared prefix, so those
        chunks are simply never queued.  Returns True on the far route.
        """
        self.page_table.register(req.rid)
        req.target_len = len(req.prompt)
        far = False
        for l in hits:
            key = self.prefix.far_key(l)
            if self.prefix.entry_state(l) is PageState.RESIDENT:
                phys = self.prefix.entry_phys(l)
                logical = self.page_table.append_shared(req.rid, phys)
                self.page_pool.touch(phys)
            else:
                far = True
                logical = self.page_table.append_parked(req.rid)
                self.stats["prefix_far_hits"] += 1
            # far alias (no copy: same host payload) so this mapping can
            # always park clean and a far hit fetches through the pager
            self.pager.store_far(req.rid, logical, self.far_tier.home(key),
                                 tokens=self.page_size)
        req.prefill_pos = len(hits) * self.page_size
        self.stats["prefix_hits"] += len(hits)
        self.stats["prefix_tokens_saved"] += req.prefill_pos
        if far:
            req.parked = True
        return far

    def _admit(self) -> None:
        with self.tracer.span("engine", "admit"):
            if self._resuming:
                with self.tracer.span("engine", "resume"):
                    self._try_finish_resumes()
            now = self.clock()
            self.sched.order_queue(self.queue, now)
            while self.queue:
                req = self.queue[0]
                if req.arrival_t > now:
                    break                 # trace replay: not in the system yet
                if req.parked:                         # preempted: resume
                    if req.rid in self._resuming or \
                            not self._start_resume(req):
                        break
                    self.queue.pop(0)
                    with self.tracer.span("engine", "resume"):
                        self._try_finish_resumes()
                    continue
                if not self.pool.n_free:
                    break
                hits: List[int] = []
                if self.paging:
                    need = pages_for(min(len(req.prompt), self.slot_tokens),
                                     self.page_size)
                    if self.prefix is not None and self._chunkable(req) \
                            and req.rid not in self.page_table.sequences():
                        hits = self.prefix.match(req.prompt)
                        # device-resident hits take no new frames
                        need -= sum(
                            1 for l in hits if self.prefix.entry_state(l)
                            is PageState.RESIDENT)
                    if not self.sched.may_admit(req, need):
                        # SLO load shedding: the highest-priority admissible
                        # request is batch-tier and the pool is too tight to
                        # take it without risking interactive deadlines
                        self.stats["shed_admissions"] += 1
                        if self.tracer.enabled:
                            self.tracer.instant(
                                "engine", "sched", "shed",
                                {"rid": req.rid, "tier": req.tier.name,
                                 "need_pages": need,
                                 "free": self.page_pool.n_free})
                        break
                    if not self.policy.can_admit(self.page_pool, need) and \
                            not self._make_room(need + self.policy.low,
                                                frozenset(), preempt=False):
                        break
                if hits and self._admit_prefix(req, hits):
                    # far-tier hits: request left at the queue head, parked;
                    # the next iteration routes it through _start_resume
                    continue
                self.queue.pop(0)
                slot = self.pool.alloc()
                req.slot = slot
                req.admit_t = self.clock()
                if self._chunkable(req):
                    # chunk-queue admission: install bookkeeping only — the
                    # prompt is computed chunk-by-chunk by the mixed step,
                    # interleaved with every running slot's decode
                    if req.rid not in self.page_table.sequences():
                        self.page_table.register(req.rid)
                    req.target_len = len(req.prompt)
                    req.chunk_rows = np.full((self.pages_per_seq,),
                                             self.trash_frame, np.int32)
                    # prefix hits already mapped: pin them for the slot and
                    # point the chunk row at the shared frames
                    for logical in range(self.page_table.n_pages(req.rid)):
                        self.page_table.pin_page(req.rid, logical)
                        req.chunk_rows[logical] = \
                            self.page_table.entry(req.rid, logical).phys
                    if self.cfg.family == "hybrid":
                        req.chunk_ssm = jax.tree_util.tree_map(
                            np.copy, self._zero_chunk_ssm)
                    if self.cfg.family == "encdec":
                        self._install_cross(req)
                    req.admit_seq = next(self._admits)
                    self.prefilling[slot] = req
                    self.stats["admitted"] += 1
                    self._obs_phase(req, "prefill")
                    self.events.post(EventKind.ADMIT, req.rid)
                    continue
                logits, single = self._prefill_one(req)
                if self.paging:
                    self.page_table.register(req.rid)
                    self._alloc_pinned(req,
                                       min(len(req.prompt), self.slot_tokens))
                    self._install_sequence(req, single)
                else:
                    self.cache = insert_slot(self.cache, single, slot,
                                             self.max_batch)
                req.admit_seq = next(self._admits)
                first = int(np.argmax(np.asarray(logits)[0]))
                req.generated.append(first)
                req.first_token_t = self.clock()
                req.token_ts.append(req.first_token_t)
                self.active[slot] = req
                self.stats["admitted"] += 1
                self._obs_phase(req, "decode")
                if self.tracer.enabled:
                    self.tracer.instant(
                        "requests", f"req{req.rid}", "first_token",
                        {"ttft_s": req.first_token_t - req.arrival_t})
                self.events.post(EventKind.ADMIT, req.rid)
                self._finish_if_done(req)

    # -- cross-engine handoff admission (DECODE role) --------------------------
    def admit_handoff(self, rec: HandoffRecord,
                      arrival_t: Optional[float] = None) -> int:
        """Adopt a PREFILL-role engine's graduated request from the
        shared far tier.

        The aux residue rides the pager's fault-safe overlapped fetch
        (:meth:`~repro.paging.Pager.fetch_keys` — a mid-transfer AMU
        fault raises with the tier entry intact, so the caller simply
        retries), the prompt pages register as PARKED page-table
        entries against the tier's ``(rid, logical)`` homes, and the
        request joins the queue *parked*: the ordinary resume machinery
        LATENCY-prefetches the pages and slots it into the decode batch
        — no handoff-specific transfer path exists to get wrong.  A
        record already done under fused semantics (one-token request or
        first-token EOS) finishes immediately and clears its tier
        entries.  Returns the adopted rid (unchanged from the prefill
        side; the local rid counter jumps past it)."""
        if self.role is not EngineRole.DECODE:
            raise PagingError(
                f"admit_handoff requires EngineRole.DECODE; this engine "
                f"is {self.role.value!r}")
        rid = rec.rid
        if rid in self.finished or rid in self.page_table.sequences():
            raise PagingError(f"handoff rid {rid} already known here")
        # handed-off rids stay globally unique: local submissions must
        # never collide with them
        self._next_rid = max(self._next_rid, rid + 1)
        self.far_tier.poll()             # retire the tier-AMU offloads
        now = self.clock()
        req = Request(
            rid=rid, prompt=np.asarray(rec.prompt, np.int32),
            max_new_tokens=rec.max_new_tokens, eos_id=rec.eos_id,
            tier=Tier(rec.tier), ttft_slo=rec.ttft_slo,
            tpot_slo=rec.tpot_slo,
            # both engines' virtual clocks share an origin, so the
            # prefill-side arrival/first-token instants stay meaningful
            # for SLO attainment measured on this side
            arrival_t=rec.arrival_t if arrival_t is None else arrival_t,
            submitted_t=rec.submitted_t, src_len=rec.src_len)
        req.generated = list(rec.generated)
        req.token_ts = list(rec.token_ts)
        req.first_token_t = rec.first_token_t
        if rec.done:
            # one-token / first-token-EOS request: nothing to decode;
            # every transfer already landed, so the tier entries may go
            self.far_tier.discard_seq(rid)
            req.done_t = now
            self.finished[rid] = req
            self.stats["handoffs"] += 1
            self.stats["slo_attained" if req.slo_attained()
                       else "slo_missed"] += 1
            return rid
        # aux residue: fault-safe overlapped fetch, discarded only after
        # the payload verifiably landed (fault ⇒ raise, entry intact,
        # caller retries with no local state to unwind — nothing below
        # this line has happened yet, including the handoff counter)
        meta = self.pager.fetch_keys([(rid, "aux")],
                                     discard_after=True)[(rid, "aux")]
        self.stats["handoffs"] += 1
        self.page_table.register_parked(rid, meta["pages"])
        req.parked = True
        req.residue = meta["aux"]
        self.queue.append(req)
        self.sched.on_submit(req)
        return rid
