"""The decode-loop role component: the step and the finish path.

:class:`DecodeMixin` owns chunk-vs-decode work selection, the jitted
mixed/decode step dispatch, prefill graduation, and the request finish
path — including the one hook that differentiates the engine roles:
:meth:`_role_done`.  A FUSED or DECODE engine finishes a request when
its token budget (or EOS) says so; a PREFILL engine finishes it at its
*first token* — graduation — at which point the ordinary
``offload_finished`` park plus a published handoff record hand the
request to the decode side.  Everything else in the loop is shared.
The mixin assumes the host class provides the engine state surface —
``serve/engine.py`` assembles it.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.amu import QoS
from repro.paging import EventKind, PagingError
from repro.serve.config import EngineRole, Tier
from repro.serve.request import Request

__all__ = ["DecodeMixin"]


class DecodeMixin:
    """Decode loop + finish path (see the module docstring).  Mixed
    into :class:`~repro.serve.engine.Engine`."""

    # -- the role hook ---------------------------------------------------------
    def _role_done(self, req: Request) -> bool:
        """Is this request finished *for this engine's role*?  FUSED and
        DECODE: the request's own budget/EOS (``req.done``).  PREFILL:
        any first token — prefill's job ends at graduation; whether the
        request is done under fused semantics travels on the handoff
        record (``rec.done``) for the decode side to honour."""
        if self.role is EngineRole.PREFILL:
            return bool(req.generated)
        return req.done

    # -- chunk-queue scheduling (chunked paged prefill) ------------------------
    def _select_chunks(self) -> List:
        """Pick chunk-vs-decode work for this step.

        A chunk for the oldest admitting slots runs fused with the
        decode step when (a) the LATENCY aload window has room — resume
        traffic saturating the per-QoS window (§2.2 MACR) means parked
        pages are mid-flight and chunk compute would only delay their
        landing — and (b) the chunk's pages fit the pool without
        preempting anyone (free-page-watermark occupancy; chunk growth,
        like decode growth, is a continuation and so is exempt from the
        admission low watermark)."""
        if not self.prefilling:
            return []
        if self._resuming and not self.pager.windows.has_room(QoS.LATENCY):
            return []
        picks: List = []
        t_exact = None
        exact = self.cfg.family == "hybrid"    # pad tokens corrupt SSM state
        for req in self.sched.chunk_order(self.prefilling.values()):
            if len(picks) >= self.chunk_slots:
                break
            start = req.prefill_pos
            end = min(req.target_len, start + self.chunk_tokens)
            if exact and t_exact is not None and end - start != t_exact:
                continue                   # exact-shape batch: next step
            need = self.page_table.pages_needed(req.rid, end)
            if need and not self._make_room(need, frozenset({req.rid}),
                                            preempt=False):
                continue                   # pool tight: decode-only step
            if exact and t_exact is None:
                t_exact = end - start      # pin shape only once a row fits
            self._alloc_pinned(req, end)
            picks.append((req, start, end))
        return picks

    def _force_chunk(self) -> List:
        """Nothing decodable and no chunk fit the pool politely: force
        the oldest admitting slot's chunk through, preempting (parking
        another half-prefilled victim) if that is what it takes — the
        loop must always progress."""
        req = min(self.prefilling.values(), key=lambda r: r.admit_seq)
        end = min(req.target_len, req.prefill_pos + self.chunk_tokens)
        need = self.page_table.pages_needed(req.rid, end)
        if need and not self._make_room(need, frozenset({req.rid}),
                                        preempt=True):
            raise PagingError(
                f"chunked prefill of request {req.rid} cannot progress: "
                f"pool of {self.page_pool.n_pages} pages exhausted")
        self._alloc_pinned(req, end)
        return [(req, req.prefill_pos, end)]

    def _build_chunk(self, picks) -> Dict[str, Any]:
        """Assemble the mixed step's chunk operand (C = ``chunk_slots``
        rows, unused rows inert with length 0 / trash page rows)."""
        C = self.chunk_slots
        if self.cfg.family == "hybrid":
            T = picks[0][2] - picks[0][1]  # exact shapes (no pad tokens)
        else:
            T = self.chunk_tokens
        tokens = np.zeros((C, T), np.int32)
        offset = np.zeros((C,), np.int32)
        length = np.zeros((C,), np.int32)
        slots = np.zeros((C,), np.int32)
        src_len = np.zeros((C,), np.int32)
        rows = np.full((C, self.pages_per_seq), self.trash_frame, np.int32)
        for i, (req, start, end) in enumerate(picks):
            n = end - start
            tokens[i, :n] = req.prompt[start:end]
            offset[i] = start
            length[i] = n
            slots[i] = req.slot
            src_len[i] = req.src_len
            rows[i] = req.chunk_rows
        chunk = {"tokens": jnp.asarray(tokens),
                 "offset": jnp.asarray(offset),
                 "length": jnp.asarray(length),
                 "page_rows": jnp.asarray(rows)}
        if self.cfg.family == "encdec":
            chunk["slots"] = jnp.asarray(slots)
            chunk["src_len"] = jnp.asarray(src_len)
        if self.cfg.family == "hybrid":
            trees = [r.chunk_ssm for r, _, _ in picks]
            trees += [self._zero_chunk_ssm] * (C - len(picks))
            chunk["ssm"] = jax.tree_util.tree_map(
                lambda *xs: jnp.asarray(np.concatenate(xs, axis=1)), *trees)
        return chunk

    def _finish_chunks(self, picks, chunk_logits, carry) -> None:
        """Advance every picked request past its chunk; rows that just
        covered their prompt's last token graduate to the decode batch
        (their first sampled token is the chunk's last-valid logits)."""
        tr = self.tracer
        for i, (req, start, end) in enumerate(picks):
            req.prefill_pos = end
            if tr.enabled:
                tr.instant("requests", f"req{req.rid}", "chunk",
                           {"start": start, "end": end,
                            "target": req.target_len})
            if self.cfg.family == "hybrid":
                req.chunk_ssm = jax.tree_util.tree_map(
                    lambda a: np.asarray(a[:, i:i + 1]), carry)
            if end >= req.target_len:
                self._finalize_prefill(req, chunk_logits[i])

    def _finalize_prefill(self, req: Request, logits_row) -> None:
        """Graduate a fully-prefilled request into the decode batch: the
        device page-table row flips from the trash frame to the real
        frames (one host-mirror write — the KV is already in its pool
        frames), pos and any SSM carry land in the cache, and the first
        token comes from the final chunk's logits at the prompt's last
        valid position — matching the dense path's ``last_pos`` exactly."""
        slot = req.slot
        self._pt_np[slot] = req.chunk_rows
        self._pt_dirty = True
        pos_row = jnp.asarray([req.target_len], jnp.int32)
        cache = self.cache
        new_pos = jax.lax.dynamic_update_slice_in_dim(
            cache.pos, pos_row.astype(cache.pos.dtype), slot, axis=0)
        ssm = cache.ssm
        if self.cfg.family == "hybrid":
            ssm = jax.tree_util.tree_map(
                lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
                    dst, jnp.asarray(src).astype(dst.dtype), slot, axis=1),
                ssm, req.chunk_ssm)
            req.chunk_ssm = None
        self.cache = cache._replace(pos=new_pos, ssm=ssm)
        req.chunk_rows = None
        del self.prefilling[slot]
        if self.prefix is not None:
            # donate the prompt's full pages to the prefix cache: future
            # requests with the same prefix share these frames instead
            # of re-running their chunks
            self.prefix.intern(req.prompt, req.rid, self._read_frame)
        first = int(np.argmax(np.asarray(logits_row)))
        req.generated.append(first)
        req.first_token_t = self.clock()
        req.token_ts.append(req.first_token_t)
        self.active[slot] = req
        self._obs_phase(req, "decode")
        if self.tracer.enabled:
            self.tracer.instant(
                "requests", f"req{req.rid}", "first_token",
                {"ttft_s": req.first_token_t - req.arrival_t})
        self._finish_if_done(req)

    def _propose_drafts(self) -> Dict[int, int]:
        """Ask the proposer for a draft per active slot (speculation on).

        Returns rid -> draft length; the drafted tokens themselves land
        in ``self._draft_toks``.  Drafts are capped to the slot's token
        head-room and the request's remaining budget (a draft past the
        budget could never commit — the bonus token uses the last unit),
        and trimmed at the first drafted EOS.  An empty dict means this
        step runs the plain single-token path."""
        drafts: Dict[int, int] = {}
        self._draft_toks: Dict[int, List[int]] = {}
        pos_np = np.asarray(self.cache.pos)
        if any(int(pos_np[slot]) + 1 > self.slot_tokens
               for slot in self.active):
            # a slot at full capacity writes its token at the clamped
            # last row in decode_step but would scatter to the trash
            # frame in verify_step — fall back to the plain path for
            # the whole batch this step
            return drafts
        for slot, req in self.active.items():
            pos = int(pos_np[slot])
            room = self.slot_tokens - pos - 1
            budget = req.max_new_tokens - len(req.generated) - 1
            cap = min(self.speculate_k, room, budget)
            if cap <= 0:
                continue
            history = req.prompt.tolist() + req.generated
            draft = list(self.proposer.propose(req.rid, history))[:cap]
            if req.eos_id is not None and req.eos_id in draft:
                draft = draft[:draft.index(req.eos_id) + 1]
            if draft:
                drafts[req.rid] = len(draft)
                self._draft_toks[req.rid] = draft
        return drafts

    def _step(self) -> None:
        tr = self.tracer
        drafts: Dict[int, int] = {}
        if self.speculating and self.active:
            with tr.span("engine", "prepare"):
                drafts = self._propose_drafts()
        with tr.span("engine", "grow"):
            if self.paging:
                # draft-aware growth: a speculating slot pins frames for
                # its whole write window [pos, pos + 1 + draft); entries
                # clamp in place when the pool cannot cover the draft
                self._ensure_growth(drafts or None)
            picks = self._select_chunks() if self.chunking else []
            if self.chunking and not picks and not self.active and \
                    self.prefilling and not self._resuming:
                picks = self._force_chunk()
        if not self.active and not picks:
            return
        if drafts:
            # growth/chunk allocation may have preempted a drafting slot
            # (its draft dies with the park) or clamped a draft to zero
            live = {req.rid for req in self.active.values()}
            drafts = {r: n for r, n in drafts.items() if r in live and n > 0}
        with tr.span("engine", "prepare"):
            if self.paging and self._pt_dirty:
                # refresh the device page-table rows from the host mirror
                # (skipped on steady-state steps with no scheduling events)
                kv = self.cache.kv
                self.cache = self.cache._replace(
                    kv=dict(kv, page_table=jnp.asarray(self._pt_np)))
                self._pt_dirty = False
            chunk = self._build_chunk(picks) if picks else None
            if not drafts:
                toks = np.zeros((self.max_batch, 1), np.int32)
                for slot, req in self.active.items():
                    toks[slot, 0] = req.generated[-1]
                toks = jnp.asarray(toks)
        if drafts:
            self._spec_step(drafts, picks, chunk)
            return
        with tr.span("engine", "launch"):
            if picks:
                logits, chunk_logits, carry, self.cache = self._mixed(
                    self.params, self.cache, toks, chunk)
            else:
                logits, self.cache = self._decode(self.params, self.cache,
                                                  toks)
        if picks:
            self.stats["mixed_steps"] += 1
            self.stats["chunks"] += len(picks)
        self.stats["steps"] += 1
        with tr.span("engine", "wait_device"):
            jax.block_until_ready(logits)
        with tr.span("engine", "logits_to_host"):
            logits = np.asarray(logits) if self.active else None
            chunk_logits = np.asarray(chunk_logits) if picks else None
        with tr.span("engine", "sample"):
            t_now = self.clock()
            for slot, req in list(self.active.items()):
                nxt = int(np.argmax(logits[slot]))
                req.generated.append(nxt)
                req.token_ts.append(t_now)
                self._finish_if_done(req)
        if picks:
            with tr.span("engine", "finish_chunks"):
                self._finish_chunks(picks, chunk_logits, carry)

    def _spec_step(self, drafts: Dict[int, int], picks: List,
                   chunk) -> None:
        """One speculative verify-K step: score every slot's draft in a
        single jitted program, then accept/rollback host-side.

        Acceptance is the standard greedy-speculation rule: the longest
        draft prefix that matches the verify logits' argmax commits,
        plus one *bonus* token from the first non-matching row — so a
        fully-rejected draft still commits one token (exactly the plain
        step's), and the emitted stream is token-identical to
        single-step greedy decode by construction.  Rollback is
        host-only: the verify step never advances ``pos``, the engine
        writes ``pos + appended`` back and
        :meth:`~repro.paging.PageTable.rewind_tokens` drops any page
        left holding only the rejected tail (whose K/V beyond the new
        ``pos`` is dead — masked by every future read, overwritten by
        future appends, and excluded from a later park's freshness tag
        because parks derive valid tokens from ``pos``)."""
        S = self.speculate_k + 1
        tr = self.tracer
        with tr.span("engine", "prepare"):
            toks = np.zeros((self.max_batch, S), np.int32)
            length = np.zeros((self.max_batch,), np.int32)
            per_slot: Dict[int, List[int]] = {}
            for slot, req in self.active.items():
                n = drafts.get(req.rid, 0)
                d = self._draft_toks.get(req.rid, [])[:n]
                per_slot[slot] = d
                toks[slot, 0] = req.generated[-1]
                toks[slot, 1:1 + len(d)] = d
                length[slot] = 1 + len(d)
            toks, length = jnp.asarray(toks), jnp.asarray(length)
        with tr.span("engine", "launch"):
            if picks:
                logits, chunk_logits, carry, self.cache = \
                    self._mixed_verify(self.params, self.cache, toks,
                                       length, chunk)
            else:
                logits, self.cache = self._verify(self.params, self.cache,
                                                  toks, length)
        if picks:
            self.stats["mixed_steps"] += 1
            self.stats["chunks"] += len(picks)
        self.stats["steps"] += 1
        self.stats["spec_steps"] += 1
        with tr.span("engine", "wait_device"):
            jax.block_until_ready(logits)
        with tr.span("engine", "logits_to_host"):
            logits = np.asarray(logits)
            chunk_logits = np.asarray(chunk_logits) if picks else None
        with tr.span("engine", "sample"):
            self._accept_drafts(logits, per_slot)
        if picks:
            with tr.span("engine", "finish_chunks"):
                self._finish_chunks(picks, chunk_logits, carry)

    def _accept_drafts(self, logits: np.ndarray,
                       per_slot: Dict[int, List[int]]) -> None:
        """Greedy acceptance of every slot's draft against the verify
        logits, position rewinds, and the finish path (see
        :meth:`_spec_step`)."""
        t_now = self.clock()
        tr = self.tracer
        pos_np = np.array(self.cache.pos)
        step_drafted = step_accepted = 0
        for slot, req in list(self.active.items()):
            d = per_slot[slot]
            m = len(d)
            start = int(pos_np[slot])
            greedy = np.argmax(logits[slot, :m + 1], axis=-1)
            acc = 0
            while acc < m and d[acc] == int(greedy[acc]):
                acc += 1
            appended = 0
            for t in d[:acc] + [int(greedy[acc])]:
                req.generated.append(int(t))
                req.token_ts.append(t_now)
                appended += 1
                if self._role_done(req):
                    break
            committed = min(acc, appended)   # drafts actually appended
            step_drafted += m
            step_accepted += committed
            # positions are host-owned across a verify step: advance by
            # what committed, and drop pages holding only rejected tail
            pos_np[slot] = start + appended
            released = self.page_table.rewind_tokens(req.rid,
                                                     start + appended)
            if released:
                keep = self.page_table.n_pages(req.rid)
                self._pt_np[slot, keep:] = self.trash_frame
                self._pt_dirty = True
        # write rewound positions back BEFORE finishing slots: finish
        # may offload the request's KV, and offload reads cache.pos
        self.cache = self.cache._replace(pos=jnp.asarray(pos_np))
        for req in list(self.active.values()):
            self._finish_if_done(req)
        self.stats["drafted"] += step_drafted
        self.stats["accepted"] += step_accepted
        self.stats["rejected"] += step_drafted - step_accepted
        if tr.enabled:
            tr.instant("engine", "spec", "verify",
                       {"drafted": step_drafted,
                        "accepted": step_accepted,
                        "rejected": step_drafted - step_accepted})
            tr.counter("engine", "spec_drafted", self.stats["drafted"])
            tr.counter("engine", "spec_accepted", self.stats["accepted"])
            tr.counter("engine", "spec_rejected", self.stats["rejected"])

    def _finish_if_done(self, req: Request) -> None:
        if not self._role_done(req):
            return
        if self.speculating:
            self.proposer.drop(req.rid)
        slot = req.slot
        if slot is not None and slot in self.active:
            del self.active[slot]
        if slot is not None:
            if self.offload_finished:
                self._offload_finished(req)
                if self.role is EngineRole.PREFILL:
                    # graduation: pages + aux are in the shared tier,
                    # publish the control-plane record the decode-role
                    # engine admits by
                    self._publish_handoff(req)
            if self.paging:
                self._pt_np[slot] = self.trash_frame
                self._pt_dirty = True
            self.pool.release(slot)
        req.done_t = self.clock()
        self.finished[req.rid] = req
        self.stats["slo_attained" if req.slo_attained()
                   else "slo_missed"] += 1
        if req.token_ts:
            tier = req.tier.name
            self.metrics.observe(f"engine/ttft_s/{tier}", req.ttft)
            if len(req.token_ts) > 1:
                self.metrics.observe(f"engine/tpot_s/{tier}", req.tpot)
        if self.tracer.enabled:
            self._obs_phase(req, None)       # close the lifecycle track
            # everything trace_report needs to rebuild slo_report() from
            # the trace alone rides on this one instant
            self.tracer.instant(
                "requests", f"req{req.rid}", "finish",
                {"tier": req.tier.name, "arrival": req.arrival_t,
                 "first_token": req.first_token_t, "done": req.done_t,
                 "n_new": len(req.generated),
                 "n_preempts": req.n_preempts,
                 "ttft_slo": req.ttft_slo, "tpot_slo": req.tpot_slo,
                 "attained": bool(req.slo_attained())})
        self.events.post(EventKind.COMPLETE, req.rid)
        self.events.drain()

    # -- SLO telemetry --------------------------------------------------------
    def slo_report(self) -> Dict[str, Any]:
        """Per-tier SLO attainment over the finished requests.

        All numbers live on the engine's one clock (virtual seconds by
        default).  *Goodput* is the serving-paper definition: tokens
        generated by requests that met every SLO they carry — work that
        arrived uselessly late counts for nothing.  Example::

            eng.run()
            rep = eng.slo_report()
            rep["interactive"]["goodput"]      # SLO-attaining tok/s
            rep["interactive"]["ttft_p95"]
        """
        elapsed = max(self.clock(), 1e-12)
        out: Dict[str, Any] = {"elapsed": elapsed}
        for tier in Tier:
            reqs = [r for r in self.finished.values() if r.tier is tier]
            ttfts = sorted(r.ttft for r in reqs if r.token_ts)
            good = [r for r in reqs if r.slo_attained()]
            good_tokens = sum(len(r.generated) for r in good)
            out[tier.name.lower()] = {
                "n": len(reqs),
                "attained": len(good),
                "attainment": len(good) / len(reqs) if reqs else 1.0,
                "good_tokens": good_tokens,
                "goodput": good_tokens / elapsed,
                "ttft_p50": (float(np.percentile(ttfts, 50))
                             if ttfts else 0.0),
                "ttft_p95": (float(np.percentile(ttfts, 95))
                             if ttfts else 0.0),
                "ttft_p99": (float(np.percentile(ttfts, 99))
                             if ttfts else 0.0),
            }
        return out
