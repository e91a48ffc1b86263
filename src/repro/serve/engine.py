"""Continuous-batching serving engine over the paged KV subsystem.

The scheduler is the paper's *event-driven model* (§2.3.2) applied to
requests instead of cache lines: decode steps are the event loop's
ticks; pager ``getfin`` completions post PAGE_ARRIVED events; admission
and preemption decisions come from *free-page watermarks* over the
device page pool (``repro.paging``) instead of free-slot counts.  This
is what lets the engine admit more concurrent sequences than device
memory can hold:

  * each sequence's KV is accounted in fixed-size pages of a shared
    :class:`~repro.paging.PagePool`; active slots pin their pages,
  * when growth (or a new admission) exceeds the pool, a victim is
    *preempted*: only its **cold** pages are written back (BULK-QoS
    ``astore``; pages whose far-tier copy is still current move for
    free), while the hot tail stays cached on-device,
  * rescheduling prefetches the parked pages **hot tail first** with
    LATENCY-QoS ``aload`` that overlaps the current decode step; the
    sequence re-enters a slot the moment its residency bits are all set
    — no re-prefill, bit-exact resume.

Decode computes **directly on the paged layout**: the device cache is a
:class:`~repro.models.model.PagedCache` whose k/v live in the pool's
page frames, and the serve step's attention reads them through the
per-slot page table (:func:`~repro.models.attention.
paged_decode_attention_block` — the Pallas scalar-prefetch gather on
TPU).  Preemption parks cold pages without ever extracting a dense
slot; resume is a page-table patch plus a LATENCY prefetch.  The
admit/preempt/resume hot path performs **zero dense KV
re-materialisation** — ``extract_slot``/``insert_slot`` survive only on
the non-paged fallback, exactly the round-trip the AMU papers argue
against eliminating elsewhere.

**The storage layer is an explicit two-tier hierarchy**: the device
page pool (near tier) over ONE host
:class:`~repro.core.offload.FarMemoryTier` behind the pager.  Every
cold page is a page-granularity resident of that tier — preempted
pages via BULK writeback (or for free when the far copy's valid-token
tag is current), watermark-evicted pages via the pager's LRU
``balance`` loop that runs every tick the free-frame count sits under
the low watermark, and *finished* sequences' KV via the same shed
path (``offload_finished``; ``fetch_finished`` reassembles with
overlapped LATENCY aloads, discarding entries only after every
transfer verifiably landed).  There is no sequence-granularity side
store.

**Cross-request prefix sharing** (``prefix_cache=True``) sits on top:
full prompt pages are content-addressed by a rolling token-id hash
(:mod:`repro.paging.prefix_cache`) and interned at prefill
graduation; a later request whose prompt starts with the same tokens
maps its page-table rows onto the shared frames — refcounted + COW on
a device hit, one LATENCY page fetch on a far-tier hit — and its
prefill simply starts past them (``prefill_pos``), so a system prompt
shared by thousands of users costs one prefill.  Only the partial
boundary page and the unseen tail are computed; outputs stay
token-exact with the dense engine.

**Prefill is chunked and continuously batched** (``chunk_tokens``): the
last dense-KV hold-out — admit-then-scatter whole-prompt prefill — is
replaced by a *chunk queue*.  Admission installs a slot and page-table
bookkeeping only; the prompt is then computed in chunks **on the pool
layout** (:func:`~repro.models.model.prefill_chunk` scatters each
chunk's K/V straight into its mapped frames while flash-attending the
pool-resident prefix), fused with every running slot's decode token in
one jitted mixed step (:func:`~repro.dist.steps.make_mixed_step`).  The
scheduler picks chunk-vs-decode work off free-page watermarks and the
pager's LATENCY-window occupancy, and preemption can cancel a
half-prefilled sequence by parking its completed chunks — the prompt
remainder re-enters the chunk queue on resume.  A new request therefore
never serialises a dense-prefill bubble in front of running decodes:
the request-level massive parallelism the follow-up AMU paper
(2404.11044) targets.  With ``chunk_tokens=None`` (default) admission
falls back to the legacy whole-prompt dense prefill; both paths are
token-exact with a dense non-paged run.

Decode itself is mesh-sharded: the step function comes from
``repro.dist.steps.make_serve_step`` (TP-sharded params, paged-cache
PartitionSpecs) bound to the engine's mesh — a 1×1 mesh by default, the
production (data, model) mesh when one is passed in.  Decode runs with
a *fixed* batch of ``max_batch`` slots (one compiled program); per-slot
positions make the mixed-depth batch correct, and empty slots decode
garbage into a reserved *trash frame* that no live sequence maps — the
standard fixed-shape trade on TPU, made safe at page granularity.

**The engine is assembled from role components** (this module is the
assembly; the behaviour lives in the mixins):

  * :class:`~repro.serve.policy.SchedulerPolicy` /
    :class:`~repro.serve.policy.SLOScheduler` — every discretionary
    scheduling decision (``serve/policy.py``),
  * :class:`~repro.serve.admission.AdmissionMixin` — dense + chunked
    prefill admission, prefix mapping, and the DECODE-role
    ``admit_handoff`` (``serve/admission.py``),
  * :class:`~repro.serve.transfer.TransferMixin` — park/resume,
    room-making, finished-sequence offload/fetch, and the PREFILL-role
    handoff publish (``serve/transfer.py``),
  * :class:`~repro.serve.decode.DecodeMixin` — the step loop, chunk
    scheduling, graduation, and the finish path with the
    ``_role_done`` hook (``serve/decode.py``).

An :class:`~repro.serve.config.EngineRole` parameterises the assembly:
``FUSED`` (default) is the single-engine pipeline, bit-identical to the
pre-split engine; ``PREFILL``/``DECODE`` run two engines against ONE
shared far tier with the park/resume machinery pointed *across* them —
see :mod:`repro.serve.disagg` and ``docs/ARCHITECTURE.md``.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.steps import (make_mixed_step, make_serve_step,
                              paged_cache_specs)
from repro.kernels import ops
from repro.kernels.kv_quant import KVQuantConfig
from repro.launch.mesh import make_mesh_compat
from repro.models import ssm as ssm_mod
from repro.models.model import init_cache, init_paged_cache
from repro.obs import (MetricsRegistry, Tracer, to_chrome_trace,
                       write_chrome_trace, write_metrics)
from repro.paging import (DeadlineQueue, EventKind, EventLoop, PagePool,
                          PageState, PageTable, Pager, PagingError,
                          PrefixCache, WatermarkPolicy, pages_for)
from repro.serve.admission import AdmissionMixin
from repro.serve.config import EngineConfig, EngineRole, Tier, VirtualClock
from repro.serve.decode import DecodeMixin
from repro.serve.disagg import HandoffBoard
from repro.serve.kv_cache import SlotPool
from repro.serve.policy import SCHEDULERS as _SCHEDULERS
from repro.serve.policy import SchedulerPolicy, SLOScheduler
from repro.serve.request import Request
from repro.serve.speculate import NgramProposer
from repro.serve.transfer import TransferMixin

__all__ = ["Request", "Engine", "SchedulerPolicy", "SLOScheduler"]


class Engine(AdmissionMixin, TransferMixin, DecodeMixin):
    """Continuous-batching serving engine on the paged far-memory KV.

    The module docstring describes the design; operationally::

        eng = Engine(cfg, params, EngineConfig(
            max_batch=4, max_len=256,
            paging=PagingConfig(page_size=16,
                                device_pages=48),   # oversubscribed
            chunking=ChunkingConfig(chunk_tokens=32)))  # chunked prefill
        for p in prompts:
            eng.submit(p, max_new_tokens=16)
        outputs = eng.run()                           # {rid: tokens}

    Construction takes one frozen :class:`~repro.serve.config.
    EngineConfig`.  Knobs:
    ``paging.device_pages`` below ``max_batch * pages_per_seq``
    oversubscribes the pool (watermark admission + preemption, §2.3.2);
    ``chunking.chunk_tokens`` switches admission to the chunk queue
    (mixed prefill/decode steps); ``chunking.prefix_cache=True`` adds
    cross-request prefix sharing on top of it (content-addressed prompt
    pages; dense/moe global-attention families);
    ``paging.offload_finished`` parks finished sequences' pages in the
    far tier for later :meth:`fetch_finished` reuse;
    ``paging.enabled=False`` is the dense A/B reference;
    ``kernel_impl`` selects the paged-attention backend
    (``auto``/``pallas``/``interpret``/``xla``; on a TPU only ``auto``
    and ``pallas`` are accepted);
    ``paging.pager_factory`` injects a custom
    :class:`~repro.paging.Pager` (tests use a simulated-latency AMU
    backend); ``scheduler.policy="slo"`` switches scheduling from
    utilisation to goodput (see :class:`SLOScheduler`); ``role``
    selects the disaggregated half this engine runs (default
    ``"fused"`` — see :mod:`repro.serve.disagg`).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        config: Optional[EngineConfig] = None,
    ):
        ec = config or EngineConfig()
        if ops.on_tpu() and ec.kernel_impl in ("interpret", "xla"):
            # on a TPU the serving path runs the compiled Pallas kernels;
            # the interpreter and the XLA references stay callable for
            # comparisons, never as the engine's path
            raise ValueError(
                f"kernel_impl={ec.kernel_impl!r} on a TPU backend: the "
                "engine serves through the compiled Pallas kernels there "
                "(use 'auto' or 'pallas')")
        pg, ck, sc = ec.paging, ec.chunking, ec.scheduler
        max_batch, max_len = ec.max_batch, ec.max_len
        self.config = ec
        self.sched_cfg = sc
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.buckets = tuple(sorted(b for b in ec.prefill_buckets
                                    if b <= max_len)) or (max_len,)
        self.greedy = ec.greedy
        # ONE clock for every request timestamp (arrival, first token,
        # per-token, completion).  Default: an engine-owned VirtualClock
        # advanced by step_dt per tick, in lockstep with the pager's
        # simulated AMU — deterministic SLO measurement.  Injecting
        # e.g. time.monotonic opts into wall-clock telemetry.
        self.clock = sc.clock if sc.clock is not None else VirtualClock()
        self._own_clock = sc.clock is None
        # -- unified telemetry: one registry + one tracer on THE clock ------
        # (repro.obs; ec.obs.tracing turns span emission on — default off,
        # in which case every instrumented site costs one branch)
        self.metrics = MetricsRegistry()
        self.tracer = Tracer(clock=self.clock, enabled=ec.obs.tracing)
        self._phase_span: Dict[int, int] = {}    # rid -> open lifecycle sid
        self._obs_started: set = set()           # rids with a queued span
        self.pool = SlotPool(max_batch)
        self.queue: List[Request] = []
        self.active: Dict[int, Request] = {}     # slot -> request
        self.finished: Dict[int, Request] = {}
        self.offload_finished = pg.offload_finished
        # rid allocation is a plain counter (not itertools.count) so a
        # DECODE-role engine can bump it past handed-off rids — local
        # submissions and adopted requests share one id space
        self._next_rid = 0
        self._admits = itertools.count()

        self.mesh = ec.mesh if ec.mesh is not None else \
            make_mesh_compat((1, 1), ("data", "model"))
        # -- page-granularity KV residency over a fixed device pool --------
        # (decided before the decode step is built: the step consumes the
        # paged layout directly when the family has attention KV)
        page_size = pg.page_size
        shapes = jax.eval_shape(lambda: init_cache(cfg, max_batch, max_len))
        kv_shapes = shapes.kv if isinstance(shapes.kv, dict) else {}
        self.paging = ("k" in kv_shapes) if pg.enabled is None else \
            (pg.enabled and "k" in kv_shapes)
        self.page_size = page_size
        self.step_dt = sc.step_dt
        self.hot_tail_pages = max(0, pg.hot_tail_pages)
        self._resuming: Dict[int, Request] = {}
        # -- quantized paged frames (int8/fp8 + per-(frame, head) scales) --
        # resolved up front so the pool dtype, page byte accounting, and
        # the far-tier payload layout all agree; "none" keeps every path
        # bit-identical to the unquantized engine (no scale arrays exist)
        self.kv_quant = KVQuantConfig.from_name(pg.kv_quant)
        if self.kv_quant.enabled:
            if not self.paging:
                raise PagingError(
                    "kv_quant requires the paged engine: quantized frames "
                    "live in the device page pool")
            if cfg.family not in ("dense", "moe") or \
                    cfg.attention == "swa":
                raise PagingError(
                    "kv_quant supports global-attention dense/moe "
                    "families (append-only KV; a SWA ring wrap would "
                    "rewrite rows under a stale frame scale); got "
                    f"family={cfg.family!r} attention={cfg.attention!r}")
        if self.paging:
            k = kv_shapes["k"]
            self.slot_tokens = int(k.shape[2])       # ring size for SWA
            if self.slot_tokens % page_size:
                raise PagingError(
                    f"page_size {page_size} must divide the per-sequence "
                    f"token capacity {self.slot_tokens}")
            self.pages_per_seq = self.slot_tokens // page_size
            n_pages = pg.device_pages if pg.device_pages is not None \
                else max_batch * self.pages_per_seq
            if self.kv_quant.enabled:
                # quantized frames: k+v payload bytes at the pool dtype
                # plus the per-(layer, head) f32 scale pair that rides
                # every page transfer
                page_nbytes = int(
                    2 * k.shape[0] * page_size * k.shape[3] * k.shape[4]
                    * self.kv_quant.itemsize
                    + 2 * k.shape[0] * k.shape[3] * 4)
            else:
                page_nbytes = int(2 * k.shape[0] * page_size * k.shape[3]
                                  * k.shape[4] * k.dtype.itemsize)
            self.page_pool = PagePool(n_pages, page_size)
            self.page_table = PageTable(self.page_pool)
            if pg.pager_factory is not None:
                self.pager = pg.pager_factory(self.page_pool,
                                              self.page_table,
                                              page_nbytes=page_nbytes)
            else:
                self.pager = Pager(self.page_pool, self.page_table,
                                   page_nbytes=page_nbytes)
            if self.pager.read_frame is None:    # keep a factory's hook
                self.pager.read_frame = self._read_frame
            # adopt the pager (factory-built or not) into the engine's
            # registry + tracer: its ad-hoc stats migrate into the
            # "pager" counter group and its page table emits on the
            # engine clock.  The AMU's simulated ticks are that clock
            # only when the engine owns it: on an injected clock the
            # AMU is left unbound, its spans and latencies not wall time
            self.pager.bind_obs(self.metrics, self.tracer,
                                amu=self._own_clock)
            # THE far tier: one FarMemoryTier behind the pager holds
            # every cold page — preempted, watermark-evicted, finished —
            # plus finished sequences' aux residues and the prefix
            # cache's shared page homes.  Under disaggregation the
            # pager_factory points two engines' pagers at ONE shared
            # tier (see repro.serve.disagg.tier_pager_factory).
            self.far_tier = self.pager.tier
            # device frames: pool frames + one trash frame at the end;
            # made in the shardings the step programs take, so a pool
            # sharded over the mesh is never whole on one device
            self.trash_frame = n_pages
            self.cache: Any = init_paged_cache(
                cfg, max_batch, max_len, n_frames=n_pages + 1,
                page_size=page_size,
                quant=self.kv_quant if self.kv_quant.enabled else None,
                shardings={name: NamedSharding(self.mesh, spec)
                           for name, spec in
                           paged_cache_specs(self.mesh, cfg).items()})
            self._pt_np = np.full((max_batch, self.pages_per_seq),
                                  self.trash_frame, np.int32)
            self._pt_dirty = True
        else:
            self.slot_tokens = 0
            self.page_pool = self.page_table = self.pager = None
            self.far_tier = None
            self.cache = init_cache(cfg, max_batch, max_len)
        if self.offload_finished and not self.paging:
            raise PagingError(
                "offload_finished requires the paged engine: finished KV "
                "is parked page-by-page through the pager's far tier")
        # -- engine role: which half of the pipeline this assembly runs ----
        self.role = EngineRole(ec.role)
        if self.role is not EngineRole.FUSED and not self.paging:
            raise PagingError(
                "disaggregated roles require the paged engine: the "
                "prefill/decode handoff travels through the far tier")
        if self.role is EngineRole.PREFILL:
            # graduation IS an offload_finished park into the (shared)
            # far tier — the role implies the flag
            self.offload_finished = True
        self.handoff = ec.handoff
        if self.role is EngineRole.PREFILL and self.handoff is None:
            self.handoff = HandoffBoard()
        self.policy = pg.watermark or WatermarkPolicy(low=0, critical=0)
        # the scheduling-policy layer: every discretionary decision
        # (queue order, victim, chunk order, per-request QoS) goes
        # through self.sched — see SchedulerPolicy / SLOScheduler
        if sc.policy not in _SCHEDULERS:
            raise PagingError(
                f"unknown scheduler policy {sc.policy!r}; "
                f"expected one of {sorted(_SCHEDULERS)}")
        self.sched = _SCHEDULERS[sc.policy](self)
        self.deadlines = DeadlineQueue()

        # -- mesh-sharded decode step (dist.steps, not a raw jit) ----------
        shape = ShapeConfig("serve_engine", max_len, max_batch, "decode")
        # cache donated: the step aliases the pool frames in place —
        # no per-token copy of the KV pool (self.cache is rebound to the
        # step's output immediately, so the donation is safe)
        self._decode, self._decode_specs = make_serve_step(
            cfg, self.mesh, shape, donate=True, paged=self.paging,
            kernel_impl=ec.kernel_impl)
        self._prefills: Dict[Any, Any] = {}

        # -- chunk-queue admission (chunked paged prefill) ------------------
        # admission installs page-table rows only; prompts are then fed
        # through the mixed step in chunks that interleave with decode
        self.chunk_tokens = int(ck.chunk_tokens) if ck.chunk_tokens else 0
        self.chunk_slots = max(1, int(ck.chunk_slots))
        self.chunking = bool(self.chunk_tokens) and self.paging
        self.prefilling: Dict[int, Request] = {}     # slot -> admitting req
        if self.chunking:
            self._mixed, self._mixed_specs = make_mixed_step(
                cfg, self.mesh, shape, donate=True,
                kernel_impl=ec.kernel_impl)
            if cfg.family == "hybrid":
                s = ssm_mod.mamba2_state_init(cfg, 1)
                self._zero_chunk_ssm = jax.tree_util.tree_map(
                    lambda a: np.zeros((cfg.num_layers,) + a.shape,
                                       np.asarray(a).dtype), s)

        # -- cross-request prefix sharing (content-addressed prompt pages)
        # full prompt pages are interned by rolling token-id hash at
        # prefill graduation; later requests map their page-table rows
        # onto the shared frames (device hit) or fetch a private copy
        # with a LATENCY aload (far hit) and skip those prefill chunks.
        # Supported where the shared KV is position- and content-exact
        # for every sharer: global-attention dense/moe (append-only KV,
        # absolute rope; SWA ring wrap rewrites pages in place, and
        # hybrid/encdec carry non-KV per-request prefix state).
        self.prefix: Optional[PrefixCache] = None
        if ck.prefix_cache:
            if not self.chunking:
                raise PagingError(
                    "prefix_cache requires chunked paged admission "
                    "(chunk_tokens > 0 on the paged engine)")
            if cfg.family not in ("dense", "moe") or \
                    cfg.attention == "swa":
                raise PagingError(
                    "prefix_cache supports global-attention dense/moe "
                    f"families; got family={cfg.family!r} "
                    f"attention={cfg.attention!r}")
            self.prefix = PrefixCache(self.page_pool, self.page_table,
                                      self.pager, page_size)

        # -- draft-free self-speculative decode (verify-K) ------------------
        # an n-gram prompt-lookup proposer drafts up to K tokens per slot
        # from the slot's own committed history; one jitted verify step
        # scores all drafts through the multi-query paged kernel, and
        # greedy acceptance keeps the stream token-exact with single-step
        # decode.  Same family gate as the prefix cache: append-only KV,
        # absolute rope (SWA ring wrap would rewrite rolled-back pages).
        sp = ec.speculation
        self.speculate_k = int(sp.speculate_k or 0)
        self.speculating = self.speculate_k > 0
        self.proposer = None
        if self.speculating:
            if not self.paging:
                raise PagingError(
                    "speculative decode requires the paged engine "
                    "(verify-K scatters through the page table)")
            if cfg.family not in ("dense", "moe") or \
                    cfg.attention == "swa":
                raise PagingError(
                    "speculative decode supports global-attention "
                    f"dense/moe families; got family={cfg.family!r} "
                    f"attention={cfg.attention!r}")
            if self.role is EngineRole.PREFILL:
                raise PagingError(
                    "a PREFILL-role engine never decodes past the first "
                    "token — speculation has nothing to draft")
            if sp.proposer_factory is not None:
                self.proposer = sp.proposer_factory(sp.speculate_ngram,
                                                    self.speculate_k)
            else:
                self.proposer = NgramProposer(n=sp.speculate_ngram,
                                              k=self.speculate_k)
            self._verify, self._verify_specs = make_serve_step(
                cfg, self.mesh, shape, donate=True, paged=True,
                kernel_impl=ec.kernel_impl, speculate_k=self.speculate_k)
            if self.chunking:
                self._mixed_verify, _ = make_mixed_step(
                    cfg, self.mesh, shape, donate=True,
                    kernel_impl=ec.kernel_impl,
                    speculate_k=self.speculate_k)

        self.events = EventLoop(metrics=self.metrics)
        self.events.on(EventKind.TICK, self._on_tick)
        self.events.on(EventKind.PAGE_ARRIVED, self._on_page_arrived)
        self.events.on(EventKind.COMPLETE, self._on_complete)
        self.events.on(EventKind.DEADLINE, self._on_deadline)
        # dict-compatible view onto the shared registry ("engine" group):
        # callers keep reading eng.stats["preemptions"] etc. unchanged
        initial = {"steps": 0, "prefills": 0, "admitted": 0,
                   "preemptions": 0, "resumes": 0, "mixed_steps": 0,
                   "chunks": 0, "prefill_preempts": 0,
                   "prefix_hits": 0, "prefix_tokens_saved": 0,
                   "prefix_far_hits": 0, "deadline_misses": 0,
                   "slo_attained": 0, "slo_missed": 0,
                   "shed_admissions": 0}
        if self.role is not EngineRole.FUSED:
            initial["handoffs"] = 0      # FUSED snapshots stay unchanged
        if self.speculating:
            # seeded only when speculation is on, so non-speculative
            # snapshots (and the bench baselines) stay byte-identical
            initial.update({"spec_steps": 0, "drafted": 0,
                            "accepted": 0, "rejected": 0})
        self.stats = self.metrics.counters("engine", initial=initial)

    # -- public API ----------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               src_embeds: Optional[np.ndarray] = None,
               tier: Tier = Tier.INTERACTIVE,
               ttft_slo: Optional[float] = None,
               tpot_slo: Optional[float] = None,
               arrival_t: Optional[float] = None) -> int:
        """Queue one request.  SLO fields: ``tier`` picks the priority
        class (maps to pager QoS under the SLO scheduler), ``ttft_slo``
        / ``tpot_slo`` override the :class:`SchedulerConfig` defaults,
        and ``arrival_t`` places the request on the virtual-clock time
        axis (a trace replay submits the whole workload up front; the
        engine admits nothing before its arrival time).  Defaults
        reproduce the old behaviour: arrive now, no SLOs."""
        prompt = np.asarray(prompt, np.int32)
        if self.paging:
            # a PREFILL-role engine never decodes: its pool only ever
            # holds the prompt's pages, so the completion horizon is the
            # prompt alone
            horizon = len(prompt) + (
                0 if self.role is EngineRole.PREFILL else max_new_tokens)
            full = pages_for(min(horizon, self.slot_tokens),
                             self.page_size)
            if full > self.page_pool.n_pages:
                raise PagingError(
                    f"request needs {full} pages; pool has only "
                    f"{self.page_pool.n_pages} — it could never complete")
            # admission only ever needs the prompt's pages (growth is
            # exempt from the low watermark) — reject what can't admit
            admit = pages_for(min(len(prompt), self.slot_tokens),
                              self.page_size)
            if admit + self.policy.low > self.page_pool.n_pages:
                raise PagingError(
                    f"request needs {admit} pages at admission; pool of "
                    f"{self.page_pool.n_pages} under low watermark "
                    f"{self.policy.low} can never admit it")
        rid = self._next_rid
        self._next_rid += 1
        now = self.clock()
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      src_embeds=src_embeds, submitted_t=now,
                      tier=Tier(tier),
                      ttft_slo=(ttft_slo if ttft_slo is not None
                                else self.sched_cfg.ttft_slo),
                      tpot_slo=(tpot_slo if tpot_slo is not None
                                else self.sched_cfg.tpot_slo),
                      arrival_t=now if arrival_t is None else arrival_t)
        self.queue.append(req)
        self.sched.on_submit(req)
        return rid

    @property
    def drained(self) -> bool:
        """No work anywhere: queue, batch, chunk queue and resume set
        all empty (a disaggregated driver polls this per engine)."""
        return not (self.queue or self.active or self._resuming
                    or self.prefilling)

    def step_once(self) -> None:
        """One iteration of the serving loop: admit, step, tick, and the
        stall handling that keeps the loop progressing.  Public so a
        disaggregated driver (:func:`~repro.serve.disagg.
        run_disaggregated`) can interleave two engines; :meth:`run` is
        this in a drain loop."""
        with self.tracer.span("engine", "step"):
            self._admit()
            if self.active or self.prefilling:
                self._step()
            with self.tracer.span("engine", "tick"):
                self.events.tick()
            if not self.active and not self.prefilling and self._resuming:
                # nothing decodable: land the in-flight pages, then
                # demand-fetch the head resume so the loop always
                # progresses (its misses may evict other resumes' pages)
                with self.tracer.span("engine", "resume"):
                    for req in list(self._resuming.values()):
                        self.pager.wait_arriving(req.rid)
                    self.pager.wait_seq(
                        next(iter(self._resuming.values())).rid)
                self._admit()
            if not self.active and not self.prefilling \
                    and not self._resuming and self.queue:
                # everything just finished this step: retry admission
                # now rather than waiting for the next iteration
                self._admit()
                if not self.active and not self.prefilling \
                        and not self._resuming:
                    future = [r.arrival_t for r in self.queue
                              if r.arrival_t > self.clock()]
                    if future and len(future) == len(self.queue):
                        # the system is idle only because the trace is:
                        # fast-forward the virtual clock to the next
                        # arrival (a wall clock advances by itself)
                        if self._own_clock:
                            self.clock.advance(min(future) - self.clock())
                        return
                    # nothing running and nothing in flight: the state
                    # can never change, so admission is blocked for
                    # good — fail loudly instead of spinning to max_steps
                    raise PagingError(
                        f"{len(self.queue)} queued requests can never be "
                        "admitted (free pages "
                        f"{self.page_pool.n_free if self.paging else 'n/a'}"
                        f", low watermark {self.policy.low})")

    def run(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        """Event loop until every submitted request completes.

        Example (8 requests through 3 slots, continuous batching)::

            eng = Engine(cfg, params, EngineConfig(
                max_batch=3, max_len=64,
                chunking=ChunkingConfig(chunk_tokens=8)))
            rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
            outputs = eng.run()          # {rid: [token, ...]}
        """
        for _ in range(max_steps):
            if self.drained:
                break
            self.step_once()
        if self.drained:
            # fully drained: the telemetry counters must balance
            self.check_invariants()
        ob = self.config.obs
        if ob.trace_out:
            self.export_trace(ob.trace_out)
        if ob.metrics_out:
            self.export_metrics(ob.metrics_out)
        return {r.rid: r.generated for r in self.finished.values()}

    # -- event handlers -------------------------------------------------------
    def _on_tick(self, ev) -> None:
        # the engine-owned virtual clock advances here, by step_dt, in
        # lockstep with the pager's simulated backend below — one time
        # axis for transfers AND request telemetry
        if self._own_clock:
            self.clock.advance(self.step_dt)
        for t, rid in self.deadlines.pop_due(self.clock()):
            self.events.post(EventKind.DEADLINE, (t, rid))
        if self.pager is None:
            return
        for seq, logical in self.pager.advance(self.step_dt):
            self.events.post(EventKind.PAGE_ARRIVED, (seq, logical))
        # capacity pressure: when free frames sit under the low
        # watermark, push cold RESIDENT pages (parked hot tails, idle
        # prefix-cache frames) to the far tier *now*, so the BULK
        # astores overlap decode instead of serialising inside the next
        # admission's _make_room
        if self.policy.low:
            self.pager.balance(self.policy.low)

    def _on_page_arrived(self, ev) -> None:
        seq, logical = ev.payload
        pte = self.page_table.entry(seq, logical)
        if pte.state is PageState.RESIDENT:
            self._land_frame(pte.phys)       # scatter into the device pool
            self.page_pool.touch(pte.phys)

    def _on_complete(self, ev) -> None:
        rid = ev.payload
        if self.paging and rid in self.page_table.sequences():
            self.page_table.drop(rid)
            if not self.offload_finished:
                # offloaded sequences keep their far-tier pages: that IS
                # the finished-KV store fetch_finished (or a DECODE-role
                # peer's admit_handoff) reads back
                self.pager.drop_far(rid)

    def _on_deadline(self, ev) -> None:
        """A TTFT deadline passed.  If the request still has no first
        token it has missed its SLO *now* — count it while it is still
        schedulable, so preemption's already-blown preference and the
        telemetry agree in real time rather than post hoc."""
        t, rid = ev.payload
        req = self.finished.get(rid)
        if req is None:
            for r in itertools.chain(self.queue, self.active.values(),
                                     self.prefilling.values(),
                                     self._resuming.values()):
                if r.rid == rid:
                    req = r
                    break
        if req is not None and not req.token_ts:
            self.stats["deadline_misses"] += 1
            if self.tracer.enabled:
                self.tracer.instant("engine", "sched", "deadline_miss",
                                    {"rid": rid, "tier": req.tier.name,
                                     "deadline": t})

    # -- telemetry ------------------------------------------------------------
    def _obs_phase(self, req: Request, name: Optional[str]) -> None:
        """Advance a request's lifecycle track: close its current phase
        span and open ``name`` (None just closes — the finish path).
        The first phase a request ever enters also back-fills a
        ``queued`` span covering arrival → now, so the Perfetto track
        reads arrival → admit → prefill/decode → … end to end."""
        tr = self.tracer
        if not tr.enabled:
            return
        tid = f"req{req.rid}"
        if req.rid not in self._obs_started:
            self._obs_started.add(req.rid)
            tr.complete("requests", tid, "queued", req.arrival_t,
                        args={"tier": req.tier.name})
        tr.end(self._phase_span.pop(req.rid, 0))
        if name is not None:
            self._phase_span[req.rid] = tr.begin(
                "requests", tid, name, {"tier": req.tier.name})

    def check_invariants(self) -> None:
        """Cross-layer conservation checks over the telemetry counters.

        * preemptions == resumes + requests *currently* parked by a
          preemption (a prefix-far admission parks without one, so only
          ``n_preempts > 0`` requests count),
        * ADMIT events == admissions + resumes (every ADMIT post has
          exactly one matching stats increment),
        * on a PREFILL role: HANDOFF events == published handoffs,
        * speculating: accepted + rejected == drafted (every drafted
          token is adjudicated exactly once), and no active slot's
          valid tokens exceed its scattered (mapped) frames,
        * the pager's per-QoS window takes/releases balance its
          in-flight gauges (see :meth:`Pager.check_invariants`).
        """
        s = self.stats
        if self.speculating:
            if s["accepted"] + s["rejected"] != s["drafted"]:
                raise PagingError(
                    f"speculation imbalance: {s['accepted']} accepted + "
                    f"{s['rejected']} rejected != {s['drafted']} drafted")
            if self.paging:
                pos_np = np.asarray(self.cache.pos)
                for slot, req in self.active.items():
                    covered = self.page_table.n_pages(req.rid) \
                        * self.page_size
                    if int(pos_np[slot]) > covered:
                        raise PagingError(
                            f"rid {req.rid}: valid tokens "
                            f"{int(pos_np[slot])} exceed scattered frames "
                            f"({covered} positions mapped)")
        pending = sum(
            1 for r in itertools.chain(self.queue, self._resuming.values())
            if r.parked and r.n_preempts > 0)
        if s["preemptions"] != s["resumes"] + pending:
            raise PagingError(
                f"preempt/resume imbalance: {s['preemptions']} preemptions "
                f"!= {s['resumes']} resumes + {pending} currently parked")
        admits = self.events.history.get(EventKind.ADMIT, 0)
        if admits != s["admitted"] + s["resumes"]:
            raise PagingError(
                f"ADMIT event imbalance: {admits} events != "
                f"{s['admitted']} admissions + {s['resumes']} resumes")
        if self.role is EngineRole.PREFILL:
            hoffs = self.events.history.get(EventKind.HANDOFF, 0)
            if hoffs != s["handoffs"]:
                raise PagingError(
                    f"HANDOFF event imbalance: {hoffs} events != "
                    f"{s['handoffs']} published handoffs")
        if self.pager is not None:
            self.pager.check_invariants()

    def export_trace(self, path: Optional[str] = None) -> dict:
        """Chrome-trace/Perfetto JSON of everything traced so far (AMU
        transfers, pager actions, residency flips, request lifecycle —
        one virtual time axis).  Writes to ``path`` when given."""
        if path is not None:
            write_chrome_trace(path, self.tracer, metrics=self.metrics)
        return to_chrome_trace(self.tracer, metrics=self.metrics)

    def export_metrics(self, path: Optional[str] = None) -> dict:
        """Flat JSON snapshot of every counter/gauge/histogram."""
        if path is not None:
            write_metrics(path, self.metrics)
        return self.metrics.snapshot()
