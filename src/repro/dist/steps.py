"""Jitted, donated, mesh-sharded step factories (train / prefill / serve).

Each ``make_*_step`` returns ``(fn, specs)``:

  * ``fn`` — a callable that enters the mesh context and invokes the
    underlying ``jax.jit``; it also exposes ``.lower(*abstract_args)``
    so a step can be compiled from abstract arguments without
    allocating,
  * ``specs`` — the PartitionSpec trees (``params`` / ``opt`` /
    ``batch``) the caller uses to place inputs.

Sharding is enforced *inside* the step via ``with_sharding_constraint``
(callers may hand in replicated arrays — restore/elastic paths do), and
train outputs carry explicit ``out_shardings`` so donation lines up and
updated parameters stay TP/ZeRO-sharded across steps.

``abstract_params`` / ``abstract_opt_state`` build ``ShapeDtypeStruct``
pytrees — nothing is allocated — for spec construction.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.dist import act_sharding as acts
from repro.dist.sharding import batch_specs, opt_state_specs, param_specs
from repro.models import model as model_mod
from repro.optim.adamw import adamw_init, adamw_update

__all__ = [
    "make_train_step", "make_prefill_step", "make_serve_step",
    "make_mixed_step", "abstract_params", "abstract_opt_state",
    "init_params_sharded", "paged_cache_specs",
]


# -- abstract inputs (ShapeDtypeStruct pytrees; nothing allocated) --------------

def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda k: model_mod.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def init_params_sharded(cfg: ModelConfig, mesh, key):
    """Random parameters created straight into their :func:`param_specs`
    shardings on ``mesh``: each device generates only its own shards,
    so a model larger than one device's memory never lands on one."""
    pspecs = param_specs(mesh, abstract_params(cfg))
    init = jax.jit(lambda k: model_mod.init_params(cfg, k),
                   out_shardings=_named(mesh, pspecs))
    return init(key)


def abstract_opt_state(cfg: ModelConfig):
    return jax.eval_shape(adamw_init, abstract_params(cfg))


def paged_cache_specs(mesh, cfg: ModelConfig) -> Dict[str, P]:
    """PartitionSpecs for the PagedCache pool layout.

    Frames are ``(L, N, page, Hkv, D)``: the KV-head axis shards over
    ``model`` exactly like the dense per-slot cache would, provided the
    head count divides the axis; otherwise the pool replicates (the
    page table and positions always do — they are tiny int32 control
    state every shard needs whole, like the paper's APRs).

    Cross-request prefix sharing does not change these specs: a shared
    frame is the same ``N``-axis row read by several slots' page-table
    rows, and the frame axis is never sharded — only the KV-head axis
    inside a frame is.  Sharing interacts with *donation* instead; see
    the audit note on :func:`make_serve_step`.
    """
    model_size = mesh.shape.get("model", 1)
    sharded = model_size > 1 and cfg.num_kv_heads % model_size == 0
    pages = P(None, None, None, "model", None) if sharded else P()
    # quantized pools carry per-(layer, frame, head) f32 scales beside
    # the frames; their head axis shards exactly like the frames' does.
    # The entries are harmless when the cache has no scale arrays — the
    # constraint loops below skip names absent from cache.kv.
    scales = P(None, None, "model") if sharded else P()
    return {"k_pages": pages, "v_pages": pages, "page_table": P(),
            "k_scales": scales, "v_scales": scales}


# -- shared plumbing -----------------------------------------------------------

def _policy_for(act_policy: Optional[acts.ActPolicy],
                tcfg: Optional[TrainConfig] = None) -> acts.ActPolicy:
    if act_policy is not None:
        return act_policy
    if tcfg is not None and tcfg.act_sharding == "optimized":
        return acts.OPTIMIZED
    return acts.BASELINE


def _constrain_tree(tree, specs, mesh):
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)),
        tree, specs)


def _named(mesh, specs):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)


class _MeshedStep:
    """Jitted step bound to its mesh: entering the mesh context at call
    time makes the thread-local mesh visible to trace-time policy code
    (``act_sharding.constrain``) even when the caller sits outside any
    ``with mesh:`` block (the training loop does)."""

    def __init__(self, fn, mesh):
        self._fn = fn
        self.mesh = mesh

    def __call__(self, *args):
        with self.mesh:
            return self._fn(*args)

    def lower(self, *args):
        with self.mesh:
            return self._fn.lower(*args)


# -- train ---------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh,
                    shape: ShapeConfig, *, donate: bool = True,
                    act_policy: Optional[acts.ActPolicy] = None):
    """Build the sharded train step: ``fn(params, opt, batch) ->
    (params, opt, metrics)`` with gradient accumulation over
    ``tcfg.microbatches`` and optional bf16 gradient compression."""
    pshapes = abstract_params(cfg)
    pspecs = param_specs(mesh, pshapes)
    ospecs = opt_state_specs(mesh, pshapes, zero1=tcfg.zero1)
    bspecs = batch_specs(mesh, cfg, shape)
    pol = _policy_for(act_policy, tcfg)
    k = max(1, tcfg.microbatches)
    if shape.global_batch % k:
        raise ValueError(
            f"microbatches ({k}) must divide the global batch "
            f"({shape.global_batch})")

    def _compress(g):
        if tcfg.grad_compression == "bf16":
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), g)
        return g

    def loss_fn(p, mb):
        return model_mod.train_loss(p, cfg, mb, remat=tcfg.remat)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params, opt, batch):
        params = _constrain_tree(params, pspecs, mesh)
        opt = _constrain_tree(opt, ospecs, mesh)
        batch = _constrain_tree(batch, bspecs, mesh)
        with acts.policy(pol):
            if k == 1:
                (_, metrics), grads = grad_fn(params, batch)
                grads = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), _compress(grads))
            else:
                def micro(acc, mb):
                    (_, m), g = grad_fn(params, mb)
                    acc = jax.tree_util.tree_map(
                        lambda a, x: a + x.astype(jnp.float32), acc,
                        _compress(g))
                    return acc, m

                mbatch = jax.tree_util.tree_map(
                    lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                    batch)
                acc0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                grads, metrics = jax.lax.scan(micro, acc0, mbatch)
                grads = jax.tree_util.tree_map(lambda g: g / k, grads)
                metrics = jax.tree_util.tree_map(lambda m: m.mean(), metrics)
            new_p, new_opt, opt_metrics = adamw_update(grads, opt, params,
                                                       tcfg)
        return new_p, new_opt, {**metrics, **opt_metrics}

    fn = jax.jit(
        step,
        donate_argnums=(0, 1) if donate else (),
        out_shardings=(_named(mesh, pspecs), _named(mesh, ospecs),
                       NamedSharding(mesh, P())))
    specs = {"params": pspecs, "opt": ospecs, "batch": bspecs}
    return _MeshedStep(fn, mesh), specs


# -- inference -----------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, mesh, shape: ShapeConfig, *,
                      act_policy: Optional[acts.ActPolicy] = None,
                      max_len: Optional[int] = None):
    """Build the sharded prefill: ``fn(params, batch) -> (logits, cache)``.

    The cache is sized to ``max_len`` (default: the shape's sequence
    length) so the serve step built from the same shape accepts it."""
    pshapes = abstract_params(cfg)
    pspecs = param_specs(mesh, pshapes)
    bspecs = batch_specs(mesh, cfg, shape)
    pol = _policy_for(act_policy)
    cache_len = max_len or shape.seq_len

    def step(params, batch):
        params = _constrain_tree(params, pspecs, mesh)
        with acts.policy(pol):
            return model_mod.prefill(params, cfg, batch, max_len=cache_len)

    fn = jax.jit(step)
    return _MeshedStep(fn, mesh), {"params": pspecs, "batch": bspecs}


def make_serve_step(cfg: ModelConfig, mesh, shape: ShapeConfig, *,
                    donate: bool = True,
                    act_policy: Optional[acts.ActPolicy] = None,
                    paged: bool = False, kernel_impl: str = "auto",
                    speculate_k: int = 0):
    """Build the sharded one-token decode: ``fn(params, cache, tokens) ->
    (logits, cache)`` with the cache donated (in-place KV update).

    With ``paged=True`` the step consumes a
    :class:`~repro.models.model.PagedCache` — decode computes directly
    on the page-pool layout, with the pool arrays mesh-constrained via
    :func:`paged_cache_specs` so the sharded serve step reads frames
    without a resharding collective.  ``kernel_impl`` selects the
    paged-attention backend (``auto``: the Pallas gather kernel on TPU,
    the XLA gather elsewhere).

    With ``speculate_k > 0`` (paged only) the step is the speculative
    **verify-K branch** instead: ``fn(params, cache, tokens, length) ->
    (logits, cache)`` with ``tokens`` (B, K+1) — the last committed
    token plus K drafts per slot — ``length`` (B,) the valid rows, and
    ``logits`` (B, K+1, V) scoring every draft in one jitted program
    (:func:`~repro.models.model.verify_step`).  ``cache.pos`` is NOT
    advanced; the engine decides acceptance host-side and writes the
    rewound positions back.

    Donation audit (prefix sharing): the cache is donated, so the pool
    frames update *in place* — with refcounted shared frames this is
    safe only because no live schedule ever routes a write at a frame
    with more than one mapping: decode scatters at ``pos``, which lies
    strictly past every shared (full, interned) page; empty slots write
    the trash frame; and the engine's COW guard
    (``Engine._ensure_private``) remaps before any write that would
    violate this.  Reads of a shared frame from several slots in one
    step are unordered but read-only — no aliasing hazard.  The verify
    branch widens the write window to ``[pos, pos + length)``: still
    strictly past the shared prefix (``pos`` never rewinds into it),
    and the engine extends the COW guard over the whole draft range
    before speculating (``_ensure_growth``'s draft-aware pass)."""
    pshapes = abstract_params(cfg)
    pspecs = param_specs(mesh, pshapes)
    pol = _policy_for(act_policy)
    cspecs = paged_cache_specs(mesh, cfg) if paged else None
    if speculate_k and not paged:
        raise ValueError("speculate_k requires the paged serve step")

    def _constrain_cache(cache):
        kv = dict(cache.kv)
        for name, spec in cspecs.items():
            if name not in kv:
                continue                 # scale arrays exist only quantized
            kv[name] = jax.lax.with_sharding_constraint(
                kv[name], NamedSharding(mesh, spec))
        return cache._replace(kv=kv)

    # the function's name names the program's module in a profile
    # (jit_serve_decode, jit_serve_verify): device time splits by program
    if speculate_k:
        def serve_verify(params, cache, tokens, length):
            params = _constrain_tree(params, pspecs, mesh)
            cache = _constrain_cache(cache)
            with acts.policy(pol):
                return model_mod.verify_step(params, cfg, cache, tokens,
                                             length, impl=kernel_impl)
        step = serve_verify
    else:
        def serve_decode(params, cache, tokens):
            params = _constrain_tree(params, pspecs, mesh)
            if cspecs is not None:
                cache = _constrain_cache(cache)
            with acts.policy(pol):
                return model_mod.decode_step(params, cfg, cache, tokens,
                                             impl=kernel_impl)
        step = serve_decode

    fn = jax.jit(step, donate_argnums=(1,) if donate else ())
    specs = {"params": pspecs}
    if cspecs is not None:
        specs["paged_cache"] = cspecs
    return _MeshedStep(fn, mesh), specs


def make_mixed_step(cfg: ModelConfig, mesh, shape: ShapeConfig, *,
                    donate: bool = True,
                    act_policy: Optional[acts.ActPolicy] = None,
                    kernel_impl: str = "auto", speculate_k: int = 0):
    """Build the continuously-batched serve step: one decode token for
    every running slot **fused with** one paged prompt chunk for up to C
    admitting slots, in a single jitted, donated, mesh-bound program —
    ``fn(params, cache, tokens, chunk) -> (logits, chunk_logits,
    chunk_carry, cache)``.

    The decode half is exactly :func:`make_serve_step`'s paged program
    (same ``decode_step`` trace, so running slots' tokens are unchanged
    by the fusion); the chunk half is
    :func:`~repro.models.model.prefill_chunk`, which scatters the
    chunk's K/V into its page-table-mapped pool frames and attends the
    pool-resident prefix.  Fusing them is the serving-level version of
    the paper's overlap thesis: admission work rides the same step that
    keeps every running sequence's decode in flight, so a new request
    never serialises a dense-prefill bubble in front of running decodes.

    The cache is donated (pool frames update in place); the paged-cache
    pool arrays are mesh-constrained via :func:`paged_cache_specs`, and
    the chunk's control state (tokens, offsets, page rows) is replicated
    like the page table — tiny int32 state every shard needs whole, the
    APR analogue.  ``chunk`` layouts are documented on
    :func:`~repro.models.model.prefill_chunk`; jit re-specialises per
    (chunk rows, chunk length) shape, which the engine keeps to a small
    fixed set.

    Donation audit (prefix sharing): chunk rows may point at shared
    (prefix-cache) frames for the resident prefix — those are gathered
    read-only; the chunk's own K/V scatter lands at
    ``[offset, offset + length)``, which starts past the shared pages
    by construction (``prefill_pos`` skips them), so the in-place
    update never writes a multi-mapped frame.  See
    :func:`make_serve_step` for the decode half of the audit.

    With ``speculate_k > 0`` the decode half becomes the speculative
    verify-K branch (``fn(params, cache, tokens, length, chunk)`` with
    ``tokens`` (B, K+1), ``logits`` (B, K+1, V), positions host-owned —
    see :func:`make_serve_step`); the chunk half is byte-identical to
    the non-speculative program, so admitting slots' graduation logits
    are unchanged by the fusion either way.
    """
    pshapes = abstract_params(cfg)
    pspecs = param_specs(mesh, pshapes)
    pol = _policy_for(act_policy)
    cspecs = paged_cache_specs(mesh, cfg)

    def _constrain(params, cache, chunk):
        params = _constrain_tree(params, pspecs, mesh)
        kv = dict(cache.kv)
        for name, spec in cspecs.items():
            if name not in kv:
                continue                 # scale arrays exist only quantized
            kv[name] = jax.lax.with_sharding_constraint(
                kv[name], NamedSharding(mesh, spec))
        cache = cache._replace(kv=kv)
        chunk = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P())), chunk)
        return params, cache, chunk

    # named as its module appears in a profile (see make_serve_step)
    if speculate_k:
        def serve_mixed_verify(params, cache, tokens, length, chunk):
            params, cache, chunk = _constrain(params, cache, chunk)
            with acts.policy(pol):
                logits, cache = model_mod.verify_step(
                    params, cfg, cache, tokens, length, impl=kernel_impl)
                chunk_logits, cache, carry = model_mod.prefill_chunk(
                    params, cfg, cache, chunk, impl=kernel_impl)
            return logits, chunk_logits, carry, cache
        step = serve_mixed_verify
    else:
        def serve_mixed(params, cache, tokens, chunk):
            params, cache, chunk = _constrain(params, cache, chunk)
            with acts.policy(pol):
                logits, cache = model_mod.decode_step(
                    params, cfg, cache, tokens, impl=kernel_impl)
                chunk_logits, cache, carry = model_mod.prefill_chunk(
                    params, cfg, cache, chunk, impl=kernel_impl)
            return logits, chunk_logits, carry, cache
        step = serve_mixed

    fn = jax.jit(step, donate_argnums=(1,) if donate else ())
    return _MeshedStep(fn, mesh), {"params": pspecs, "paged_cache": cspecs}
