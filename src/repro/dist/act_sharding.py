"""Scoped activation-sharding / precision policy.

A policy is a small frozen value object; the *active* policy is a
dynamically-scoped stack entry (``with policy(OPTIMIZED): ...``) read by
the model layers at trace time.  Two named instances:

  * :data:`BASELINE`  — paper-faithful run: f32 einsum operands, no
    explicit activation layouts (GSPMD decides everything from the
    parameter shardings),
  * :data:`OPTIMIZED` — the beyond-paper perf path: operands stay in the
    native compute dtype (f32 accumulation), attention layouts are
    constrained explicitly (heads- or query-seq-sharded over ``model``),
    the residual stream is Megatron-SP sequence-sharded between layers,
    and SSM kernels use the factorized chunk form with head sharding.

Everything degrades to a no-op when no mesh is active or when a shape
does not divide the mesh axis — single-device tests exercise the exact
same code path as a sharded mesh.

Layout selection for attention (:func:`attn_plan`):

  ``("heads", ax)``  H % ax_size == 0 — shard heads; K/V are repeated to
                     full H locally so no collective appears inside the
                     KV-chunk scan (the AMU rule: keep the stream loop
                     free of synchronisation);
  ``("seq", ax)``    otherwise, if Sq % ax_size == 0 — shard the query
                     sequence (also forced while the residual stream is
                     seq-sharded, so attention consumes the layout the
                     residual already has);
  ``None``           nothing fits — leave the layout to GSPMD.
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "ActPolicy", "BASELINE", "OPTIMIZED", "policy", "current",
    "residual_layout", "residual_spec", "attn_plan", "constrain",
    "dp_spec_prefix", "model_axis_size",
]


@dataclass(frozen=True)
class ActPolicy:
    """Activation sharding/precision knobs (value object, hash/eq by value)."""

    native_dtype: bool = False     # einsum operands in compute dtype (f32 acc)
    attn_explicit: bool = False    # constrain attention layouts explicitly
    seq_residual: bool = False     # Megatron-SP residual stream over model
    ssm_factorized: bool = False   # factorized chunk form in wkv6/ssd
    ssm_head_shard: bool = False   # constrain SSM head dims over model
    model_axis: str = "model"      # mesh axis carrying intra-layer sharding


BASELINE = ActPolicy()
OPTIMIZED = ActPolicy(native_dtype=True, attn_explicit=True,
                      seq_residual=True, ssm_factorized=True,
                      ssm_head_shard=True)

_policy_stack: List[ActPolicy] = []
_residual_stack: List[bool] = []


def current() -> ActPolicy:
    """The innermost active policy (``BASELINE`` outside any context)."""
    return _policy_stack[-1] if _policy_stack else BASELINE


@contextmanager
def policy(pol: ActPolicy):
    """Scope ``pol`` as the active policy (re-entrant, nestable)."""
    _policy_stack.append(pol)
    try:
        yield pol
    finally:
        _policy_stack.pop()


@contextmanager
def residual_layout(seq_sharded: bool):
    """Layer-scoped signal: the residual stream entering attention is
    sequence-sharded, so :func:`attn_plan` must pick the seq plan even
    when the head count divides the mesh axis."""
    _residual_stack.append(bool(seq_sharded))
    try:
        yield
    finally:
        _residual_stack.pop()


def _residual_is_seq() -> bool:
    return _residual_stack[-1] if _residual_stack else False


# -- mesh introspection (module-level so tests can monkeypatch) -----------------

def _current_mesh():
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            return m
    except Exception:  # pragma: no cover - jax internals moved
        pass
    return None


def _mesh_axis_sizes() -> Dict[str, int]:
    """Axis name -> size of the active mesh ({} when single-device)."""
    m = _current_mesh()
    return dict(m.shape) if m is not None else {}


def model_axis_size() -> int:
    """Size of the active policy's model axis on the current mesh (1
    when no mesh is active or the axis is absent)."""
    return _mesh_axis_sizes().get(current().model_axis, 1)


def dp_spec_prefix():
    """Spec entry for the batch dim: data-parallel axes of the active mesh.

    Returns a single axis name, a tuple of axis names (multipod), or
    ``None`` when no data-parallel axis exists.
    """
    sizes = _mesh_axis_sizes()
    axes = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


# -- layout decisions -----------------------------------------------------------

def attn_plan(num_heads: int, num_kv_heads: int, seq_len: int
              ) -> Optional[Tuple[str, str]]:
    """Pick the attention layout under the active policy.

    Returns ``("heads", axis)``, ``("seq", axis)`` or ``None`` (leave it
    to GSPMD).  ``num_kv_heads`` is carried for future plans that shard
    the KV heads instead of repeating them.
    """
    pol = current()
    if not pol.attn_explicit:
        return None
    m = _mesh_axis_sizes().get(pol.model_axis, 1)
    if m <= 1:
        return None
    if _residual_is_seq():
        # the residual stream is already seq-sharded: attention must
        # consume that layout or pay a reshard on every layer boundary
        return ("seq", pol.model_axis) if seq_len % m == 0 else None
    if num_heads % m == 0:
        return ("heads", pol.model_axis)
    if seq_len % m == 0:
        return ("seq", pol.model_axis)
    return None


def residual_spec(seq_len: int, *, gather: bool = False):
    """PartitionSpec for the (B, S, d) residual stream between layers.

    ``None`` unless the active policy seq-shards the residual AND the
    sequence divides the model axis.  ``gather=True`` returns the spec
    that collects the sequence back to full (MoE layers need the whole
    sequence per row for sort-based dispatch).
    """
    pol = current()
    if not pol.seq_residual:
        return None
    m = _mesh_axis_sizes().get(pol.model_axis, 1)
    if m <= 1 or seq_len % m != 0:
        return None
    dp = dp_spec_prefix()
    if gather:
        return P(dp, None, None)
    return P(dp, pol.model_axis, None)


def constrain(x, spec):
    """``with_sharding_constraint`` under the active mesh; no-op when the
    spec is ``None`` or no mesh is active (single-device tests)."""
    if spec is None:
        return x
    mesh = _current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
