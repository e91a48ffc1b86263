"""Sharded-execution layer: activation policies, parameter/optimizer
PartitionSpecs, and jitted step factories over the launch mesh axes.

Three modules, consumed by ``repro.models`` (lazily, per call site),
``repro.launch`` and the serve engine:

  * :mod:`repro.dist.act_sharding` — scoped activation sharding /
    precision policy (baseline = paper-faithful GSPMD-implicit layout;
    optimized = explicit heads-/seq-sharded attention, seq-sharded
    residual stream, native-dtype norms),
  * :mod:`repro.dist.sharding` — PartitionSpec assignment for parameter,
    optimizer (ZeRO-1) and batch pytrees over the (pod, data, model)
    mesh axes built by :mod:`repro.launch.mesh`,
  * :mod:`repro.dist.steps` — jitted, donated, mesh-sharded train /
    prefill / serve step factories plus abstract-input builders.

The AMU thesis at system scale: latency (far memory there, inter-chip
collectives here) is hidden by keeping many independent units of work in
flight — here, donated mesh-parallel step functions whose parameters and
KV state live sharded across devices.
"""

from repro.dist import act_sharding, sharding, steps

__all__ = ["act_sharding", "sharding", "steps"]
