"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches JAX device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; the multi-pod mesh prepends a 2-pod axis.

    Axis semantics:
      pod   — the high-latency decentralized boundary (pipeline stages for
              serving, folded into DP for training)
      data  — batch/FSDP axis (fast ICI)
      model — tensor/expert parallel axis (fast ICI)
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(devices: int, *, model_parallel: int = 16):
    """Elastic variant: the largest (data, model) mesh for ``devices``."""
    from repro.distributed.elastic import ElasticPlanner
    plan = ElasticPlanner(model_parallel=model_parallel).plan(devices)
    return _auto_mesh(plan.shape, plan.axes)


def _auto_mesh(shape, axes):
    """A mesh whose axes the partitioner shards automatically.  ``jax.make_mesh``
    otherwise types every axis Explicit, and the sharding rules of this
    package (``with_sharding_constraint`` inside ``jit``) are written for
    automatic axes."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)
