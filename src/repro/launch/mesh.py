"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then builds the mesh.

Axes:
  pod   — across pods (DCN); pure data parallelism — the paper's §4
          hybrid (DP across nodes, partitioning within the node)
  data  — within-pod data parallel / ZeRO-1 / context parallel
  model — tensor/expert parallel
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    The step builders place values with ``NamedSharding`` and
    ``with_sharding_constraint`` and leave propagation to the compiler,
    which only ``Auto`` axes allow (``jax.make_mesh`` defaults to
    ``Explicit``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1,
                   pod: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / elastic restart)."""
    n = jax.device_count()
    if data is None:
        data = n // (model * pod)
    assert pod * data * model <= n, (pod, data, model, n)
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def mesh_num_chips(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
