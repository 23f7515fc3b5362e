"""Random weights from the seed, made on the device.

The benchmark makes the weights itself, by its own rule, so that the
plain reference can make the same ones again without taking anything
from the program. Leaf ``i`` of the program's parameter tree (in
flattened order) is drawn from ``fold_in(key, i)``:

* matrices and the embedding: normal, scaled by ``1/sqrt(fan_in)``
  (the embedding by ``1/sqrt(hidden)``, the fan-in of the output head
  tied to it), in the configuration's dtype;
* norm scales: ``1 + 0.1 * normal`` (float32, as the program keeps them);
* biases: ``0.02 * normal``, so that the QKV-bias path carries weight.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def make_leaf(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if last == "scale":
        v = 1.0 + 0.1 * z
    elif last in ("bq", "bk", "bv"):
        v = 0.02 * z
    elif last == "embed":
        v = z / math.sqrt(shape[-1])
    else:
        v = z / math.sqrt(shape[-2])
    return v.astype(dtype)


def make_params(spec, key, leaf_devices: list):
    """The tree ``spec`` (of ``ShapeDtypeStruct``) filled from ``key``,
    leaf ``i`` made directly on ``leaf_devices[i]``: one jitted call per
    device, so no device holds another's leaves."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(spec)
    leaves: list = [None] * len(flat)
    by_device: dict = {}
    for i, dev in enumerate(leaf_devices):
        by_device.setdefault(dev, []).append(i)
    for dev, idx in by_device.items():
        def make(k, idx=tuple(idx)):
            return [make_leaf(jax.random.fold_in(k, i),
                              leaf_name(flat[i][0]), flat[i][1].shape,
                              flat[i][1].dtype) for i in idx]
        vals = jax.jit(make, out_shardings=SingleDeviceSharding(dev))(
            jax.device_put(key, dev))
        for i, v in zip(idx, vals):
            leaves[i] = v
    return jax.tree_util.tree_unflatten(treedef, leaves)
