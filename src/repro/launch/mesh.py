"""Production mesh construction.

Importing this module never touches jax device state; meshes are built
lazily by functions (the dry-run sets XLA_FLAGS before first jax init).

Every mesh here has ``Auto`` axes: the model layer's sharding hints
(``core.sharding_hints.constrain``) leave dimensions ``UNCONSTRAINED`` for
the partitioner to decide, which only ``Auto`` axes allow.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips, axes (data, model).
    Multi-pod: 2 pods x 256 = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, *, tp: int = 2,
                    devices=None):
    """Small (data, model) mesh over the first ``n_devices`` of ``devices``
    (default: every visible device)."""
    devices = list(devices if devices is not None else jax.devices())
    n = n_devices or len(devices)
    tp = min(tp, n)
    dp = n // tp
    return make_mesh((dp, tp), ("data", "model"), devices=devices[:dp * tp])


def replica_meshes(n_replicas: int, devices=None) -> list:
    """One mesh per serving replica.  With at least as many devices as
    replicas, the devices split evenly and each replica's mesh holds only
    its own share (four chips, four replicas: one chip each).  With fewer,
    every replica shares one mesh over all of them (they time-share)."""
    devices = list(devices if devices is not None else jax.devices())
    per = len(devices) // n_replicas
    if per == 0:
        return [make_debug_mesh(devices=devices)] * n_replicas
    return [make_debug_mesh(devices=devices[i * per:(i + 1) * per])
            for i in range(n_replicas)]


def mesh_device_count(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
