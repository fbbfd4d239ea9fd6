"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so these meshes can be built on the CPU container.

Production topology (TPU v5e pods):
  single pod:  (data=16, model=16)          = 256 chips
  multi pod:   (pod=2, data=16, model=16)   = 512 chips
``pod`` is the DCN axis (pure data parallel; optionally int8-compressed
gradient all-reduce), ``data`` is within-pod FSDP/batch, ``model`` is
tensor/expert parallel. Scaling to 1000+ nodes grows ``pod`` (the mesh
construction takes the pod count as a parameter).
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False, n_pods: int = 2):
    if multi_pod:
        shape = (n_pods, 16, 16)
        axes = ("pod", "data", "model")
    else:
        shape = (16, 16)
        axes = ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)}; "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return jax.make_mesh(shape, axes, _auto(axes), devices=devices[:n])


def make_host_mesh():
    """1-device mesh for smoke tests / CPU examples."""
    axes = ("data", "model")
    return jax.make_mesh((1, 1), axes, _auto(axes),
                         devices=jax.devices()[:1])


def _auto(axes):
    """Auto axis types: the LM code places values with
    ``with_sharding_constraint``, which Explicit axes (the
    ``jax.make_mesh`` default) refuse."""
    return (jax.sharding.AxisType.Auto,) * len(axes)
