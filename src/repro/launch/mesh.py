"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import; smoke tests and benches see the 1 real CPU device.

Axes:
  pod   — cross-pod data parallelism (2 pods in the multi-pod dry-run)
  data  — in-pod data parallelism / FSDP sharding
  model — tensor/expert parallelism
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Every mesh in the repo is built here. Axes are `Auto`: the sharding
    rules (`runtime.sharding`) place weights and constrain activations, and
    the partitioner propagates the rest. `jax.make_mesh` defaults to
    `Explicit` axes, under which `with_sharding_constraint` turns into a
    type assertion and unannotated gathers raise `ShardingTypeError`."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_chips(mesh) -> int:
    out = 1
    for v in mesh.shape.values():
        out *= v
    return out
