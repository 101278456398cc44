"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
sets XLA_FLAGS before any jax initialization (see dryrun.py).

Production topology (TPU v5e):
  single-pod : (16, 16)      axes ("data", "model")   — 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") — 512 chips
Batch shards over ("pod", "data"); model-parallel dims over "model".

Meshes carry ``AxisType.Auto`` axes; callers install one as the ambient
mesh with ``jax.sharding.set_mesh`` and read it back with
``jax.sharding.get_abstract_mesh``.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke tests of the sharded code paths."""
    return make_mesh((1, 1), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The axes a global batch shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
