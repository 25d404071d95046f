"""Production mesh construction + the one place that touches JAX's mesh APIs.

Import of this module never touches jax device state; meshes are built by
functions only (the dry-run sets XLA_FLAGS before any jax import).

Everything that builds or installs a mesh goes through the shims here
instead of calling jax directly (contractcheck's ``shim-discipline`` rule),
so a change of JAX's mesh API is one edit in this file:

  - :func:`make_mesh` — ``jax.make_mesh`` with explicitly ``Auto`` axis
    types, optionally over a given device list.
  - :func:`use_mesh` — ``jax.set_mesh``: installs the ambient mesh.
  - :func:`shard_map_compat` — ``jax.shard_map`` with its ``check_vma``
    flag exposed as ``check``.
  - :data:`Mesh` — re-export of ``jax.sharding.Mesh`` for type annotations.
    contractcheck's shim-discipline rule forbids importing it from
    ``jax.sharding`` anywhere else, so every raw-API touch stays in this
    one file.
"""

from __future__ import annotations

import contextlib

import jax
from jax.sharding import Mesh

__all__ = ["Mesh", "make_mesh", "use_mesh", "shard_map_compat",
           "make_production_mesh", "make_test_mesh", "batch_axes"]


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types; ``devices`` fixes which
    devices the mesh spans instead of letting JAX pick them."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto, devices=devices)


@contextlib.contextmanager
def use_mesh(mesh):
    """Install ``mesh`` as the ambient mesh for the ``with`` block."""
    with jax.set_mesh(mesh):
        yield


def shard_map_compat(f, mesh, in_specs, out_specs, check=False):
    """``jax.shard_map``; ``check`` is its ``check_vma`` flag."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def _mk(shape, axes):
    return make_mesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Tiny mesh for CI-scale dry-run tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def batch_axes(mesh) -> tuple:
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))
