"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  Single pod: 16x16 = 256 chips (data, model);
multi-pod: 2x16x16 = 512 chips (pod, data, model) — the ``pod`` axis is an
outer data-parallel axis by default (optionally a pipeline axis, see
distributed/pipeline.py).

All mesh construction in this repo goes through :func:`make_mesh` /
:func:`make_abstract_mesh` / :func:`mesh_context`, which fix the axis types
(``AxisType.Auto``) in one place.
"""

from __future__ import annotations

from typing import ContextManager, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices=None,
) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names), **kwargs,
    )


def make_abstract_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str]
) -> "jax.sharding.AbstractMesh":
    """Abstract (device-free) mesh for sharding-spec math."""
    return jax.sharding.AbstractMesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names),
    )


def mesh_context(mesh: jax.sharding.Mesh) -> ContextManager:
    """Install ``mesh`` as the current mesh (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """1-device mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))


__all__ = [
    "make_mesh",
    "make_abstract_mesh",
    "mesh_context",
    "make_production_mesh",
    "make_host_mesh",
]
