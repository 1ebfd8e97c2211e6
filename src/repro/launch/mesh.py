"""Production mesh builders.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax use.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["dp_axes", "dp_size", "make_mesh", "make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; the multi-pod mesh adds a leading DCN 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic resizes, CI-scale meshes).

    Every axis is ``AxisType.Auto``: the models place activations with
    ``with_sharding_constraint`` and compose kernels under ``jax.shard_map``,
    which is auto-mode sharding (``jax.make_mesh`` defaults to Explicit).
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh, axes: tuple[str, ...] | None = None) -> int:
    """Data-parallel extent of ``mesh`` — the **canonical** definition.

    ``axes`` names the mesh axes that play the DP role; None means the
    production convention (``dp_axes``: whichever of 'pod'/'data' exist).
    ``models.sharding.dp_size`` is the rules-context wrapper around this —
    it resolves the active rules table's ``act_batch`` mapping and
    delegates here, so the two can never drift (pinned by
    tests/test_mesh_flex.py::test_dp_size_single_definition).
    """
    import math

    if axes is None:
        axes = dp_axes(mesh)
    return math.prod(mesh.shape[a] for a in axes if a in mesh.axis_names)
