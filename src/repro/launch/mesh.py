"""Production mesh construction (single-pod 16×16, multi-pod 2×16×16).

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], **kwargs):
    """``jax.make_mesh`` with Auto axis types, so sharding propagation works
    as on a plain ``Mesh``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes), **kwargs,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh(shape, axes)
