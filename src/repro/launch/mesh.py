"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run forces a 512-device
host platform while tests/benches must see the real single device.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AbstractMesh


def make_abstract_mesh(shape: Sequence[int],
                       axes: Sequence[str]) -> AbstractMesh:
    """Device-free AbstractMesh from parallel (shape, axes) sequences.

    The one place that builds an ``AbstractMesh``, so tests and library
    code agree on a construction API mirroring ``jax.make_mesh``.
    """
    assert len(shape) == len(axes), (shape, axes)
    return AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod ("data","model"); multi_pod adds a 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model shards through with_sharding_constraint, which
    # refuses the Explicit axes jax.make_mesh defaults to.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CI-scale sharding tests (requires ≥ n_data·n_model
    host devices, typically via --xla_force_host_platform_device_count)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"))
