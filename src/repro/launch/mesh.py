"""Production mesh construction (brief-fixed shapes).

Single pod : (data=16, model=16)           = 256 chips
Multi-pod  : (pod=2, data=16, model=16)    = 512 chips

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
Mesh construction goes through ``repro.core.compat.make_mesh`` (every axis
``Auto``).
"""

from __future__ import annotations

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_local_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over host devices for tests/examples."""
    return compat.make_mesh(shape, axes)
