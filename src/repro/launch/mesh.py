"""Production mesh definitions (deliverable e).

Functions — never module-level constants — so importing this module does not
touch jax device state (the dry-run must set XLA_FLAGS before first init)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod: (data=16, model=16); two pods: (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (axes kept for spec reuse)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_abstract_mesh(shape, axes):
    """Device-free mesh for sharding-rule checks (no real devices needed)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))
