"""Production mesh builders (multi-pod dry-run spec).

Functions, not module-level constants: importing this module never touches
jax device state. Every mesh has ``Auto`` axis types.
"""
from __future__ import annotations

import jax


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever this host offers, as a 1-D 'data' mesh (smoke/e2e runs)."""
    return _mesh((len(jax.devices()),), ("data",))


def make_nodelet_mesh(p: int = 8) -> jax.sharding.Mesh:
    """Emu-like mesh for the core irregular algorithms: one axis of nodelets
    (8 = one Chick node, 64 = the 8-node Chick)."""
    return _mesh((p,), ("nodelet",))
