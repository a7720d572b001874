"""Production mesh construction.

A function (never a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* first jax
init, and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips ('data','model') per pod; 2 pods with a leading
    'pod' axis for the multi-pod dry-run."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh():
    """Whatever devices exist, as a 1-D 'data' mesh (CPU tests/examples)."""
    return make_mesh((len(jax.devices()),), ("data",))


def make_abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Device-less mesh for spec math."""
    return AbstractMesh(shape, axes)
