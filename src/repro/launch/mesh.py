"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. The production target is TPU v5e-class:
one pod = 256 chips as a (data=16, model=16) mesh; multi-pod adds a
leading "pod" axis (2 pods = 512 chips for the dry-run; the axis is what
scales to 1000+ nodes — nothing in the framework assumes pod == 2).
"""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(*, devices: int = 8):
    """Small mesh over host devices for unit/integration tests.

    8 devices -> (pod=2, data=2, model=2): every axis is non-trivial so the
    hierarchical shuffle paths are fully exercised.
    """
    if devices == 8:
        return _mesh((2, 2, 2), ("pod", "data", "model"))
    if devices == 4:
        return _mesh((2, 2), ("data", "model"))
    return _mesh((devices,), ("data",))
