"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets its
fake-device XLA flag before the first jax call, and tests/benches keep their
1-device view.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh with Auto axis types (tests, examples, benches)."""
    return _mesh(shape, axes)


def host_device_mesh(model_axis: int = 1) -> Mesh:
    """Mesh over whatever devices this process actually has (CPU tests)."""
    n = len(jax.devices())
    return make_mesh((n // model_axis, model_axis), ("data", "model"))
