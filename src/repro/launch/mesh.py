"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required because the 512-device
host-platform override must be set before jax initializes, and only
``launch/dryrun.py`` does that.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    # NOT repro.core.compat.make_mesh: importing repro.core would build
    # module-level jnp constants and initialize the backend, which this
    # module must never do (see module docstring).
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_pod_mesh(data: int, model: int):
    """Single-pod mesh with a custom (data, model) factorization of the 256
    chips — the §Perf 'resharding' knob (e.g. 32x8 for archs whose expert /
    kv-head counts don't divide 16)."""
    assert data * model == 256, (data, model)
    return _make_mesh((data, model), ("data", "model"))


def make_mini_mesh(data: int = 2, model: int = 4):
    """Small host mesh for CI-grade dry-run tests (8 fake devices)."""
    return _make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes that shard the batch: ('pod','data') when the pod axis exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
