"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
while tests and benches see the single real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False, shape=None) -> Mesh:
    """Build the serving mesh.

    Without ``shape`` this is the full dry-run topology — (16, 16) or
    (2, 16, 16) with ``multi_pod`` — and requires the 512-device
    host-platform env. With an explicit ``shape`` (a 2- or 3-tuple) it
    builds a small (data, model) / (pod, data, model) mesh from however
    many real devices the process has, so tests and benches can get a
    (1, 2) or (1, 4) mesh without XLA_FLAGS gymnastics.
    """
    explicit = shape is not None
    if explicit:
        shape = tuple(int(s) for s in shape)
        if len(shape) not in (2, 3) or any(s < 1 for s in shape):
            raise ValueError(
                f"mesh shape must be a 2- or 3-tuple of positive ints, "
                f"got {shape!r}")
        axes = ("pod", "data", "model") if len(shape) == 3 else \
            ("data", "model")
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]  # single-pod uses 256 of the dry-run's 512
    if len(devices) < n:
        if explicit:
            raise RuntimeError(
                f"mesh {shape} needs {n} devices, have {len(devices)}; "
                "run with more devices (e.g. XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={n}) or pick a smaller shape")
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)}; the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=512 before any jax import")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests use small ones, e.g. (2, 4))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> Mesh:
    """Single-device mesh with the production axis names (smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
