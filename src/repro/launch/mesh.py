"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to build these meshes on CPU.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_single_machine_mesh(n_devices: int = 8):
    """The paper's deployment target: one 8-accelerator host (TP only)."""
    return _make_mesh((1, n_devices), ("data", "model"))


def make_host_mesh():
    """Whatever devices exist locally (tests / examples)."""
    return _make_mesh((1, len(jax.devices())), ("data", "model"))


def mesh_from_spec(spec: str | None):
    """Parse a CLI mesh spec into a mesh (or ``None``).

    ``None``/``"none"`` -> no mesh (single-device serving, today's
    behavior); ``"host"`` -> :func:`make_host_mesh` over every local
    device; ``"DxM"`` (e.g. ``"2x4"``) -> an explicit
    ``(data, model)`` mesh, validated against the local device count.
    """
    if spec is None or spec.lower() == "none":
        return None
    if spec.lower() == "host":
        return make_host_mesh()
    try:
        d, m = (int(tok) for tok in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"bad mesh spec {spec!r}: expected 'none', 'host' or 'DxM'"
        ) from None
    if d < 1 or m < 1:
        raise ValueError(f"bad mesh spec {spec!r}: axes must be >= 1")
    have = len(jax.devices())
    if d * m > have:
        raise ValueError(
            f"mesh spec {spec!r} needs {d * m} devices, have {have} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before the first jax import for CPU meshes)")
    return _make_mesh((d, m), ("data", "model"))


def describe_mesh(mesh) -> str:
    if mesh is None:
        return "none"
    return "x".join(f"{mesh.shape[a]}" for a in mesh.axis_names)
