"""Production mesh builders.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required by the dry-run contract.  Meshes carry
Auto axis types (GSPMD propagation); callers make one ambient with
`jax.set_mesh`.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ("data", "model") = 256 chips (TPU v5e pod).
    Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2):
    """Small mesh over forced host devices (tests/examples on CPU)."""
    n = data * model
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices; set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "before importing jax"
        )
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple[str, ...]:
    """Batch-sharding axes for the given mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
