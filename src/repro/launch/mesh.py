"""Production mesh + TPU v5e hardware constants.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before *any* jax
initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod mesh (data, model); 2 pods adds a leading "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh for CPU integration tests (requires that many devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(n: int):
    """Auto axes: the model code constrains activations with
    ``with_sharding_constraint``, which only accepts Auto mesh axes (jax
    0.9's ``make_mesh`` defaults to Explicit)."""
    return (jax.sharding.AxisType.Auto,) * n


# --- TPU v5e per-chip constants (assignment-specified) ----------------------
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s/link

def mesh_chips(mesh) -> int:
    return mesh.devices.size
