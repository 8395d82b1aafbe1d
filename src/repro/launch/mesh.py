"""Production mesh construction (TPU v5e target).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests see 1 CPU device; only dryrun.py
sets XLA_FLAGS for 512 host devices).
"""
from __future__ import annotations

import jax

# hardware constants (TPU v5e) used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """jax.make_mesh with Auto axis types (sharding propagates from the
    params and the constraints; no explicit-axis typing)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests)."""
    return make_mesh((data, model), ("data", "model"))


def mesh_chips(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
