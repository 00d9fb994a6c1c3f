"""Production mesh definitions (multi-pod dry-run target).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    """GSPMD-style axes: the model stack shards by constraints, not by type."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh():
    """Whatever devices exist locally, as a ('data','model') mesh with
    model=1 — used by tests and CPU examples."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=_auto(2))
