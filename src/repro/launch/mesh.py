"""Production mesh definitions (TPU v5e).

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: JAX 0.9 defaults to Explicit axes,
    under which `with_sharding_constraint` and the mesh decode path's
    implicit resharding are refused."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips with a leading
    "pod" axis (data-parallel across the slower inter-pod links)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n: int | None = None, model: int = 1):
    """A mesh over forced host devices (tests on CPU): 1-D ``(data,)`` by
    default, 2-D ``(data, model)`` when ``model > 1`` — the debug twin of
    the production mesh's trailing tensor-parallel axis."""
    if model > 1:
        n = n or len(jax.devices()) // model
        return _auto_mesh((n, model), ("data", "model"))
    n = n or len(jax.devices())
    return _auto_mesh((n,), ("data",))


# v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
