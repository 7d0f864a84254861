"""Production mesh construction.

A function — not a module-level constant — so importing this module never
touches jax device state.  The dry-run entrypoint (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import jax


def axis_types_kwargs(n_axes: int) -> dict:
    """``axis_types=(Auto,) * n``: every mesh axis sharded automatically."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_abstract_mesh(shape, axes):
    """Device-less mesh (shapes/names only)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes),
                        **axis_types_kwargs(len(axes)))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **axis_types_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic rescale)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **axis_types_kwargs(len(axes)))
