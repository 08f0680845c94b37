"""Production mesh definitions.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def _make(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod slice: 16x16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ``data`` (batch / FSDP), ``model`` (TP / EP), and in multi-pod
    runs ``pod`` (a second pure-data axis across the inter-pod links — DCN
    in a real deployment)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic reconfigurations, tests)."""
    return _make(tuple(shape), tuple(axes))
