"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init to obtain the placeholder devices.  Mesh construction goes through
``repro.compat.make_mesh`` (every axis ``AxisType.Auto``).
"""
from __future__ import annotations

import jax

from ..compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a (1, n_dev) data x model mesh —
    used by CPU integration tests of the sharded code paths."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))
