"""Production meshes (assignment spec).

Defined as functions, not module constants, so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None):
    """Small host mesh for tests: (1, n) data×model over available devices."""
    n = n_devices or len(jax.devices())
    return make_mesh((1, n), ("data", "model"))
