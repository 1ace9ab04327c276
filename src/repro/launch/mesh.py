"""Production mesh construction.

Functions, never module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
device query).

Target hardware: TPU v5e pods — 256 chips (16×16) per pod, 2 pods for the
multi-pod configuration (512 chips).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has — used by smoke tests/examples."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))
