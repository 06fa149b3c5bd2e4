"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run forces 512 host
devices while tests/benches must see one.
"""
from __future__ import annotations

from .. import compat
from .runtime import require_devices


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis (the slow inter-pod links carry only the data-parallel gradient
    reduction)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_dd_mesh(n_ranks: int):
    """1-D mesh for the MD virtual-DD inference layer (axis "dd") over the
    first ``n_ranks`` devices JAX sees."""
    devices = require_devices(n_ranks, f"make_dd_mesh({n_ranks})")
    return compat.make_mesh((n_ranks,), ("dd",), devices=devices)
