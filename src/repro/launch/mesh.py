"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state. Single pod: 256 chips as (data=16, model=16). Multi-pod: a
leading pod=2 axis (512 chips) — the "pod" axis carries pure data parallelism
with gradient all-reduce over the (slow) cross-pod links.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_data: int | None = None) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    n_data = n_data or n
    return make_mesh((n_data, n // n_data), ("data", "model"))
