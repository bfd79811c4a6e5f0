"""Production mesh definitions.

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS before any jax import to obtain 512 placeholder host devices.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — DP over
pod×data (DCN across pods), TP over model (ICI within pod).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many devices exist (tests, examples)."""
    return jax.make_mesh((data, model), ("data", "model"))
