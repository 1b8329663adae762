"""Production mesh construction (single-pod 16×16, multi-pod 2×16×16).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Auto axes: the model code places arrays with sharding constraints,
    which ``jax.make_mesh``'s default Explicit axes refuse."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh with production axis names (CPU smoke paths)."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
