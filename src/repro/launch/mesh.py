"""Production meshes.

A function, not a module-level constant — importing this module never
touches jax device state.  The dry-run process forces 512 host-platform
devices (launch/dryrun.py sets XLA_FLAGS before any jax import); everything
else (tests, benches) sees the real single CPU device and uses small meshes.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — run under "
            "launch/dryrun.py (it forces 512 host devices)")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * 2)
