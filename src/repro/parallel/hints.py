"""Ambient activation-sharding hints.

Model code is mesh-agnostic; the launcher (dryrun/train/serve) installs a
mesh + strategy here and layers call ``constrain`` at a handful of
anchor points (embedding output, block boundaries).  Outside a hints
context every call is a no-op, so smoke tests and single-device runs are
untouched.  Every axis is divisibility-guarded.

Strategies (ArchConfig.strategy):
  tp — tensor parallel: activations (dp, None, ...), weights TP+FSDP.
  sp — sequence parallel: activations (dp, "model", ...) on the seq dim;
       for small models whose head counts don't divide the model axis
       (whisper-base), replicating attention would multiply compute by the
       model-axis size — SP keeps every chip busy on distinct rows instead.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_state = threading.local()


@contextlib.contextmanager
def use_mesh_hints(mesh: Mesh):
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.mesh = prev


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


def _resolve(dim: int, axis, sizes) -> object:
    if axis is None:
        return None
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            if a not in sizes:
                return None
            n *= sizes[a]
        return axis if dim % n == 0 else None
    if axis not in sizes:
        return None
    return axis if dim % sizes[axis] == 0 else None


def constrain(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint(x, P(*axes)) guarded by mesh presence and
    per-dim divisibility.  ``axes`` may use "dp" (resolved to ("pod","data")
    when the mesh has a pod axis)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    sizes = dict(mesh.shape)
    resolved = []
    for dim, ax in zip(x.shape, axes):
        if ax == "dp":
            ax = ("pod", "data") if "pod" in sizes else "data"
        resolved.append(_resolve(dim, ax, sizes))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


def shard_rows(fn, x: jax.Array, *operands):
    """``fn(x, *operands)``, run per device on its share of x's leading
    (batch) dim when a mesh is in effect, the operands whole on every
    device.  Pallas TPU (Mosaic) kernels cannot be partitioned by XLA, so
    a kernel call under a mesh must be a shard_map.  Gradients of the
    whole operands are summed over the devices (shard_map's transpose of
    an unmapped input).  Outside a hints context: a plain call."""
    mesh = current_mesh()
    if mesh is None:
        return fn(x, *operands)
    sizes = dict(mesh.shape)
    rows = P(_resolve(x.shape[0], ("pod", "data") if "pod" in sizes
                      else "data", sizes))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(rows,) + (P(),) * len(operands),
                         out_specs=rows, check_vma=False)(x, *operands)


def constrain_tokens3d(x: jax.Array, cfg) -> jax.Array:
    """Anchor for [B, S, D] residual-stream activations.

    The residual stream is stored *sequence-sharded over the model axis*
    under both strategies: for "sp" it is the compute layout; for "tp" it is
    Megatron-style sequence partitioning of the saved-for-backward carry —
    without it a deep scan stores n_layers full [B,S,D] carries per device
    (qwen2-72b: 80 x 1.07 GiB = 86 GiB; sharded: 5.4 GiB).  XLA turns the
    wo all-reduce into reduce-scatter + all-gather around each block, so
    communication volume is unchanged."""
    return constrain(x, "dp", "model", None)
