"""Logical-axis sharding rules → PartitionSpec trees.

Two mesh layouts (launch/mesh.py):
  single-pod  (data=16, model=16)
  multi-pod   (pod=2, data=16, model=16)  — "pod" is hierarchical DP.

Parameters are 2-D sharded (TP on "model" + FSDP on "data") so the
104B-param arch fits: per-device bytes = total/(data*model).  Every rule is
guarded by divisibility — a dim that doesn't divide its mesh axis is
replicated instead (whisper's 8 heads vs model=16, batch=1 long-context).

The KV cache shards its *sequence* dim over "model": decode attention then
lowers to local partial softmax + scalar-sized all-reduces (flash-decoding,
DESIGN.md Sec. 5).
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.sparse_linear import MOE_PATTERN_LEAVES, PATTERN_LEAVES

# linear containers whose w is [in, out]: out-dim -> "model", in-dim -> "data"
_OUT_MODEL = {"wq", "wk", "wv", "wi", "wg", "in_proj", "wkv_b",
              "in_z", "in_xbc", "in_dt", "dt_proj"}
# linear containers whose w is [in, out]: out-dim -> "data", in-dim -> "model"
_OUT_DATA = {"wo", "out_proj"}
# replicated small projections
_REPL = {"wkv_a", "x_proj"}


def _fit(dim: int, axis: str | None, mesh: Mesh):
    """Use axis only if dim divides its size."""
    if axis is None:
        return None
    sizes = dict(mesh.shape)
    ax = sizes.get(axis)
    if isinstance(axis, tuple):
        ax = int(np.prod([sizes[a] for a in axis]))
    return axis if ax and dim % ax == 0 else None


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def _dp_fit(dim: int, mesh: Mesh):
    sizes = dict(mesh.shape)
    axes = dp_axes(mesh)
    if isinstance(axes, tuple):
        total = int(np.prod([sizes[a] for a in axes]))
        if dim % total == 0:
            return axes
        # fall back to the inner data axis alone
        return "data" if dim % sizes["data"] == 0 else None
    return axes if dim % sizes[axes] == 0 else None


def _linear_spec(parent: str, leaf: str, lshape: tuple, mesh: Mesh,
                 head_aligned: bool = True):
    """Spec for one leaf of a linear container (logical shape, no stack dims).

    head_aligned=False (attention projections whose head count doesn't divide
    the model axis, e.g. whisper's 8 heads on model=16) forces the head-fused
    dim to replicate: sharding it would misalign the [.., H, hd] reshape and
    the partitioner would emit score-sized all-reduces per chunk."""
    nd = len(lshape)
    if leaf in PATTERN_LEAVES:
        return (None,) * nd
    if parent in _REPL:
        return ((_fit(lshape[0], "data", mesh),) + (None,) * (nd - 1)
                if nd >= 1 else ())
    if leaf == "b":
        axis = "model" if parent in _OUT_MODEL else "data"
        if not head_aligned:
            axis = None
        return (_fit(lshape[0], axis, mesh),)
    # weights
    if nd == 2:  # dense [in, out]
        if parent in _OUT_MODEL:
            return (_fit(lshape[0], "data", mesh),
                    _fit(lshape[1], "model", mesh) if head_aligned else None)
        return (_fit(lshape[0], "model", mesh) if head_aligned else None,
                _fit(lshape[1], "data", mesh))
    if nd == 4:  # block-sparse [nob, kb, bs, bs]
        return (_fit(lshape[0], "model", mesh), None,
                _fit(lshape[2], "data", mesh), None)
    return (None,) * nd


def _leaf_spec(path: list[str], lshape: tuple, mesh: Mesh,
               cfg: ArchConfig | None = None):
    leaf = path[-1]
    parent = path[-2] if len(path) > 1 else ""
    grandparent = path[-3] if len(path) > 2 else ""
    nd = len(lshape)
    model_size = dict(mesh.shape)["model"]
    # attention projections: shardable only when head counts divide "model"
    head_aligned = True
    if cfg is not None and grandparent in ("attn", "cross", "shared_attn"):
        if parent in ("wq", "wo", "wkv_b"):
            head_aligned = cfg.n_heads % model_size == 0
        elif parent in ("wk", "wv"):
            head_aligned = cfg.kv_heads % model_size == 0
    # norms / small vectors
    if leaf in ("scale",) or (leaf == "bias" and nd == 1 and parent.startswith("norm")):
        return (None,) * nd
    if parent in ("kv_norm", "final_norm") or leaf == "pos":
        return (None,) * nd
    # embeddings
    if leaf == "tok":
        return (_fit(lshape[0], "model", mesh), _fit(lshape[1], "data", mesh))
    if leaf == "out" and nd == 2:
        return (_fit(lshape[0], "data", mesh), _fit(lshape[1], "model", mesh))
    # moe
    if leaf == "router":
        return (_fit(lshape[0], "data", mesh), _fit(lshape[1], "model", mesh))
    if leaf in MOE_PATTERN_LEAVES:
        # shared expert block pattern + its reverse: replicated like every
        # other pattern leaf (scalar-prefetch operands of the unified kernels)
        return (None,) * nd
    if parent == "moe" or (nd in (3, 5) and leaf in ("wi", "wg", "wo")):
        if nd == 5:               # sparse experts [E, nob, kb, bs, bs]: EP only
            return (_fit(lshape[0], "model", mesh), None, None, None, None)
        if leaf in ("wi", "wg"):  # [E, D, F]
            return (_fit(lshape[0], "model", mesh), _fit(lshape[1], "data", mesh), None)
        if leaf == "wo":          # [E, F, D]
            return (_fit(lshape[0], "model", mesh), None, _fit(lshape[2], "data", mesh))
    # ssm extras
    if leaf == "conv_w":
        return (None, _fit(lshape[1], "model", mesh))
    if leaf in ("conv_b", "D", "dt_bias"):
        return (_fit(lshape[0], "model", mesh),)
    if leaf == "A_log":
        return (_fit(lshape[0], "model", mesh),) + (None,) * (nd - 1)
    # linear containers
    if len(path) >= 2:
        return _linear_spec(parent, leaf, lshape, mesh, head_aligned)
    return (None,) * nd


# stack depth of each top-level params subtree
_STACK_DEPTH = {"layers": 1, "dense_layers": 1, "encoder.layers": 1}


def param_specs(cfg: ArchConfig, params_tree: Any, mesh: Mesh):
    """PartitionSpec tree mirroring params (works on ShapeDtypeStructs)."""
    hybrid = cfg.family == "hybrid"
    sp_strategy = cfg.strategy == "sp"

    def rec(tree, path, nstack):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                ns = nstack
                if path == [] and k in ("layers", "dense_layers"):
                    ns = 2 if (hybrid and k == "layers") else 1
                elif path == ["encoder"] and k == "layers":
                    ns = 1
                out[k] = rec(v, path + [k], ns)
            return out
        shape = tuple(tree.shape)
        lshape = shape[nstack:]
        spec = _leaf_spec(path, lshape, mesh, cfg)
        if sp_strategy:  # "model" carries the sequence dim — weights FSDP-only
            spec = tuple(None if s == "model" else s for s in spec)
        return P(*((None,) * nstack + tuple(spec)))

    return rec(params_tree, [], 0)


def opt_state_specs(opt_state: Any, pspecs: Any):
    """PartitionSpec tree for an optimizer state that mirrors params slot
    by slot (``{slot: params-like tree}``, e.g. Adam's m/v): each leaf
    takes its parameter's spec, and the scalar placeholders kept for
    non-trainable (pattern) leaves replicate.  Works on arrays and
    ShapeDtypeStructs; a slot-free state (``()``) passes through."""
    return {k: jax.tree.map(lambda t, s: P() if len(t.shape) == 0 else s,
                            v, pspecs)
            for k, v in opt_state.items()} if opt_state else opt_state


def batch_specs(cfg: ArchConfig, batch_tree: Any, mesh: Mesh):
    seq_ax = "model" if cfg.strategy == "sp" else None

    def leaf(t):
        nd = len(t.shape)
        if nd == 0:
            return P()
        spec = [_dp_fit(t.shape[0], mesh)] + [None] * (nd - 1)
        if nd >= 2 and seq_ax:
            spec[1] = _fit(t.shape[1], seq_ax, mesh)
        return P(*spec)
    return jax.tree.map(leaf, batch_tree)


def cache_specs(cfg: ArchConfig, cache_tree: Any, mesh: Mesh):
    """Cache leaves all carry ≥1 stack dims then [B, S|state...].

    Rule: first dim(s) = layer stacks -> None; batch -> dp; the sequence /
    d_inner dim -> "model" (seq-sharded KV cache / channel-sharded SSM state).
    """
    def rec(tree, path):
        if isinstance(tree, dict):
            return {k: rec(v, path + [k]) for k, v in tree.items()}
        shape = tuple(tree.shape)
        leaf = path[-1]
        # explicit per-leaf handling (stack dims located by negative indexing)
        if leaf in ("k", "v", "ck", "cv"):          # [L,B,S,H,hd]
            b, s = shape[1], shape[2]
            return P(None, _dp_fit(b, mesh), _fit(s, "model", mesh), None, None)
        if leaf in ("latent", "k_rope"):            # [L,B,S,r]
            b, s = shape[1], shape[2]
            return P(None, _dp_fit(b, mesh), _fit(s, "model", mesh), None)
        if leaf == "conv":                          # [...,B,K-1,C]
            ns = len(shape) - 3
            return P(*([None] * ns), _dp_fit(shape[-3], mesh), None,
                     _fit(shape[-1], "model", mesh))
        if leaf == "ssm":
            if len(shape) >= 4 and cfg.ssm_kind == "mamba1":  # [L,B,di,N]
                return P(None, _dp_fit(shape[1], mesh),
                         _fit(shape[2], "model", mesh), None)
            # mamba2 [ns(,ev),B,H,hd,N]
            ns = len(shape) - 4
            return P(*([None] * ns), _dp_fit(shape[-4], mesh),
                     _fit(shape[-3], "model", mesh), None, None)
        return P(*([None] * len(shape)))

    return rec(cache_tree, [])


def logits_spec(cfg: ArchConfig, batch: int, mesh: Mesh):
    if cfg.strategy == "sp":  # [B, S, V] with seq on model (decode: S=1 -> repl)
        return P(_dp_fit(batch, mesh), None, None)
    vocab_ax = "model" if cfg.vocab % dict(mesh.shape)["model"] == 0 else None
    return P(_dp_fit(batch, mesh), None, vocab_ax)


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def attach(shape_tree, spec_tree, mesh: Mesh):
    """ShapeDtypeStruct tree + spec tree -> ShapeDtypeStructs with shardings."""
    return jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shape_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, P))
