"""A dense decoder language model with sparse FFN junctions, as the
program's ``ArchConfig`` and parameter tree take it, built from the
configuration file's keys (Hugging Face names).

The benchmark makes the weights itself, from the run's seed, in one
jitted call on the device: ``flat_weights`` gives them by name in
float32 (the names are the program's tree paths, joined by ``/``), and
``to_program`` nests them into the program's tree at its parameter
dtype with the benchmark's block patterns beside each junction.  The
reference takes the same flat weights, rounded to the same dtype.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import patterns as pat_mod
from chipbench import work

JUNCTIONS = ("wg", "wi", "wo")


def head_dim(conf) -> int:
    return conf["hidden_size"] // conf["num_attention_heads"]


def patterns(conf) -> dict:
    sp = conf["sparse_ffn"]
    d, f = conf["hidden_size"], conf["intermediate_size"]
    dims = {"wg": (d, f), "wi": (d, f), "wo": (f, d)}
    return {k: pat_mod.block_pattern(*dims[k], sp["density"], sp["block"],
                                     sp["pattern_seeds"][k])
            for k in JUNCTIONS}


def shape(conf) -> work.DecoderShape:
    sp = conf["sparse_ffn"]
    d, f = conf["hidden_size"], conf["intermediate_size"]
    return work.DecoderShape(
        layers=conf["num_hidden_layers"], d_model=d,
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=head_dim(conf),
        vocab=conf["vocab_size"],
        ffn=(work.junction(d, f, sp["density"], sp["block"]),
             work.junction(d, f, sp["density"], sp["block"]),
             work.junction(f, d, sp["density"], sp["block"])))


def arch_config(conf, *, param_dtype: str, fused_update: bool):
    from repro.configs.base import ArchConfig
    from repro.core.sparsity import SparsityConfig
    sp = conf["sparse_ffn"]
    if conf["hidden_act"] != "silu" or conf["use_qkv_bias"] \
            or conf["tie_word_embeddings"] or conf["use_parallel_residual"]:
        raise ValueError("dense_lm covers SwiGLU, no qkv bias, untied, "
                         "sequential-residual decoders")
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=head_dim(conf),
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        raw_vocab=conf["vocab_size"],
        partial_rotary=conf["partial_rotary_factor"],
        rope_theta=float(conf["rope_theta"]), norm="layernorm",
        norm_eps=conf["layer_norm_eps"], act="silu",
        max_seq=conf["max_position_embeddings"],
        sparsity=SparsityConfig(density=sp["density"], block=sp["block"],
                                where="ffn"),
        engine="pallas", dtype=conf["compute_dtype"],
        param_dtype=param_dtype, fused_update=fused_update)


def leaf_shapes(conf, pats) -> dict:
    L, d, V = (conf["num_hidden_layers"], conf["hidden_size"],
               conf["vocab_size"])
    H, Hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  head_dim(conf))
    bs = conf["sparse_ffn"]["block"]
    out = {"embed/tok": (V, d), "embed/out": (d, V),
           "final_norm/scale": (d,), "final_norm/bias": (d,)}
    for n in ("norm1", "norm2"):
        out[f"layers/{n}/scale"] = (L, d)
        out[f"layers/{n}/bias"] = (L, d)
    out["layers/attn/wq/w"] = (L, d, H * hd)
    out["layers/attn/wk/w"] = (L, d, Hkv * hd)
    out["layers/attn/wv/w"] = (L, d, Hkv * hd)
    out["layers/attn/wo/w"] = (L, H * hd, d)
    for k in JUNCTIONS:
        nob, kb = pats[k]["idx"].shape
        out[f"layers/mlp/{k}/w"] = (L, nob, kb, bs, bs)
    return out


def _std(conf, pats, name) -> float:
    d = conf["hidden_size"]
    if name.endswith("/scale") or name.endswith("/bias"):
        return 0.0
    if name == "layers/attn/wo/w":
        return float(1 / np.sqrt(conf["num_attention_heads"] * head_dim(conf)))
    if name.startswith("layers/mlp/"):
        p = pats[name.split("/")[2]]
        bs = conf["sparse_ffn"]["block"]
        fan_in = p["idx"].shape[1] * bs
        fan_out = p["rev_ob"].shape[1] * bs
        return float(np.sqrt(2.0 / (fan_in + fan_out)))
    return float(1 / np.sqrt(d))


def flat_weights(conf, pats, key) -> dict:
    """The initial weights by name, float32 (traceable)."""
    out = {}
    for i, (name, shp) in enumerate(sorted(leaf_shapes(conf, pats).items())):
        if name.endswith("/scale"):
            out[name] = jnp.ones(shp, jnp.float32)
        elif name.endswith("/bias"):
            out[name] = jnp.zeros(shp, jnp.float32)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shp,
                                           jnp.float32)
                         * _std(conf, pats, name))
    return out


def to_program(conf, pats, flat, dtype) -> dict:
    L = conf["num_hidden_layers"]
    tree: dict = {}
    for name, v in flat.items():
        node = tree
        *path, leaf = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = v.astype(dtype)
    for k in JUNCTIONS:
        j = tree["layers"]["mlp"][k]
        for leaf, arr in pats[k].items():
            j[leaf] = jnp.broadcast_to(jnp.asarray(arr), (L,) + arr.shape)
    return tree


def flatten(tree, names) -> dict:
    """The leaves of a program tree (params or an optimizer slot tree)
    named like ``flat_weights``, for the names given."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        if name in names:
            out[name] = leaf
    return out


def leaf_norms(flat: dict) -> dict:
    """Float32 norm of each leaf; a leaf stacked over layers gives one
    norm per layer, named ``layers/<i>/...``."""
    out = {}
    for name, v in flat.items():
        v = v.astype(jnp.float32)
        if name.startswith("layers/"):
            n = jnp.sqrt(jnp.sum(jnp.square(v.reshape(v.shape[0], -1)),
                                 axis=1))
            for i in range(v.shape[0]):
                out[name.replace("layers/", f"layers/{i}/", 1)] = n[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


def sample_index(shapes: dict, k: int = 4096, seed: int = 0) -> dict:
    """Fixed positions to compare elementwise: up to ``k`` per leaf (per
    layer of a stacked leaf), the same for every run."""
    rng = np.random.default_rng(seed)
    out = {}
    for name in sorted(shapes):
        shp = shapes[name]
        n = int(np.prod(shp[1:] if name.startswith("layers/") else shp))
        out[name] = np.sort(rng.choice(n, min(k, n), replace=False))
    return out


def leaf_samples(flat: dict, index: dict) -> dict:
    """The values of each leaf at ``index``, float32, per layer."""
    out = {}
    for name, v in flat.items():
        v = v.astype(jnp.float32)
        if name.startswith("layers/"):
            s = v.reshape(v.shape[0], -1)[:, index[name]]
            for i in range(v.shape[0]):
                out[name.replace("layers/", f"layers/{i}/", 1)] = s[i]
        else:
            out[name] = v.reshape(-1)[index[name]]
    return out


def change_norms(new: dict, old: dict) -> dict:
    return leaf_norms({k: new[k].astype(jnp.float32)
                       - old[k].astype(jnp.float32) for k in old})


@dataclasses.dataclass
class TokenBatches:
    """Language-model batches from the run's seed: arithmetic runs of
    token ids from a random start per row, with 15% of positions replaced
    by random ids, so the loss can fall (the formula of the program's
    ``data/pipeline.LMTokenPipeline``, kept here with the benchmark).
    Batch ``i`` is a pure function of (seed, i); every row differs."""
    seed: int
    batch: int
    seq: int
    vocab: int
    noise: float = 0.15

    def __call__(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([int(self.seed) & (2**63 - 1), 7, i])
        B, S, V = self.batch, self.seq, self.vocab
        base = rng.integers(0, V - S - 2, size=(B, 1))
        runs = base + np.arange(S)[None, :]
        noise = rng.integers(0, V, size=(B, S))
        mask = rng.random((B, S)) < self.noise
        return np.where(mask, noise, runs % V).astype(np.int32)
