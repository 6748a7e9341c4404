"""A DeepSeek-V2-style decoder (latent attention, a dense first layer,
then expert layers with shared experts) as the program's ``ArchConfig``
and parameter tree take it, built from the configuration file's keys
(Hugging Face names), cut to one chip's share of an expert-parallel
deployment: ``n_routed_experts`` experts held from ``first_held_expert``
of the router's ``router_experts``, and a ``vocab_held`` slice of the
vocabulary.

As for ``dense_lm``, the benchmark makes the weights from the run's seed
in one jitted call: ``flat_weights`` gives them by name in float32 (the
program's tree paths joined by ``/``), ``to_program`` nests them at the
parameter dtype with the benchmark's block patterns beside each
junction, and the reference takes the same flat weights.  Norms of the
expert weights are taken per layer and per expert, so a check sees one
expert's update go missing.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench
from chipbench import patterns as pat_mod
from chipbench import work_moe

_dense = bench.load_module(bench.HERE / "adapters" / "dense_lm.py")

STACKED = ("layers/", "dense_layers/")
EXPERT_LEAVES = ("layers/moe/wg", "layers/moe/wi", "layers/moe/wo")


def _dims(conf) -> dict:
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    fs = f * conf["n_shared_experts"]
    return {"expert_in": (d, f), "expert_out": (f, d), "shared_wg": (d, fs),
            "shared_wi": (d, fs), "shared_wo": (fs, d)}


def patterns(conf) -> dict:
    sp = conf["sparse_ffn"]
    return {k: pat_mod.block_pattern(*dims, sp["density"], sp["block"],
                                     sp["pattern_seeds"][k])
            for k, dims in _dims(conf).items()}


def shape(conf) -> work_moe.MoEShape:
    sp = conf["sparse_ffn"]
    j = {k: work_moe.junction(*dims, sp["density"], sp["block"])
         for k, dims in _dims(conf).items()}
    return work_moe.MoEShape(
        layers=conf["num_hidden_layers"],
        dense_layers=conf["first_k_dense_replace"],
        d_model=conf["hidden_size"], heads=conf["num_attention_heads"],
        q_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        rope_dim=conf["qk_rope_head_dim"], v_dim=conf["v_head_dim"],
        kv_lora=conf["kv_lora_rank"], dense_ffn=conf["intermediate_size"],
        router=conf["router_experts"], top_k=conf["num_experts_per_tok"],
        held=conf["n_routed_experts"], expert=(j["expert_in"], j["expert_in"], j["expert_out"]),
        shared=(j["shared_wg"], j["shared_wi"], j["shared_wo"]),
        vocab=conf["vocab_held"])


def arch_config(conf, *, param_dtype: str, fused_update: bool):
    from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,
                                    RopeScaling)
    from repro.core.sparsity import SparsityConfig
    if (conf["hidden_act"] != "silu" or conf["q_lora_rank"] is not None
            or conf["tie_word_embeddings"] or conf["attention_bias"]
            or conf["scoring_func"] != "softmax"
            or conf["topk_method"] != "greedy" or conf["n_group"] != 1
            or conf["moe_layer_freq"] != 1):
        raise ValueError("moe_lm covers SwiGLU, no q-LoRA, untied, softmax "
                         "greedy routing, every layer after the dense ones "
                         "an expert layer")
    rs = conf["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling {rs['type']!r}: moe_lm covers yarn")
    sp = conf["sparse_ffn"]
    V = conf["vocab_held"]
    return ArchConfig(
        name=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["v_head_dim"],
        d_ff=conf["intermediate_size"], vocab=V, raw_vocab=V,
        attn_kind="mla",
        mla=MLAConfig(kv_lora_rank=conf["kv_lora_rank"],
                      qk_nope_head_dim=conf["qk_nope_head_dim"],
                      qk_rope_head_dim=conf["qk_rope_head_dim"],
                      v_head_dim=conf["v_head_dim"]),
        moe=MoEConfig(
            num_experts=conf["router_experts"],
            top_k=conf["num_experts_per_tok"],
            d_expert=conf["moe_intermediate_size"],
            num_shared=conf["n_shared_experts"],
            d_shared=conf["n_shared_experts"] * conf["moe_intermediate_size"],
            aux_loss_weight=conf["aux_alpha"],
            aux_loss="sequence" if conf["seq_aux"] else "batch",
            norm_topk_prob=conf["norm_topk_prob"],
            routed_scale=float(conf["routed_scaling_factor"]),
            held=conf["n_routed_experts"],
            first_held=conf["first_held_expert"],
            first_dense_layers=conf["first_k_dense_replace"]),
        rope_theta=float(conf["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        norm="rmsnorm", norm_eps=conf["rms_norm_eps"], act="silu",
        attn_chunk=conf["attn_chunk"],
        sparsity=SparsityConfig(density=sp["density"], block=sp["block"],
                                where="ffn"),
        engine="pallas", dtype=conf["compute_dtype"],
        param_dtype=param_dtype, fused_update=fused_update)


def leaf_shapes(conf, pats) -> dict:
    """Name -> shape of every trainable leaf of the program's tree."""
    from repro.models import model as M
    arch = arch_config(conf, param_dtype="float32", fused_update=False)
    tree = jax.eval_shape(lambda k: M.init(arch, k), jax.random.PRNGKey(0))
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            out["/".join(str(k.key) for k in path)] = tuple(leaf.shape)
    for name, key in (("layers/moe/wg", "expert_in"),
                      ("layers/moe/wo", "expert_out"),
                      ("layers/moe/shared/wo/w", "shared_wo")):
        nob, kb = pats[key]["idx"].shape
        assert out[name][-4:-2] == (nob, kb), (name, out[name], nob, kb)
    return out


def _std(conf, pats, name) -> float:
    d = conf["hidden_size"]
    if name.endswith("/scale"):
        return 0.0
    if name.endswith("attn/wo/w"):
        return float(1 / np.sqrt(conf["num_attention_heads"]
                                 * conf["v_head_dim"]))
    if name.endswith("attn/wkv_b/w"):
        return float(1 / np.sqrt(conf["kv_lora_rank"]))
    if name == "dense_layers/mlp/wo/w":
        return float(1 / np.sqrt(conf["intermediate_size"]))
    key = {"layers/moe/wg": "expert_in", "layers/moe/wi": "expert_in",
           "layers/moe/wo": "expert_out",
           "layers/moe/shared/wg/w": "shared_wg",
           "layers/moe/shared/wi/w": "shared_wi",
           "layers/moe/shared/wo/w": "shared_wo"}.get(name)
    if key is not None:
        p = pats[key]
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
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shp,
                                           jnp.float32)
                         * _std(conf, pats, name))
    return out


def to_program(conf, pats, flat, dtype) -> dict:
    L = conf["num_hidden_layers"] - conf["first_k_dense_replace"]
    tree: dict = {}
    for name, v in flat.items():
        node = tree
        *path, leaf = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = v.astype(dtype)

    def stack(arr):
        return jnp.broadcast_to(jnp.asarray(arr), (L,) + arr.shape)

    moe = tree["layers"]["moe"]
    for side, key in (("in", "expert_in"), ("out", "expert_out")):
        p = pats[key]
        moe[f"idx_{side}"] = stack(p["idx"])
        for leaf in ("rev_ob", "rev_t", "rev_cnt"):
            moe[leaf.replace("rev_", f"rev_{side}_")] = stack(p[leaf])
    for k in ("wg", "wi", "wo"):
        for leaf, arr in pats[f"shared_{k}"].items():
            moe["shared"][k][leaf] = stack(arr)
    return tree


# the leaves of a program tree named like ``flat_weights``
flatten = _dense.flatten


def _per_layer(name: str, i: int) -> str:
    head, rest = name.split("/", 1)
    return f"{head}/{i}/{rest}"


def leaf_norms(flat: dict) -> dict:
    """Float32 norm of each leaf; a stacked leaf gives one norm per layer
    (``layers/<i>/...``), an expert leaf one per layer and expert
    (``layers/<i>/moe/wg/<e>``)."""
    out = {}
    for name, v in flat.items():
        v = v.astype(jnp.float32)
        if name in EXPERT_LEAVES:
            n = jnp.sqrt(jnp.sum(jnp.square(v.reshape(*v.shape[:2], -1)),
                                 axis=2))
            for i in range(v.shape[0]):
                for e in range(v.shape[1]):
                    out[f"{_per_layer(name, i)}/{e}"] = n[i, e]
        elif name.startswith(STACKED):
            n = jnp.sqrt(jnp.sum(jnp.square(v.reshape(v.shape[0], -1)),
                                 axis=1))
            for i in range(v.shape[0]):
                out[_per_layer(name, i)] = n[i]
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
        n = int(np.prod(shp[1:] if name.startswith(STACKED) else shp))
        out[name] = np.sort(rng.choice(n, min(k, n), replace=False))
    return out


def leaf_samples(flat: dict, index: dict) -> dict:
    """The values of each leaf at ``index``, float32, per layer."""
    out = {}
    for name, v in flat.items():
        v = v.astype(jnp.float32)
        if name.startswith(STACKED):
            s = v.reshape(v.shape[0], -1)[:, index[name]]
            for i in range(v.shape[0]):
                out[_per_layer(name, i)] = s[i]
        else:
            out[name] = v.reshape(-1)[index[name]]
    return out


def change_norms(new: dict, old: dict) -> dict:
    return leaf_norms({k: new[k].astype(jnp.float32)
                       - old[k].astype(jnp.float32) for k in old})


@dataclasses.dataclass
class TokenBatches:
    """Language-model batches from the run's seed: token ids uniform over
    the held vocabulary slice, every row drawn anew (rows differ), no
    packing.  Batch ``i`` is a pure function of (seed, i)."""
    seed: int
    batch: int
    seq: int
    vocab: int

    def __call__(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([int(self.seed) & (2**63 - 1), 11, i])
        return rng.integers(0, self.vocab, size=(self.batch, self.seq),
                            dtype=np.int32)
