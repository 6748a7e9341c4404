"""Sharding-rule invariants over every assigned arch x both meshes.

Uses AbstractMesh — no devices needed, so the production 512-chip layouts
are checkable in the normal test process.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import registry
from repro.models import model as M
from repro.parallel import sharding as sh


MESHES = {
    "single": AbstractMesh((16, 16), ("data", "model")),
    "multi": AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}
ARCHS = list(registry.ARCHS)


@functools.lru_cache(maxsize=None)
def _pshapes(arch):
    cfg = registry.get(arch)
    return cfg, jax.eval_shape(functools.partial(M.init, cfg),
                               jax.random.PRNGKey(0))


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_param_specs_divisible(arch, mesh_name):
    """Every sharded dim divides its mesh axis; spec rank == leaf rank."""
    cfg, pshapes = _pshapes(arch)
    mesh = MESHES[mesh_name]
    sizes = dict(mesh.shape)
    specs = sh.param_specs(cfg, pshapes, mesh)

    leaves = jax.tree.leaves_with_path(pshapes)
    spec_leaves = {jax.tree_util.keystr(k): v
                   for k, v in jax.tree.leaves_with_path(
                       specs, is_leaf=lambda x: isinstance(x, P))}
    for key, leaf in leaves:
        spec = spec_leaves[jax.tree_util.keystr(key)]
        assert len(spec) <= len(leaf.shape), (key, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= sizes[a]
            assert dim % n == 0, (key, spec, leaf.shape)


@pytest.mark.parametrize("arch", ["whisper-base"])
def test_sp_strategy_never_model_shards_weights(arch):
    cfg, pshapes = _pshapes(arch)
    specs = sh.param_specs(cfg, pshapes, MESHES["single"])
    for k, spec in jax.tree.leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)):
        assert "model" not in [a for a in spec if isinstance(a, str)], (k, spec)


@pytest.mark.parametrize("arch", ARCHS)
def test_cache_specs_shard_sequence(arch):
    cfg = registry.get(arch)
    cshapes = jax.eval_shape(lambda: M.make_cache(cfg, 128, 32768))
    specs = sh.cache_specs(cfg, cshapes, MESHES["single"])
    # at least one leaf must shard on model (seq or state channels)
    found = any("model" in [a for a in spec if isinstance(a, str)]
                for _, spec in jax.tree.leaves_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P)))
    assert found, f"{arch}: cache entirely replicated on model axis"


def test_batch_specs_b1_replicates():
    cfg = registry.get("falcon-mamba-7b")
    spec = sh.batch_specs(cfg, {"tokens": jax.ShapeDtypeStruct((1, 524288), jnp.int32)},
                          MESHES["multi"])
    assert spec["tokens"][0] is None     # batch 1 cannot shard


def test_junction_matmul_shard_map_smoke():
    """ROADMAP follow-up: the unified junction engine composes with
    shard_map — on a 1-device mesh the wrapped kernel (batch rows sharded
    over "data") matches the unwrapped result forward AND backward (the
    custom_vjp, including the in-kernel reverse-weight DMA, traces under
    shard_map)."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.sparsity import make_block_pattern
    from repro.kernels import ops

    bs = 8
    pat = make_block_pattern(6 * bs, 4 * bs, 0.34, bs)
    idx, rob, rt, rc = (jnp.asarray(pat.idx), jnp.asarray(pat.rev_ob),
                        jnp.asarray(pat.rev_t), jnp.asarray(pat.rev_cnt))
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    M = 32
    x = jax.random.normal(ks[0], (M, 6 * bs))
    w = jax.random.normal(ks[1], (pat.n_out_blocks, pat.fan_in_blocks,
                                  bs, bs)) * 0.1
    b = jax.random.normal(ks[2], (4 * bs,)) * 0.3
    co = jax.random.normal(ks[3], (M, 4 * bs))

    def apply_fn(x, w, b):
        return ops.junction_matmul(x, w, idx, rob, rt, rc, bias=b, act="silu")

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    wrapped = jax.shard_map(apply_fn, mesh=mesh,
                            in_specs=(P("data"), P(), P()),
                            out_specs=P("data"), check_vma=False)

    y_ref = apply_fn(x, w, b)
    y_map = wrapped(x, w, b)
    np.testing.assert_allclose(np.asarray(y_map), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)

    loss_ref = lambda x, w, b: jnp.sum(apply_fn(x, w, b) * co)
    loss_map = lambda x, w, b: jnp.sum(wrapped(x, w, b) * co)
    g_ref = jax.grad(loss_ref, (0, 1, 2))(x, w, b)
    g_map = jax.grad(loss_map, (0, 1, 2))(x, w, b)
    for a, gm, name in zip(g_ref, g_map, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(gm), np.asarray(a),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_attention_head_guard():
    """whisper q/k/v/o replicate (8 heads < 16); qwen2 q shards, kv replicate."""
    cfgw, pw = _pshapes("whisper-base")
    cfgq, pq = _pshapes("qwen2-72b")
    mesh = MESHES["single"]
    sw = sh.param_specs(cfgw, pw, mesh)
    sq = sh.param_specs(cfgq, pq, mesh)
    assert sw["layers"]["attn"]["wq"]["w"] == P(None, "data", None)
    assert sq["layers"]["attn"]["wq"]["w"] == P(None, "data", "model")
    assert sq["layers"]["attn"]["wk"]["w"] == P(None, "data", None)
