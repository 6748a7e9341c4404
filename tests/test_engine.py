"""Engine switch: the fused Pallas path as the model-level execution path.

Verifies the acceptance criteria of the edge-bundle engine PRs: the whole
model forward/backward runs through engine="pallas" (interpret mode on
CPU) and matches engine="jnp" to tolerance; "auto" resolves to pallas
exactly on TPU backends; serving decodes through the kernels; density()
no longer host-syncs or under-reports; MoE expert FFNs run through the
expert-batched kernels with live row counts, whatever the routing skew,
identical to the reference loop; plus regression tests for the serving
PRNG-reuse, cache-growth-heuristic and bench --only silent-no-op fixes,
serve edge cases (early-EOS slot masking stays shape-stable, seeded
temperature sampling is deterministic), and the bench --tag meta stamp.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig
from repro.configs import registry
from repro.core import sparse_linear as sl
from repro.core.sparsity import SparsityConfig
from repro.models import model as M
from repro.models import moe as moe_mod


def _sparse_cfg(engine="auto", act="silu"):
    return ArchConfig(
        name="engine-test", family="dense", n_layers=2, d_model=128,
        n_heads=4, kv_heads=4, head_dim=32, d_ff=256, vocab=128,
        act=act, max_seq=64, attn_chunk=32, dtype="float32",
        sparsity=SparsityConfig(density=0.25, block=32, where="ffn"),
        engine=engine)


def _loss_and_grads(cfg, params, batch):
    def loss(p):
        l, _ = M.loss_fn(cfg, p, batch)
        return l
    return jax.value_and_grad(loss, allow_int=True)(params)


@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_model_forward_backward_pallas_vs_jnp(act):
    """Full train-path loss + grads agree between engines (fused epilogue
    included: silu exercises the gated MLP, gelu the plain one)."""
    cfg = _sparse_cfg(engine="jnp", act=act)
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (2, 16), 0, cfg.vocab)}
    l_jnp, g_jnp = _loss_and_grads(cfg, params, batch)
    cfg_p = dataclasses.replace(cfg, engine="pallas")
    l_pal, g_pal = _loss_and_grads(cfg_p, params, batch)
    np.testing.assert_allclose(float(l_jnp), float(l_pal), rtol=1e-5)
    flat1 = jax.tree.leaves(g_jnp)
    flat2 = jax.tree.leaves(g_pal)
    for a, b in zip(flat1, flat2):
        if jnp.issubdtype(a.dtype, jnp.inexact):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)


def test_serve_decode_pallas_matches_jnp():
    """Prefill + a few decode steps through the kernel engine produce the
    same tokens as the jnp path (serve plumbing: ServeConfig.engine)."""
    from repro.serve.engine import Engine, ServeConfig

    cfg = _sparse_cfg(engine="jnp")
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab))
    tok_jnp = Engine(cfg, params, ServeConfig(max_new_tokens=4)).generate(prompts)
    tok_pal = Engine(cfg, params, ServeConfig(max_new_tokens=4,
                                              engine="pallas")).generate(prompts)
    assert np.array_equal(tok_jnp, tok_pal)


def test_auto_resolves_by_backend():
    want = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert sl.resolve_engine("auto") == want
    assert sl.resolve_engine("pallas") == "pallas"
    assert sl.resolve_engine("jnp") == "jnp"
    with pytest.raises(ValueError):
        sl.resolve_engine("fpga")


# ------------------------------------------------------- MoE engine port
def _moe_cfg(engine="jnp", top_k=2, d_expert=64, where="ffn", held=0,
             first_held=0):
    return ArchConfig(
        name="moe-engine-test", family="moe", n_layers=1, d_model=128,
        n_heads=4, kv_heads=4, head_dim=32, d_ff=256, vocab=128,
        act="silu", max_seq=64, attn_chunk=32, dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=top_k, d_expert=d_expert,
                      held=held, first_held=first_held),
        sparsity=SparsityConfig(density=0.5, block=32, where=where),
        engine=engine)


def _moe_loss_and_grads(cfg, params, x, co):
    def loss(p):
        y, aux, _ = moe_mod.moe_apply(p, x, cfg)
        # rows first: one flat float32 sum of 8k products drifts by ~1e-5
        # of the result, as much as the rtol the engines are held to
        return jnp.sum(jnp.sum(y * co, axis=-1)) + aux
    return jax.value_and_grad(loss, allow_int=True)(params)


def _skewed(params, skew):
    """A router that sends inputs offset by 1 to expert 0 the more, the
    larger ``skew``."""
    d = params["router"].shape[0]
    return dict(params, router=params["router"].at[:, 0].add(skew / d))


@pytest.mark.parametrize("top_k,skew,held", [
    (1, 0.0, 0),
    (2, 0.0, 0),
    (2, 3.0, 0),     # skewed: expert 0 takes most slots
    (2, 1e3, 2),     # all to expert 0, two of four experts held
])
def test_moe_pallas_vs_jnp_fwd_bwd(top_k, skew, held):
    """Expert FFNs through the expert-batched fused kernels with live row
    counts match the reference gather+einsum loop over the whole buffer —
    loss, input grads and per-expert weight grads — at top-k > 1, under
    skewed routing (dead row tiles skipped, an expert with no rows) and
    with a share of the experts held."""
    cfg = _moe_cfg("jnp", top_k, held=held, first_held=held // 2)
    params = _skewed(moe_mod.moe_init(jax.random.PRNGKey(0), cfg), skew)
    assert "idx_in" in params and "rev_in_ob" in params
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model)) + 1
    co = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    _, _, st = moe_mod.moe_apply(params, x, cfg)
    assert int(st["moe_dropped_rows"]) == 0
    if skew > 100:   # confirm the skew: one expert with every token
        assert int(st["moe_max_expert_rows"]) == 64
    l_jnp, g_jnp = _moe_loss_and_grads(cfg, params, x, co)
    cfg_p = dataclasses.replace(cfg, engine="pallas")
    l_pal, g_pal = _moe_loss_and_grads(cfg_p, params, x, co)
    np.testing.assert_allclose(float(l_jnp), float(l_pal), rtol=1e-5)
    for k in sorted(g_jnp):
        if jnp.issubdtype(g_jnp[k].dtype, jnp.inexact):
            np.testing.assert_allclose(np.asarray(g_jnp[k]),
                                       np.asarray(g_pal[k]),
                                       rtol=2e-3, atol=2e-3, err_msg=k)


def test_moe_pallas_nob_ne_kb():
    """d_expert chosen so the expert junction has nob != kb — the shape
    class where the seed's _expert_apply weight slicing (axis 1, the
    output-block axis) would have shape-errored or silently transposed."""
    cfg = _moe_cfg("jnp", d_expert=128)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    nob, kb = params["wi"].shape[1], params["wi"].shape[2]
    assert nob != kb
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_jnp, _, _ = moe_mod.moe_apply(params, x, cfg)
    y_pal, _, _ = moe_mod.moe_apply(params, x,
                                    dataclasses.replace(cfg, engine="pallas"))
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_pal),
                               rtol=2e-4, atol=2e-4)


def test_moe_dense_expert_fallback():
    """When _expert_sparse_ok is false (sparsity scoped to attn only) the
    experts are dense einsums and the engine switch is a no-op — both
    engines run the identical dense path."""
    cfg = _moe_cfg("jnp", where="attn")
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    assert "idx_in" not in params and params["wi"].ndim == 3
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_jnp, aux_jnp, _ = moe_mod.moe_apply(params, x, cfg)
    y_pal, aux_pal, _ = moe_mod.moe_apply(
        params, x, dataclasses.replace(cfg, engine="pallas"))
    assert jnp.all(jnp.isfinite(y_jnp))
    np.testing.assert_array_equal(np.asarray(y_jnp), np.asarray(y_pal))
    assert float(aux_jnp) == float(aux_pal)


def test_moe_model_level_pallas_vs_jnp():
    """Whole moe-family train path (attn + routed experts through
    M.loss_fn) agrees between engines — exercises the stacked-layer scan
    over the int32 pattern/reverse-pattern param leaves."""
    cfg = _moe_cfg("jnp")
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (2, 16), 0, cfg.vocab)}
    l_jnp, g_jnp = _loss_and_grads(cfg, params, batch)
    cfg_p = dataclasses.replace(cfg, engine="pallas")
    l_pal, g_pal = _loss_and_grads(cfg_p, params, batch)
    np.testing.assert_allclose(float(l_jnp), float(l_pal), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_jnp), jax.tree.leaves(g_pal)):
        if jnp.issubdtype(a.dtype, jnp.inexact):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)


# ------------------------------------------------- serving bugfix regressions
def test_generate_uses_fresh_subkey_per_sample():
    """PRNG hygiene: every sampling call gets a distinct subkey and the
    root PRNGKey(seed) is only ever split, never consumed (the seed
    sampled the first token with the root key and then split it again)."""
    from repro.serve.engine import Engine, ServeConfig

    cfg = _sparse_cfg(engine="jnp")
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab))
    scfg = ServeConfig(max_new_tokens=4, temperature=1.0, seed=3)
    eng = Engine(cfg, params, scfg)
    seen = []
    orig = eng._sample

    def spy(logits, key):
        seen.append(tuple(np.asarray(key).tolist()))
        return orig(logits, key)

    eng._sample = spy
    tok1 = eng.generate(prompts)
    assert len(seen) == scfg.max_new_tokens
    assert len(set(seen)) == len(seen), "a PRNG key was consumed twice"
    root = tuple(np.asarray(jax.random.PRNGKey(scfg.seed)).tolist())
    assert root not in set(seen), "root key consumed by sampling"
    # deterministic per seed: a second generate reproduces the tokens
    tok2 = eng.generate(prompts)
    np.testing.assert_array_equal(tok1, tok2)


@pytest.mark.parametrize("name", [
    "stablelm-3b", "deepseek-v2-lite-16b", "falcon-mamba-7b",
    "zamba2-2.7b", "whisper-base",
])
def test_cache_seq_axes_metadata(name):
    """cache_seq_axes mirrors make_cache's structure exactly; seq-axis
    leaves scale with the seq argument on exactly that axis and state
    leaves (conv/ssm, cross-attn KV) are seq-independent."""
    cfg = registry.get(name).reduced()
    c8 = M.make_cache(cfg, 1, 8)
    c16 = M.make_cache(cfg, 1, 16)
    axes = M.cache_seq_axes(cfg)
    assert jax.tree.structure(axes) == jax.tree.structure(c8)

    def check(ax, a, b):
        if ax < 0:
            assert a.shape == b.shape
        else:
            assert a.shape[ax] == 8 and b.shape[ax] == 16
            sa, sb = list(a.shape), list(b.shape)
            sa[ax] = sb[ax] = 0
            assert sa == sb
    jax.tree.map(check, axes, c8, c16)


def test_grow_cache_places_by_metadata():
    """Attention leaves land at position 0 of their declared seq axis
    (zeros beyond), state leaves are copied wholesale — no shape
    guessing."""
    from repro.serve.engine import Engine, ServeConfig

    cfg = _sparse_cfg(engine="jnp")
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=4))
    src = jax.tree.map(lambda t: jnp.ones_like(t), M.make_cache(cfg, 2, 8))
    grown = eng._grow_cache(src, 2, 12, 8)

    def check_attn(ax, dst):
        assert ax >= 0 and dst.shape[ax] == 12
        d = np.moveaxis(np.asarray(dst), ax, 0)
        np.testing.assert_array_equal(d[:8], 1.0)
        np.testing.assert_array_equal(d[8:], 0.0)
    jax.tree.map(check_attn, M.cache_seq_axes(cfg), grown)

    # ssm family: conv/ssm are same-shape state leaves, copied exactly
    cfg2 = registry.get("falcon-mamba-7b").reduced()
    eng2 = Engine(cfg2, {})   # jit steps are built lazily; only cfg is used
    src2 = jax.tree.map(lambda t: jnp.full_like(t, 2.0),
                        M.make_cache(cfg2, 2, 8))
    grown2 = eng2._grow_cache(src2, 2, 12, 8)

    def check_state(ax, dst, s):
        assert ax < 0 and dst.shape == s.shape
        np.testing.assert_array_equal(np.asarray(dst), np.asarray(s))
    jax.tree.map(check_state, M.cache_seq_axes(cfg2), grown2, src2)


def test_eos_slot_masking_keeps_decode_shape_stable():
    """Early EOS must not change ANY shape: a finished slot keeps
    decoding into scratch and is masked to eos, the step-locked loop
    runs all max_new_tokens ticks, and unfinished slots are unaffected
    (the fixed-shape serving contract the population scheduler borrows
    its slot masking from)."""
    from repro.serve.engine import Engine, ServeConfig

    cfg = _sparse_cfg(engine="jnp")
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (3, 8), 0, cfg.vocab))
    n_new = 6
    free = Engine(cfg, params, ServeConfig(max_new_tokens=n_new)).generate(
        prompts)
    # force an early stop: sequence 0's second token becomes the EOS
    eos = int(free[0, 1])
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=n_new,
                                          eos_token=eos))
    calls = []
    orig = eng._decode

    def spy(params, cache, tok, pos):
        calls.append(tuple(tok.shape))
        return orig(params, cache, tok, pos)

    eng._decode = spy
    tok = eng.generate(prompts)
    assert tok.shape == (3, n_new)                  # output shape stable
    assert len(calls) == n_new - 1                  # no early loop exit
    assert all(s == (3, 1) for s in calls)          # per-tick shape stable
    for b in range(3):
        row = tok[b]
        hits = np.flatnonzero(row == eos)
        if hits.size:                               # after first eos: all eos
            np.testing.assert_array_equal(row[hits[0]:], eos)
        # up to (and including) each row's first eos, greedy decode is
        # unchanged by the masking
        stop = hits[0] + 1 if hits.size else n_new
        np.testing.assert_array_equal(row[:stop], free[b, :stop])


def test_temperature_sampling_deterministic_under_seed():
    """temperature > 0 sampling is a pure function of the seed: same
    seed -> identical tokens across fresh Engine instances, different
    seed -> a different draw."""
    from repro.serve.engine import Engine, ServeConfig

    cfg = _sparse_cfg(engine="jnp")
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, cfg.vocab))

    def gen(seed):
        scfg = ServeConfig(max_new_tokens=8, temperature=1.0, seed=seed)
        return Engine(cfg, params, scfg).generate(prompts)

    np.testing.assert_array_equal(gen(3), gen(3))
    assert not np.array_equal(gen(3), gen(4))


def test_bench_only_unknown_name_exits_nonzero(monkeypatch, tmp_path):
    """benchmarks/run.py --only with a typo'd name must exit nonzero and
    write no artifact (it used to print the CSV header, run nothing,
    exit 0 and write an empty --json artifact)."""
    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    import benchmarks.run as br

    art = tmp_path / "BENCH_typo.json"
    monkeypatch.setattr(sys, "argv",
                        ["run", "--only", "engin", "--json", str(art)])
    with pytest.raises(SystemExit) as ei:
        br.main()
    assert ei.value.code not in (0, None)
    assert not art.exists()


def test_bench_tag_threads_into_artifact_meta(monkeypatch, tmp_path):
    """--tag must land in the artifact's meta and round-trip through
    load_artifact; without --tag the filename-derived tag is kept (the
    stamp contract the sweep ledger shares)."""
    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    import benchmarks.engine_benches as eb
    import benchmarks.run as br

    monkeypatch.setattr(
        eb, "bench",
        lambda fast=True: [{"name": "engine.stub", "us_per_call": 1.0,
                            "derived": "stub"}])
    art = tmp_path / "BENCH_fromfile.json"
    monkeypatch.setattr(sys, "argv", ["run", "--only", "engine",
                                      "--json", str(art), "--tag", "pr5"])
    br.main()
    meta, results = br.load_artifact(str(art))
    assert meta["tag"] == "pr5"
    assert results == {"engine.stub": 1.0}
    # no --tag: derived from the BENCH_<tag>.json filename
    monkeypatch.setattr(sys, "argv", ["run", "--only", "engine",
                                      "--json", str(art)])
    br.main()
    meta, _ = br.load_artifact(str(art))
    assert meta["tag"] == "fromfile"


def test_density_static_and_exact():
    """density() must not depend on idx *values* (no host sync, exact even
    when the top input block is unused by the pattern)."""
    sp = SparsityConfig(density=0.25, block=32)
    p = sl.init_sparse(jax.random.PRNGKey(0), 256, 128, sp)
    nib, kb = p["rev_ob"].shape[0], p["w"].shape[1]
    assert sl.density(p) == kb / nib
    # drop every reference to the last input block: density unchanged
    # (the junction still spans 256 inputs, some now unconnected)
    p2 = dict(p, idx=jnp.zeros_like(p["idx"]))
    assert sl.density(p2) == sl.density(p)
    # and it works under trace (would raise ConcretizationTypeError if the
    # implementation synced idx values to host)
    @jax.jit
    def f(p):
        return jnp.float32(sl.density(p))
    assert float(f(p)) == pytest.approx(kb / nib)
