"""Fused BP+UP (ISSUE 4/7): the in-kernel weight update vs the two-pass
reference.

The contract under test: with ``ArchConfig.fused_update`` + a
``FusedOptimizer`` (fused_sgd / fused_adam) on the pallas engine, the
backward kernels apply the optimizer update in their epilogue — the hyp
row is the (HYP_K,) registry row of kernels/block_sparse_matmul.HYP_COLS
— and the train step's "grads" tree carries UPDATED params at junction
leaves; dw never materializes in HBM (the kernel-name jaxpr checks
below), and the resulting params/opt state match the two-pass reference
that materializes gradients and tree-maps the update.  Plus: Adam's
3-step bias-correction carry, bf16 params with fp32 accumulator slots,
grad-clip (norm pre-pass folded into the gs column) and microbatch
(full-batch identity) configs now running FUSED against their two-pass
references, the remaining refusals, the coalesced reverse-DMA pattern
with contiguous runs, and the make_train_step donation default.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig
from repro.core import sparse_linear as sl
from repro.core.interleaver import reverse_block_pattern
from repro.core.sparsity import SparsityConfig, make_block_pattern
from repro.kernels import ops
from repro.models import model as M
from repro.optim import (FusedSGD, adam, constant_schedule, fused_adam,
                         fused_sgd)
from repro.train.steps import fused_update_eligible, make_train_step


def _dense_cfg(**kw):
    base = dict(
        name="fused-test", family="dense", n_layers=2, d_model=128,
        n_heads=4, kv_heads=4, head_dim=32, d_ff=256, vocab=128,
        act="silu", max_seq=64, attn_chunk=32, dtype="float32",
        param_dtype="float32",
        sparsity=SparsityConfig(density=0.25, block=32, where="ffn"),
        engine="pallas", fused_update=True)
    base.update(kw)
    return ArchConfig(**base)


def _moe_cfg(**kw):
    base = dict(
        name="fused-moe-test", family="moe", n_layers=1, d_model=128,
        n_heads=4, kv_heads=4, head_dim=32, d_ff=256, vocab=128,
        act="silu", max_seq=64, attn_chunk=32, dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, d_expert=64),
        sparsity=SparsityConfig(density=0.5, block=32, where="ffn"),
        engine="pallas", fused_update=True)
    base.update(kw)
    return ArchConfig(**base)


def _batch(cfg, key=1):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(key), (2, 16),
                                         0, cfg.vocab)}


def _assert_trees_close(t1, t2, rtol, atol):
    kv1 = jax.tree_util.tree_flatten_with_path(t1)[0]
    kv2 = jax.tree_util.tree_flatten_with_path(t2)[0]
    assert [k for k, _ in kv1] == [k for k, _ in kv2]
    for (k, a), (_, b) in zip(kv1, kv2):
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=rtol, atol=atol, err_msg=str(k))


# ----------------------------------------------------------- junction level
def _mnist_junction(dtype=jnp.float32):
    """The paper's MNIST junction in block form (1024 -> 512 @ kb=2)."""
    sp = SparsityConfig(density=0.25, block=128, where="ffn")
    p = sl.init_sparse(jax.random.PRNGKey(0), 1024, 512, sp, bias=True,
                       dtype=dtype)
    return p


@pytest.mark.parametrize("momentum", [0.0, 0.9])
@pytest.mark.parametrize("act", ["none", "sigmoid"])
def test_mnist_junction_fused_matches_two_pass(momentum, act):
    """Acceptance: fused params == two-pass sgd/momentum reference on the
    paper MNIST junction (fwd+bwd+update), to fp32 round-off."""
    p = _mnist_junction()
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 1024))
    co = jax.random.normal(jax.random.PRNGKey(2), (96, 512))
    lr = 0.05
    hyp = jnp.asarray([lr, momentum], jnp.float32)
    mom = jnp.zeros(p["w"].shape, jnp.float32) if momentum else None
    mom_b = jnp.zeros(p["b"].shape, jnp.float32) if momentum else None
    pat = (p["idx"], p["rev_ob"], p["rev_t"], p["rev_cnt"])

    def loss_ref(w, b):
        y = ops.junction_matmul(x, w, *pat, bias=b, act=act)
        return jnp.sum(y * co)

    gw, gb = jax.grad(loss_ref, (0, 1))(p["w"], p["b"])
    mv = momentum * mom + gw if momentum else gw
    mbv = momentum * mom_b + gb if momentum else gb
    ref_w = p["w"] - lr * mv
    ref_b = p["b"] - lr * mbv

    def loss_fused(w, b, m, mb):
        y = ops.junction_train_update(x, w, *pat, bias=b, act=act, hyp=hyp,
                                      mom=m, mom_b=mb)
        return jnp.sum(y * co)

    argnums = (0, 1, 2, 3) if momentum else (0, 1)
    got = jax.grad(loss_fused, argnums)(p["w"], p["b"], mom, mom_b)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref_w),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref_b),
                               rtol=1e-5, atol=1e-6)
    if momentum:
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(mv),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got[3]), np.asarray(mbv),
                                   rtol=1e-5, atol=1e-6)


def test_mnist_junction_fused_adam_three_step_carry():
    """Acceptance (ISSUE 7): in-kernel Adam on the paper MNIST junction
    matches the two-pass reference formula over 3 steps — the m/v slots
    and the bias-correction time t carry across steps through the
    aliased-cotangent contract."""
    p = _mnist_junction()
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 1024))
    co = jax.random.normal(jax.random.PRNGKey(2), (96, 512))
    lr, b1, b2, eps, wd = 1e-3, 0.9, 0.95, 1e-8, 0.01
    pat = (p["idx"], p["rev_ob"], p["rev_t"], p["rev_cnt"])
    w, b = p["w"], p["b"]
    m = jnp.zeros(w.shape, jnp.float32)
    v = jnp.zeros(w.shape, jnp.float32)
    mb = jnp.zeros(b.shape, jnp.float32)
    vb = jnp.zeros(b.shape, jnp.float32)
    rw, rb, rm, rv, rmb, rvb = w, b, m, v, mb, vb

    def loss_ref(w, b):
        y = ops.junction_matmul(x, w, *pat, bias=b, act="sigmoid")
        return jnp.sum(y * co)

    def loss_fused(w, b, m, mb, v, vb, hyp):
        y = ops.junction_train_update(x, w, *pat, bias=b, act="sigmoid",
                                      hyp=hyp, mom=m, mom_b=mb,
                                      vel=v, vel_b=vb)
        return jnp.sum(y * co)

    for t in range(1, 4):
        hyp = jnp.asarray([lr, b1, b2, eps, wd, t, 1.0], jnp.float32)
        w, b, m, mb, v, vb = jax.grad(loss_fused, (0, 1, 2, 3, 4, 5))(
            w, b, m, mb, v, vb, hyp)
        gw, gb = jax.grad(loss_ref, (0, 1))(rw, rb)
        c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        rm = b1 * rm + (1 - b1) * gw
        rv = b2 * rv + (1 - b2) * jnp.square(gw)
        rw = rw - lr * ((rm / c1) / (jnp.sqrt(rv / c2) + eps) + wd * rw)
        rmb = b1 * rmb + (1 - b1) * gb
        rvb = b2 * rvb + (1 - b2) * jnp.square(gb)
        rb = rb - lr * ((rmb / c1) / (jnp.sqrt(rvb / c2) + eps) + wd * rb)
    for got, ref in ((w, rw), (b, rb), (m, rm), (v, rv), (mb, rmb),
                     (vb, rvb)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-6)


def test_expert_gated_junction_fused_matches_two_pass():
    """Expert-batched gated configuration: both weight streams updated in
    one fused pass, shared pattern, E > 1."""
    bs, E = 32, 3
    pat = make_block_pattern(8 * bs, 6 * bs, 0.34, bs)
    idx, rob, rt, rc = map(jnp.asarray, (pat.idx, pat.rev_ob, pat.rev_t,
                                         pat.rev_cnt))
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (E, 40, 8 * bs))
    wg = jax.random.normal(ks[1], (E, pat.n_out_blocks, pat.fan_in_blocks,
                                   bs, bs)) * 0.1
    wi = jax.random.normal(ks[2], wg.shape) * 0.1
    co = jax.random.normal(ks[3], (E, 40, 6 * bs))
    lr, beta = 0.05, 0.9
    hyp = jnp.asarray([lr, beta], jnp.float32)
    mg = jnp.ones(wg.shape, jnp.float32) * 0.01
    mi = jnp.ones(wi.shape, jnp.float32) * 0.02

    def loss_ref(wg, wi):
        return jnp.sum(ops.junction_matmul(x, wg, idx, rob, rt, rc, wi=wi) * co)

    gwg, gwi = jax.grad(loss_ref, (0, 1))(wg, wi)

    def loss_fused(wg, wi, mg, mi):
        return jnp.sum(ops.junction_train_update(
            x, wg, idx, rob, rt, rc, wi=wi, hyp=hyp, mom=mg, mom_wi=mi) * co)

    nwg, nwi, nmg, nmi = jax.grad(loss_fused, (0, 1, 2, 3))(wg, wi, mg, mi)
    np.testing.assert_allclose(np.asarray(nmg), np.asarray(beta * mg + gwg),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nwg),
                               np.asarray(wg - lr * (beta * mg + gwg)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nwi),
                               np.asarray(wi - lr * (beta * mi + gwi)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nmi), np.asarray(beta * mi + gwi),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("adam", [False, True], ids=["momentum", "adam"])
def test_expert_biased_junction_fused_matches_two_pass(adam):
    """E=3 biased units with per-unit hyp rows (the population sweep's
    operands): the [E, 1, N] bias and bias-slot blocks of update_dw update
    each unit's bias from its own gradient, matching the two-pass
    per-unit reference."""
    from repro.kernels import block_sparse_matmul as bsm
    from repro.search import population as pop
    E, n_in, n_out, bs, M = 3, 128, 96, 32, 40
    pat = make_block_pattern(n_in, n_out, 0.5, bs)
    patt = tuple(jnp.asarray(a) for a in (pat.idx, pat.rev_ob, pat.rev_t,
                                          pat.rev_cnt))
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    w = jax.random.normal(ks[0], (E, pat.n_out_blocks, pat.fan_in_blocks,
                                  bs, bs)) * 0.1
    b = jax.random.normal(ks[1], (E, n_out)) * 0.3
    x = jax.random.normal(ks[2], (E, M, n_in))
    co = jax.random.normal(ks[3], (E, M, n_out))
    hyp = jnp.zeros((E, bsm.HYP_K)).at[:, bsm.COL_LR].set(
        jnp.asarray([0.01, 0.03, 0.05])).at[:, bsm.COL_GS].set(1.0)
    hyp = hyp.at[:, bsm.COL_B1].set(0.9)
    if adam:
        hyp = (hyp.at[:, bsm.COL_B2].set(0.95).at[:, bsm.COL_EPS].set(1e-8)
               .at[:, bsm.COL_T].set(1.0))
    slot = lambda a: jax.random.normal(ks[4], a.shape) * 0.01
    m_w, m_b = slot(w), slot(b)
    v_w, v_b = jnp.abs(slot(w)), jnp.abs(slot(b))

    def fused(w, b, mw, mb, vw, vb):
        y = ops.junction_train_update(
            x, w, *patt, bias=b, act="sigmoid", hyp=hyp, mom=mw, mom_b=mb,
            vel=vw if adam else None, vel_b=vb if adam else None)
        return jnp.sum(y * co)

    got = jax.grad(fused, range(6))(w, b, m_w, m_b, v_w, v_b)
    gw, gb = jax.grad(lambda w, b: jnp.sum(ops.junction_matmul(
        x, w, *patt, bias=b, act="sigmoid") * co), (0, 1))(w, b)
    slots = ([{"w": m_w, "b": m_b}, {"w": v_w, "b": v_b}] if adam
             else [{"w": m_w, "b": m_b}])
    (ref_p,), ref_s = pop._two_pass_update(
        [{"w": w, "b": b}], tuple([s] for s in slots),
        [{"w": gw, "b": gb}], hyp)
    want = [ref_p["w"], ref_p["b"], ref_s[0][0]["w"], ref_s[0][0]["b"]]
    if adam:
        want += [ref_s[1][0]["w"], ref_s[1][0]["b"]]
    for g, r, name in zip(got, want, ("w", "b", "m_w", "m_b", "v_w", "v_b")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-5,
                                   atol=2e-6, err_msg=name)


def test_bf16_params_fp32_momentum():
    """bf16 junction weights update through an fp32 momentum accumulator:
    the fused path keeps dw in fp32 end-to-end (the two-pass path rounds
    dw to bf16 at the custom_vjp boundary, hence the loose tolerance —
    the fused result is the MORE precise one)."""
    bs = 32
    pat = make_block_pattern(8 * bs, 4 * bs, 0.5, bs)
    idx, rob, rt, rc = map(jnp.asarray, (pat.idx, pat.rev_ob, pat.rev_t,
                                         pat.rev_cnt))
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 8 * bs)).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(3),
                           (pat.n_out_blocks, pat.fan_in_blocks, bs, bs))
         * 0.1).astype(jnp.bfloat16)
    co = jax.random.normal(jax.random.PRNGKey(4), (64, 4 * bs))
    mom = jnp.zeros(w.shape, jnp.float32)
    hyp = jnp.asarray([0.05, 0.9], jnp.float32)

    def loss_fused(w, mom):
        y = ops.junction_train_update(x, w, idx, rob, rt, rc, act="relu",
                                      hyp=hyp, mom=mom)
        return jnp.sum(y.astype(jnp.float32) * co)

    nw, nm = jax.grad(loss_fused, (0, 1))(w, mom)
    assert nw.dtype == jnp.bfloat16          # params stay bf16
    assert nm.dtype == jnp.float32           # accumulator stays fp32

    def loss_ref(w):
        y = ops.junction_matmul(x, w, idx, rob, rt, rc, act="relu")
        return jnp.sum(y.astype(jnp.float32) * co)

    gw = jax.grad(loss_ref)(w).astype(jnp.float32)
    mv = 0.9 * mom + gw
    ref_w = (w.astype(jnp.float32) - 0.05 * mv).astype(jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(nm), np.asarray(mv),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(nw, np.float32),
                               np.asarray(ref_w, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_fused_requires_matching_dtypes():
    p = _mnist_junction()
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 1024)).astype(jnp.bfloat16)
    with pytest.raises(ValueError, match="param dtype"):
        ops.junction_train_update(
            x, p["w"], p["idx"], p["rev_ob"], p["rev_t"], p["rev_cnt"],
            hyp=jnp.asarray([0.1, 0.0], jnp.float32))


# -------------------------------------------------------------- model level
@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_model_fused_step_matches_two_pass(momentum):
    """Full dense-model train step (stacked layers under lax.scan +
    remat): fused params/opt state match the two-pass reference."""
    cfg = _dense_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    opt = fused_sgd(constant_schedule(1e-2), momentum=momentum)
    st = opt.init(params)
    ok, why = fused_update_eligible(cfg, opt)
    assert ok, why
    ts_f = make_train_step(cfg, opt, donate=False)
    ts_r = make_train_step(dataclasses.replace(cfg, fused_update=False),
                           opt, donate=False)
    p1, s1, m1 = ts_f(params, st, batch, jnp.asarray(0))
    p2, s2, m2 = ts_r(params, st, batch, jnp.asarray(0))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)
    _assert_trees_close(p1, p2, rtol=2e-4, atol=2e-5)
    if momentum:
        _assert_trees_close(s1, s2, rtol=2e-4, atol=2e-5)


def test_model_fused_momentum_carries_across_steps():
    cfg = _dense_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    opt = fused_sgd(constant_schedule(1e-2), momentum=0.9)
    ts_f = make_train_step(cfg, opt, donate=False)
    ts_r = make_train_step(dataclasses.replace(cfg, fused_update=False),
                           opt, donate=False)
    pf = pr = params
    sf = sr = opt.init(params)
    for i in range(3):
        pf, sf, _ = ts_f(pf, sf, batch, jnp.asarray(i))
        pr, sr, _ = ts_r(pr, sr, batch, jnp.asarray(i))
    _assert_trees_close(pf, pr, rtol=5e-4, atol=5e-5)
    _assert_trees_close(sf, sr, rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_fused_adam_three_steps_matches_two_pass(dtype):
    """Acceptance (ISSUE 7): fused Adam on the dense model matches the
    two-pass ``adam`` reference over 3 steps — bias correction, weight
    decay and the fp32 m/v slots all carry.  bf16 params keep fp32
    slots; the two-pass path rounds dw to bf16 at the custom_vjp
    boundary, hence the looser bf16 tolerance (the fused result is the
    more precise one)."""
    cfg = _dense_cfg(dtype=dtype, param_dtype=dtype)
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    opt = fused_adam(constant_schedule(1e-3), weight_decay=0.01)
    ok, why = fused_update_eligible(cfg, opt)
    assert ok, why
    ts_f = make_train_step(cfg, opt, donate=False)
    ts_r = make_train_step(dataclasses.replace(cfg, fused_update=False),
                           opt, donate=False)
    pf = pr = params
    sf = sr = opt.init(params)
    for i in range(3):
        pf, sf, _ = ts_f(pf, sf, batch, jnp.asarray(i))
        pr, sr, _ = ts_r(pr, sr, batch, jnp.asarray(i))
    if dtype == "bfloat16":
        for t in jax.tree.leaves(sf):
            assert t.dtype == jnp.float32    # m/v slots stay fp32
        rtol, atol = 2e-2, 2e-2
    else:
        rtol, atol = 5e-4, 5e-5
    _assert_trees_close(pf, pr, rtol=rtol, atol=atol)
    _assert_trees_close(sf, sr, rtol=rtol, atol=atol)


def test_moe_fused_step_matches_two_pass():
    """Acceptance: the MoE expert FFN (gated in-junction + wo junction,
    shared patterns, router/shared leaves dense) through the fused step
    matches the two-pass reference."""
    cfg = _moe_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    opt = fused_sgd(constant_schedule(1e-2), momentum=0.9)
    st = opt.init(params)
    ts_f = make_train_step(cfg, opt, donate=False)
    ts_r = make_train_step(dataclasses.replace(cfg, fused_update=False),
                           opt, donate=False)
    p1, s1, m1 = ts_f(params, st, batch, jnp.asarray(0))
    p2, s2, m2 = ts_r(params, st, batch, jnp.asarray(0))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)
    _assert_trees_close(p1, p2, rtol=2e-4, atol=2e-5)
    _assert_trees_close(s1, s2, rtol=2e-4, atol=2e-5)


def test_moe_fused_adam_three_steps_matches_two_pass():
    """Acceptance (ISSUE 7): fused Adam through the MoE expert FFN — the
    gated in-junction (wg/wi) and the wo junction each carry their own
    m/v slot pairs; 3 steps against the two-pass reference."""
    cfg = _moe_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    opt = fused_adam(constant_schedule(1e-3), weight_decay=0.01)
    ts_f = make_train_step(cfg, opt, donate=False)
    ts_r = make_train_step(dataclasses.replace(cfg, fused_update=False),
                           opt, donate=False)
    pf = pr = params
    sf = sr = opt.init(params)
    for i in range(3):
        pf, sf, mf = ts_f(pf, sf, batch, jnp.asarray(i))
        pr, sr, mr = ts_r(pr, sr, batch, jnp.asarray(i))
    np.testing.assert_allclose(float(mf["loss"]), float(mr["loss"]),
                               rtol=1e-5)
    _assert_trees_close(pf, pr, rtol=5e-4, atol=5e-5)
    _assert_trees_close(sf, sr, rtol=5e-4, atol=5e-5)


# ------------------------------------------------- no-dw-in-HBM acceptance
def _has_dw_kernel(jaxpr_text: str) -> bool:
    """A weight-gradient kernel (plain or gated) by its pallas_call name;
    the fused update kernels are junction_update_dw / _gated_dw."""
    return "junction_dw" in jaxpr_text or "junction_gated_dw" in jaxpr_text


@pytest.mark.parametrize("make_opt", [
    lambda: fused_sgd(constant_schedule(1e-2), momentum=0.9),
    lambda: fused_adam(constant_schedule(1e-3)),
], ids=["sgd", "adam"])
def test_fused_step_jaxpr_has_no_dw_kernel(make_opt):
    """Acceptance: dw is absent from the fused step's jaxpr — the only
    weight-gradient consumers are the fused update kernels (whose outputs
    alias the parameter inputs), for the plain AND gated configurations,
    under both fused optimizers."""
    for cfg in (_dense_cfg(), _moe_cfg()):
        params = M.init(cfg, jax.random.PRNGKey(0))
        opt = make_opt()
        raw = make_train_step(cfg, opt, jit=False)
        txt = str(jax.make_jaxpr(raw)(params, opt.init(params), _batch(cfg),
                                      jnp.asarray(0)))
        assert "junction_update_dw" in txt, cfg.name
        assert not _has_dw_kernel(txt), cfg.name
        if cfg.family == "moe":
            assert "junction_update_gated_dw" in txt
        # two-pass sanity: the reference step still runs the dw kernels
        raw_ref = make_train_step(
            dataclasses.replace(cfg, fused_update=False), opt, jit=False)
        txt_ref = str(jax.make_jaxpr(raw_ref)(params, opt.init(params),
                                              _batch(cfg), jnp.asarray(0)))
        assert _has_dw_kernel(txt_ref) and "junction_update_dw" not in txt_ref


# ------------------------------------- newly-eligible configs (ISSUE 7)
@pytest.mark.parametrize("make_opt", [
    lambda: fused_sgd(constant_schedule(1e-2), momentum=0.9, grad_clip=0.5),
    lambda: fused_adam(constant_schedule(1e-3), grad_clip=0.5),
], ids=["sgd", "adam"])
def test_grad_clip_runs_fused_and_matches_clipped_reference(make_opt):
    """Regression flip (ISSUE 7): grad_clip no longer refuses the fused
    path — a norm pre-pass over the plain loss computes the SAME global
    norm the two-pass reference clips with (optim.global_norm_scale is
    the one shared formula) and folds its scale into the hyp row's gs
    column.  The pre-pass costs a second backward, so dw kernels DO
    appear in this jaxpr — alongside, not instead of, the fused update
    kernels."""
    cfg = _dense_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    opt = make_opt()
    ok, why = fused_update_eligible(cfg, opt)
    assert ok, why
    st = opt.init(params)
    txt = str(jax.make_jaxpr(make_train_step(cfg, opt, jit=False))(
        params, st, batch, jnp.asarray(0)))
    assert "junction_update_dw" in txt and _has_dw_kernel(txt)
    ts = make_train_step(cfg, opt, donate=False)
    ts_ref = make_train_step(dataclasses.replace(cfg, fused_update=False),
                             opt, donate=False)
    pf = pr = params
    sf = sr = st
    for i in range(2):
        pf, sf, _ = ts(pf, sf, batch, jnp.asarray(i))
        pr, sr, _ = ts_ref(pr, sr, batch, jnp.asarray(i))
    _assert_trees_close(pf, pr, rtol=2e-4, atol=2e-5)
    _assert_trees_close(sf, sr, rtol=2e-4, atol=2e-5)


def test_microbatch_runs_fused_and_matches_accumulated_reference():
    """Regression flip (ISSUE 7): microbatches > 1 no longer refuses the
    fused path — the fused step runs the FULL batch (mean of equal-sized
    microbatch means == full-batch mean; the kernels' M-innermost flush
    applies the update exactly once per tile) and must match the
    two-pass scan-accumulated reference."""
    cfg = _dense_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(7), (4, 16),
                                          0, cfg.vocab)}
    opt = fused_sgd(constant_schedule(1e-2), momentum=0.9)
    ok, why = fused_update_eligible(cfg, opt, microbatches=4)
    assert ok, why
    ts = make_train_step(cfg, opt, microbatches=4, donate=False)
    ts_ref = make_train_step(dataclasses.replace(cfg, fused_update=False),
                             opt, microbatches=4, donate=False)
    st = opt.init(params)
    p1, s1, m1 = ts(params, st, batch, jnp.asarray(0))
    p2, s2, m2 = ts_ref(params, st, batch, jnp.asarray(0))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    _assert_trees_close(p1, p2, rtol=2e-4, atol=2e-5)
    _assert_trees_close(s1, s2, rtol=2e-4, atol=2e-5)


# ----------------------------------------------------- refusal / fallback


@pytest.mark.parametrize("break_it,frag", [
    (dict(engine="jnp"), "engine"),
    (dict(fused_update=False), "off"),
    (dict(param_dtype="bfloat16"), "param_dtype"),
    (dict(cast_params_once=True), "cast_params_once"),
])
def test_fused_eligibility_refusals(break_it, frag):
    cfg = _dense_cfg(**break_it)
    opt = fused_sgd(constant_schedule(1e-2), momentum=0.9)
    ok, why = fused_update_eligible(cfg, opt)
    assert not ok and frag in why, why


def test_fused_refuses_weight_shared_hybrid():
    """The hybrid family applies ONE shared attn/MLP block per super-layer
    — cotangents sum across uses, which would corrupt a fused junction's
    updated-params cotangent.  Eligibility must refuse."""
    from repro.configs import registry
    cfg = dataclasses.replace(
        registry.get("zamba2-2.7b").reduced(),
        sparsity=SparsityConfig(density=0.25, block=32, where="ffn"),
        engine="pallas", fused_update=True,
        dtype="float32", param_dtype="float32")
    opt = fused_sgd(constant_schedule(1e-2), momentum=0.9)
    ok, why = fused_update_eligible(cfg, opt)
    assert not ok and "hybrid" in why


def test_fused_rejects_non_fp32_momentum():
    """The momentum state must stay fp32 (the documented accumulator
    contract) — a bf16 buffer must raise, not silently degrade."""
    bs = 32
    pat = make_block_pattern(8 * bs, 4 * bs, 0.5, bs)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 8 * bs)).astype(jnp.bfloat16)
    w = jnp.zeros((pat.n_out_blocks, pat.fan_in_blocks, bs, bs), jnp.bfloat16)
    with pytest.raises(ValueError, match="fp32 accumulator"):
        ops.junction_train_update(
            x, w, jnp.asarray(pat.idx), jnp.asarray(pat.rev_ob),
            jnp.asarray(pat.rev_t), jnp.asarray(pat.rev_cnt),
            hyp=jnp.asarray([0.1, 0.9], jnp.float32),
            mom=jnp.zeros_like(w))


def test_fused_eligibility_wrong_optimizer():
    """A plain (non-Fused) optimizer still refuses — it has no hyp row /
    slot contract for the kernels to consume."""
    cfg = _dense_cfg()
    ok, why = fused_update_eligible(cfg, adam(constant_schedule(1e-3)))
    assert not ok and "FusedOptimizer" in why


def test_two_pass_fused_sgd_matches_plain_sgd():
    """fused_sgd without momentum IS eq. (3): parity with optim.sgd."""
    from repro.optim import sgd
    cfg = _dense_cfg(engine="jnp", fused_update=False)
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    for opt in (sgd(constant_schedule(1e-2)),
                fused_sgd(constant_schedule(1e-2))):
        ts = make_train_step(cfg, opt, donate=False)
        p, _, _ = ts(params, opt.init(params), batch, jnp.asarray(0))
        if opt.__class__ is FusedSGD:
            _assert_trees_close(p, p_ref, rtol=1e-6, atol=1e-7)
        else:
            p_ref = p


# ------------------------------------------------ coalesced reverse DMA
def test_dx_coalesces_contiguous_reverse_runs():
    """A pattern whose reverse slots form contiguous runs in the flat
    (ob, t) weight layout (input block i ends one output block's fan-in
    list and starts the next's) exercises the two-tile descriptor path;
    parity vs the jnp oracle."""
    from repro.kernels import ref

    idx_np = np.array([[0, 1], [1, 2], [2, 3]], np.int32)
    rob, rt, rc = reverse_block_pattern(idx_np, 4)
    # input 1 occupies linear slots 1 and 2; input 2 slots 3 and 4 — runs
    s = rob * idx_np.shape[1] + rt
    assert (np.diff(s[1, :rc[1]]) == 1).all()
    bs = 32
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 4 * bs))
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 2, bs, bs)) * 0.1
    co = jax.random.normal(jax.random.PRNGKey(5), (64, 3 * bs))
    args = (jnp.asarray(idx_np), jnp.asarray(rob), jnp.asarray(rt),
            jnp.asarray(rc))

    def f(x, w):
        return jnp.sum(ops.junction_matmul(x, w, *args) * co)

    def g(x, w):
        return jnp.sum(ref.block_sparse_matmul(x, w, args[0]) * co)

    d1 = jax.grad(f, (0, 1))(x, w)
    d2 = jax.grad(g, (0, 1))(x, w)
    for a, b in zip(d1, d2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- donation default
def test_make_train_step_donates_by_default():
    """Satellite: the jitted step donates params/opt_state so XLA reuses
    the buffers (no doubled peak memory across the update)."""
    cfg = _dense_cfg(engine="jnp", fused_update=False)
    params = M.init(cfg, jax.random.PRNGKey(0))
    opt = fused_sgd(constant_schedule(1e-2), momentum=0.9)
    st = opt.init(params)
    ts = make_train_step(cfg, opt)
    p2, s2, _ = ts(params, st, _batch(cfg), jnp.asarray(0))
    donated = jax.tree.leaves(params)[0].is_deleted()
    assert donated, "params were not donated by the default train step"
    # and donate=False keeps the inputs alive
    params = M.init(cfg, jax.random.PRNGKey(0))
    st = opt.init(params)
    ts2 = make_train_step(cfg, opt, donate=False)
    ts2(params, st, _batch(cfg), jnp.asarray(0))
    assert not jax.tree.leaves(params)[0].is_deleted()
