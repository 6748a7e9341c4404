"""Population engine (ISSUE 5): exploration riding the E axis.

The acceptance contract: a population of E >= 4 MNIST candidates with
DISTINCT per-member learning rates trains in one fused E-batched step
whose per-member losses and parameters match E independently-trained
single models (SGD ± momentum, including the fused BP+UP path indexing
the per-unit [E, 2] hyp table), and the successive-halving scheduler
runs a density x lr sweep end to end producing a ledger that names a
winning config.  Plus (ISSUE 7): Adam populations — distinct per-member
lr/b1/weight_decay riding the [E, HYP_K] registry table with (m, v)
slot pairs — fused vs two-pass, the (2,)/(HYP_K,) broadcast vs
explicit-table equivalence at the ops level, opt as a structural cohort
axis, cohort bucketing rules, in-place prune freezing, and ledger JSON
round-tripping.
"""
import dataclasses
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SweepConfig
from repro.core import sparse_linear as sl
from repro.core.sparsity import make_block_pattern
from repro.data.mnist import paper_dataset
from repro.kernels import ops
from repro.obs import Recorder
from repro.search import (CandidateSpec, Ledger, bucket,
                          clear_program_cache, hyp_table, init_population,
                          make_population_step, member_slice, run_sweep,
                          structure_key)
from repro.search import population as pop


def _mnist_batch(m, n_in, n_out, seed=0):
    """A real (synthetic-MNIST) batch: x sliced to the input width, one-
    hot targets zero-padded to the output width."""
    x, t, _ = paper_dataset(n=m, seed=seed)
    tp = np.zeros((m, n_out), np.float32)
    tp[:, :t.shape[1]] = t[:, :n_out]
    return jnp.asarray(x[:, :n_in]), jnp.asarray(tp)


def _specs(E=4, momentum=0.0, layers=(256, 128, 32), block=32, density=0.5):
    lrs = [0.02, 0.05, 0.08, 0.12, 0.15, 0.2][:E]
    return [CandidateSpec(lr=lr, momentum=momentum, density=density,
                          layers=layers, block=block, init_seed=i)
            for i, lr in enumerate(lrs)]


def _single_fused_step(params, mom, hyp_pair, x, t, act="sigmoid"):
    """One fused BP+UP train step of a standalone single model (4-D
    squeeze path) — the independent-training reference."""
    aug = sl.inject_update_ctx(params, mom, hyp_pair)

    def loss_fn(aug):
        y = x
        for layer in aug:
            y = sl.apply(layer, y, engine="pallas", act=act)
        return jnp.mean(jnp.square(y - t))

    loss, grads = jax.value_and_grad(loss_fn, allow_int=True)(aug)
    new_p, new_m = [], []
    for g, p, m in zip(grads, params, mom):
        lp, lm = dict(p), dict(m)
        for k, mk in sl.FUSED_MOM.items():
            if k in p and not isinstance(p[k], dict):
                lp[k] = g[k]
                lm[k] = g[mk]
        new_p.append(lp)
        new_m.append(lm)
    return new_p, new_m, loss


def _single_jnp_step(params, mom, lr, beta, x, t, act="sigmoid"):
    """Two-pass jnp reference single-model step (materialized grads,
    per-leaf SGD+momentum)."""
    def loss_fn(params):
        y = x
        for layer in params:
            y = sl.apply(layer, y, engine="jnp", act=act)
        return jnp.mean(jnp.square(y - t))

    loss, grads = jax.value_and_grad(loss_fn, allow_int=True)(params)
    new_p, new_m = [], []
    for g, p, m in zip(grads, params, mom):
        lp, lm = dict(p), dict(m)
        for k in ("w", "b"):
            mv = beta * m[k] + g[k].astype(jnp.float32)
            lp[k] = (p[k].astype(jnp.float32) - lr * mv).astype(p[k].dtype)
            lm[k] = mv
        new_p.append(lp)
        new_m.append(lm)
    return new_p, new_m, loss


# --------------------------------------------------------------- acceptance
@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_population_fused_matches_independent_singles(momentum):
    """Acceptance: E=4 candidates with distinct lrs advance in fused
    E-batched steps (per-unit [E, 2] hyp table in the update kernels)
    exactly as E independently-trained single models do through the 4-D
    squeeze path — losses and parameters, SGD +- momentum, 3 steps."""
    specs = _specs(momentum=momentum)
    E = len(specs)
    params = init_population(jax.random.PRNGKey(0), specs)
    x, t = _mnist_batch(48, specs[0].layers[0], specs[0].layers[-1])

    step = make_population_step(engine="pallas", fused=True, donate=False)
    p, m = params, pop.init_momentum(params)
    hyp, mask = hyp_table(specs), jnp.ones((E,), jnp.float32)
    pop_losses = []
    for _ in range(3):
        p, m, losses = step(p, m, hyp, mask, x, t)
        pop_losses.append(np.asarray(losses))

    for e, spec in enumerate(specs):
        sp = member_slice(params, e)
        sm = pop.init_momentum(sp)
        for i in range(3):
            sp, sm, loss = _single_fused_step(sp, sm, hyp[e], x, t)
            np.testing.assert_allclose(float(loss), pop_losses[i][e],
                                       rtol=2e-5,
                                       err_msg=f"member {e} step {i}")
        for li in range(len(sp)):
            np.testing.assert_allclose(
                np.asarray(p[li]["w"][e]), np.asarray(sp[li]["w"]),
                rtol=1e-4, atol=1e-5, err_msg=f"member {e} layer {li} w")
            np.testing.assert_allclose(
                np.asarray(p[li]["b"][e]), np.asarray(sp[li]["b"]),
                rtol=1e-4, atol=1e-5, err_msg=f"member {e} layer {li} b")


def test_population_forward_per_unit_bias_matches_jnp():
    """E=3 units, each with its own non-zero bias: the [E, 1, N] bias
    block of the forward kernel adds unit e's row to unit e only."""
    specs = _specs(E=3)
    params = init_population(jax.random.PRNGKey(0), specs)
    keys = jax.random.split(jax.random.PRNGKey(1), len(params))
    params = [dict(l, b=jax.random.normal(k, l["b"].shape))
              for l, k in zip(params, keys)]
    x, _ = _mnist_batch(40, specs[0].layers[0], specs[0].layers[-1])
    got = pop.population_forward(params, x, act="sigmoid", engine="pallas")
    want = pop.population_forward(params, x, act="sigmoid", engine="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_population_mnist_shape_fused_vs_independent_jnp():
    """The paper-shape population (1024 -> 512 -> 128, bs=128, E=4,
    distinct lrs + momentum) through the fused pallas path vs E
    independent two-pass jnp single models — cross-engine, cross-grain
    parity on real (synthetic-MNIST) data."""
    specs = _specs(momentum=0.9, layers=(1024, 512, 128), block=128,
                   density=0.25)
    E = len(specs)
    params = init_population(jax.random.PRNGKey(1), specs)
    x, t = _mnist_batch(64, 1024, 128)

    step = make_population_step(engine="pallas", fused=True, donate=False)
    p, m = params, pop.init_momentum(params)
    hyp, mask = hyp_table(specs), jnp.ones((E,), jnp.float32)
    pop_losses = []
    for _ in range(2):
        p, m, losses = step(p, m, hyp, mask, x, t)
        pop_losses.append(np.asarray(losses))

    for e, spec in enumerate(specs):
        sp = member_slice(params, e)
        sm = pop.init_momentum(sp)
        for i in range(2):
            sp, sm, loss = _single_jnp_step(sp, sm, spec.lr, spec.momentum,
                                            x, t)
            np.testing.assert_allclose(float(loss), pop_losses[i][e],
                                       rtol=1e-4,
                                       err_msg=f"member {e} step {i}")
        for li in range(len(sp)):
            np.testing.assert_allclose(
                np.asarray(p[li]["w"][e]), np.asarray(sp[li]["w"]),
                rtol=1e-3, atol=1e-4, err_msg=f"member {e} layer {li} w")


def test_population_two_pass_matches_fused():
    """Engine parity of the population step itself: jnp two-pass (per-
    member lr broadcast over materialized grads) == pallas fused."""
    specs = _specs(momentum=0.9)
    E = len(specs)
    params = init_population(jax.random.PRNGKey(2), specs)
    x, t = _mnist_batch(32, specs[0].layers[0], specs[0].layers[-1])
    hyp, mask = hyp_table(specs), jnp.ones((E,), jnp.float32)

    sf = make_population_step(engine="pallas", fused=True, donate=False)
    sj = make_population_step(engine="jnp", donate=False)
    pf, mf = params, pop.init_momentum(params)
    pj, mj = params, pop.init_momentum(params)
    for _ in range(2):
        pf, mf, lf = sf(pf, mf, hyp, mask, x, t)
        pj, mj, lj = sj(pj, mj, hyp, mask, x, t)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lj), rtol=1e-4)
    for li in range(len(pf)):
        np.testing.assert_allclose(np.asarray(pf[li]["w"]),
                                   np.asarray(pj[li]["w"]),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(mf[li]["w"]),
                                   np.asarray(mj[li]["w"]),
                                   rtol=1e-3, atol=1e-4)


def test_population_adam_fused_matches_two_pass():
    """Acceptance (ISSUE 7): an Adam population with DISTINCT per-member
    lr / b1 / weight_decay rides the same [E, HYP_K] contract — pallas
    fused (in-kernel m/v slot pairs) == jnp two-pass reference over 3
    steps, the bias-correction time stamped into COL_T each step."""
    from repro.kernels import block_sparse_matmul as bsm
    specs = [CandidateSpec(lr=lr, momentum=b1, opt="adam", weight_decay=wd,
                           density=0.5, layers=(256, 128, 32), block=32,
                           init_seed=i)
             for i, (lr, b1, wd) in enumerate(
                 [(1e-3, 0.9, 0.0), (2e-3, 0.8, 0.01),
                  (5e-4, 0.95, 0.0), (1e-3, 0.85, 0.02)])]
    E = len(specs)
    params = init_population(jax.random.PRNGKey(9), specs)
    x, t = _mnist_batch(32, specs[0].layers[0], specs[0].layers[-1])
    hyp, mask = hyp_table(specs), jnp.ones((E,), jnp.float32)

    sf = make_population_step(engine="pallas", fused=True, donate=False)
    sj = make_population_step(engine="jnp", donate=False)
    pf = pj = params
    slf = slj = pop.init_slots(params, specs)
    assert len(slf) == 2                      # (mom, vel)
    for i in range(3):
        hyp_t = hyp.at[:, bsm.COL_T].set(jnp.float32(i + 1))
        pf, slf, lf = sf(pf, slf, hyp_t, mask, x, t)
        pj, slj, lj = sj(pj, slj, hyp_t, mask, x, t)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lj), rtol=1e-4)
    for li in range(len(pf)):
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(pf[li][k]),
                                       np.asarray(pj[li][k]),
                                       rtol=1e-3, atol=1e-5)
        for s_f, s_j in zip(slf, slj):
            np.testing.assert_allclose(np.asarray(s_f[li]["w"]),
                                       np.asarray(s_j[li]["w"]),
                                       rtol=1e-3, atol=1e-5)


# ------------------------------------------------------- [E, k] hyp table
def test_hyp_pair_broadcasts_to_table():
    """A shared (2,) pair on 5-D expert weights computes exactly what the
    explicitly tiled [E, 2] table does."""
    bs, E = 32, 3
    pat = make_block_pattern(8 * bs, 4 * bs, 0.5, bs)
    args = tuple(map(jnp.asarray, (pat.idx, pat.rev_ob, pat.rev_t,
                                   pat.rev_cnt)))
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (E, 32, 8 * bs))
    w = jax.random.normal(ks[1], (E, pat.n_out_blocks, pat.fan_in_blocks,
                                  bs, bs)) * 0.1
    co = jax.random.normal(ks[2], (E, 32, 4 * bs))
    mom = jnp.full(w.shape, 0.02, jnp.float32)
    pair = jnp.asarray([0.05, 0.9], jnp.float32)

    def upd(hyp):
        def loss(w, m):
            y = ops.junction_train_update(x, w, *args, act="relu", hyp=hyp,
                                          mom=m)
            return jnp.sum(y * co)
        return jax.grad(loss, (0, 1))(w, mom)

    nw1, nm1 = upd(pair)
    nw2, nm2 = upd(jnp.tile(pair, (E, 1)))
    np.testing.assert_array_equal(np.asarray(nw1), np.asarray(nw2))
    np.testing.assert_array_equal(np.asarray(nm1), np.asarray(nm2))


def test_hyp_row_broadcasts_to_table():
    """A shared (HYP_K,) registry row on 5-D expert weights with Adam
    slots computes exactly what the explicitly tiled [E, HYP_K] table
    does."""
    bs, E = 32, 3
    pat = make_block_pattern(8 * bs, 4 * bs, 0.5, bs)
    args = tuple(map(jnp.asarray, (pat.idx, pat.rev_ob, pat.rev_t,
                                   pat.rev_cnt)))
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (E, 32, 8 * bs))
    w = jax.random.normal(ks[1], (E, pat.n_out_blocks, pat.fan_in_blocks,
                                  bs, bs)) * 0.1
    co = jax.random.normal(ks[2], (E, 32, 4 * bs))
    mom = jnp.full(w.shape, 0.02, jnp.float32)
    vel = jnp.full(w.shape, 0.003, jnp.float32)
    #                 lr,   b1,  b2,   eps,  wd,  t,   gs
    row = jnp.asarray([1e-3, 0.9, 0.95, 1e-8, 0.01, 2.0, 1.0], jnp.float32)

    def upd(hyp):
        def loss(w, m, v):
            y = ops.junction_train_update(x, w, *args, act="relu", hyp=hyp,
                                          mom=m, vel=v)
            return jnp.sum(y * co)
        return jax.grad(loss, (0, 1, 2))(w, mom, vel)

    for a, b in zip(upd(row), upd(jnp.tile(row, (E, 1)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hyp_bad_shape_raises():
    bs, E = 32, 3
    pat = make_block_pattern(4 * bs, 2 * bs, 0.5, bs)
    args = tuple(map(jnp.asarray, (pat.idx, pat.rev_ob, pat.rev_t,
                                   pat.rev_cnt)))
    x = jnp.zeros((E, 16, 4 * bs))
    w = jnp.zeros((E, pat.n_out_blocks, pat.fan_in_blocks, bs, bs))
    with pytest.raises(ValueError,
                       match=r"per-unit \[E=3, 2\] / \[E=3, 7\] table"):
        ops.junction_train_update(x, w, *args,
                                  hyp=jnp.zeros((2, 2), jnp.float32))
    # a single (4-D) junction cannot take a multi-row table
    with pytest.raises(ValueError, match="per-unit"):
        ops.junction_train_update(x[0], w[0], *args,
                                  hyp=jnp.zeros((3, 2), jnp.float32))


# --------------------------------------------------------- cohort bucketing
def test_cohort_bucketing_rules():
    """Same quantized structure -> one cohort; any structural difference
    splits; candidate order is preserved as slot order."""
    base = dict(layers=(256, 128, 32), block=32)
    specs = [
        CandidateSpec(lr=0.1, density=0.50, **base),            # kb=(4,2)
        CandidateSpec(lr=0.2, density=0.55, **base),            # same kb
        CandidateSpec(lr=0.1, density=0.25, **base),            # kb=(2,1)
        CandidateSpec(lr=0.1, density=0.50, layers=(256, 64, 32),
                      block=32),                                # widths
        CandidateSpec(lr=0.1, density=0.50, seed=7, **base),    # pattern
        CandidateSpec(lr=0.3, density=0.52, momentum=0.9,
                      init_seed=9, **base),                     # same kb
    ]
    cohorts = bucket(specs)
    by_ids = {c.member_ids: c for c in cohorts}
    assert (0, 1, 5) in by_ids          # densities quantizing to one kb
    assert (2,) in by_ids and (3,) in by_ids and (4,) in by_ids
    c = by_ids[(0, 1, 5)]
    assert [s.lr for s in c.specs] == [0.1, 0.2, 0.3]
    assert structure_key(specs[0]) == structure_key(specs[5])
    assert structure_key(specs[0]) != structure_key(specs[2])


def test_opt_is_structural_cohort_axis():
    """opt splits cohorts (the slot layout and the kernels' optimizer
    switch are static per launch) and init_slots refuses a mixed-kind
    spec list outright."""
    import dataclasses

    base = dict(lr=0.1, density=0.5, layers=(256, 128, 32), block=32)
    s_sgd = CandidateSpec(**base)
    s_adam = CandidateSpec(opt="adam", momentum=0.9, **base)
    assert structure_key(s_sgd) != structure_key(s_adam)
    assert len(bucket([s_sgd, s_adam])) == 2
    params = init_population(
        jax.random.PRNGKey(0),
        [s_sgd, dataclasses.replace(s_sgd, init_seed=1)])
    with pytest.raises(ValueError, match="optimizer kinds"):
        pop.init_slots(params, [s_sgd, s_adam])


def test_member_slice_recovers_standalone_init():
    """Each stacked slot is bit-for-bit the standalone single-model init
    for its spec (what makes the parity tests non-tautological)."""
    specs = _specs(E=3)
    key = jax.random.PRNGKey(5)
    params = init_population(key, specs)
    for e, s in enumerate(specs):
        solo = pop._init_member(jax.random.fold_in(key, s.init_seed), s)
        for li in range(len(solo)):
            np.testing.assert_array_equal(
                np.asarray(params[li]["w"][e]), np.asarray(solo[li]["w"]))
            np.testing.assert_array_equal(
                np.asarray(params[li]["idx"]), np.asarray(solo[li]["idx"]))


def test_mixed_structure_population_refused():
    specs = _specs(E=2) + [CandidateSpec(lr=0.1, density=0.25,
                                         layers=(256, 128, 32), block=32)]
    with pytest.raises(ValueError, match="share structure"):
        init_population(jax.random.PRNGKey(0), specs)


# -------------------------------------------------------------- slot prune
@pytest.mark.parametrize("engine,fused", [("jnp", False), ("pallas", True)])
def test_pruned_slot_frozen_in_place(engine, fused):
    """Zero mask entry + zero hyp row freezes that member exactly (w, b
    AND momentum stop moving) while the survivors keep training — the
    fixed-shape prune of the scheduler, on both execution paths."""
    specs = _specs(momentum=0.9)
    E = len(specs)
    params = init_population(jax.random.PRNGKey(3), specs)
    x, t = _mnist_batch(32, specs[0].layers[0], specs[0].layers[-1])
    step = make_population_step(engine=engine, fused=fused, donate=False)
    hyp = hyp_table(specs)
    mom = pop.init_momentum(params)
    # one live step so momentum is nonzero when the prune lands
    p1, m1, _ = step(params, mom, hyp, jnp.ones((E,)), x, t)
    pruned = 1
    mask = jnp.ones((E,)).at[pruned].set(0.0)
    hyp2 = hyp.at[pruned].set(0.0)
    p2, m2, losses = step(p1, m1, hyp2, mask, x, t)
    assert losses.shape == (E,)         # eval stays vectorized over all slots
    for li in range(len(p2)):
        np.testing.assert_array_equal(np.asarray(p2[li]["w"][pruned]),
                                      np.asarray(p1[li]["w"][pruned]))
        np.testing.assert_array_equal(np.asarray(p2[li]["b"][pruned]),
                                      np.asarray(p1[li]["b"][pruned]))
        for e in range(E):
            if e != pruned:
                assert not np.array_equal(np.asarray(p2[li]["w"][e]),
                                          np.asarray(p1[li]["w"][e]))


# ---------------------------------------------------- scheduler + ledger
def test_run_sweep_end_to_end(tmp_path):
    """Acceptance: a density x lr successive-halving sweep runs end to
    end and the ledger names a winning config; halving prunes globally
    across cohorts; the JSON artifact round-trips."""
    specs = [CandidateSpec(lr=lr, density=d, layers=(256, 128, 32),
                           block=32, init_seed=i)
             for i, (d, lr) in enumerate((d, lr)
                                         for d in (0.25, 0.5)
                                         for lr in (0.05, 0.2))]
    x, t, _ = paper_dataset(n=160, seed=0)
    x = x[:, :256]
    cfg = SweepConfig(rounds=2, steps_per_round=2, batch_size=32,
                      eval_samples=32, engine="jnp")
    result = run_sweep(specs, x[:128], t[:128], x[128:], t[128:], cfg,
                       tag="test")
    led = result.ledger
    assert len(led.members) == 4
    w = led.winner()
    assert w is not None and w.config["lr"] in (0.05, 0.2)
    assert w.pruned_at is None and w.rounds_survived == 2
    # halving: 2 of 4 pruned after round 0, each with one fewer round
    pruned = [m for m in led.members if m.pruned_at is not None]
    assert len(pruned) == 2 and all(m.pruned_at == 0 for m in pruned)
    assert all(m.rounds_survived == 1 for m in pruned)
    live = [m for m in led.members if m.pruned_at is None]
    assert all(len(m.loss_curve) == 4 for m in live)      # 2 rounds x 2 steps
    assert all(len(m.loss_curve) == 2 for m in pruned)    # round 0 only
    # winner's standalone params come back at the right shapes
    wp = result.winning_params()
    assert wp is not None and wp[0]["w"].ndim == 4

    # JSON round-trip (the meta.tag contract shared with BENCH artifacts)
    path = tmp_path / "SWEEP_test.json"
    led.save(str(path))
    led2 = Ledger.load(str(path))
    assert led2.meta["tag"] == "test"
    assert led2.meta["git_sha"]        # commit-attributable, like BENCH meta
    assert led2.winner().member == w.member
    assert led2.winner().config == w.config
    raw = json.loads(path.read_text())
    assert raw["winner"]["member"] == w.member


def test_run_sweep_adam_lr_x_b1_fused():
    """Acceptance (ISSUE 7): a FUSED Adam lr × b1 sweep through the
    scheduler — per-member Adam rows in the [E, HYP_K] table, COL_T
    stamped each step, quarantine riding the same in-kernel health
    flags — and the ledger names a winner."""
    specs = [CandidateSpec(lr=lr, momentum=b1, opt="adam", density=0.5,
                           layers=(256, 128, 32), block=32, init_seed=i)
             for i, (lr, b1) in enumerate((lr, b1)
                                          for lr in (1e-3, 5e-3)
                                          for b1 in (0.8, 0.9))]
    x, t, _ = paper_dataset(n=160, seed=0)
    x = x[:, :256]
    cfg = SweepConfig(rounds=2, steps_per_round=2, batch_size=32,
                      eval_samples=32, engine="pallas")
    result = run_sweep(specs, x[:128], t[:128], x[128:], t[128:], cfg,
                       tag="adam-smoke")
    led = result.ledger
    assert len(led.members) == 4
    w = led.winner()
    assert w is not None and w.config["opt"] == "adam"
    assert w.config["momentum"] in (0.8, 0.9)
    assert result.winning_params()[0]["w"].ndim == 4


def test_momentum_free_population_skips_buffers():
    """An all-momentum-0 population carries NO momentum state (the
    plain-SGD kernels run — no weight-sized fp32 stream per junction)
    and computes exactly what the zeros-buffer beta-0 variant does."""
    specs = _specs(momentum=0.0)
    E = len(specs)
    params = init_population(jax.random.PRNGKey(7), specs)
    assert pop.init_momentum(params, specs) is None
    assert pop.init_momentum(params, _specs(momentum=0.9)) is not None
    x, t = _mnist_batch(32, specs[0].layers[0], specs[0].layers[-1])
    hyp, mask = hyp_table(specs), jnp.ones((E,), jnp.float32)
    step = make_population_step(engine="pallas", fused=True, donate=False)
    p1, m1, l1 = step(params, None, hyp, mask, x, t)
    assert m1 is None
    p2, _, l2 = step(params, pop.init_momentum(params), hyp, mask, x, t)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-6)
    for li in range(len(p1)):
        np.testing.assert_allclose(np.asarray(p1[li]["w"]),
                                   np.asarray(p2[li]["w"]),
                                   rtol=1e-5, atol=1e-6)


def test_rank_score_nan_and_width_policy():
    """Ranking policy: a diverged (non-finite) eval loss scores +inf —
    pruned first, never winner — and scores are width-normalized (per-
    sample TOTAL squared error), so a wider zero-padded output doesn't
    dilute its way past a narrow cohort."""
    import math

    from repro.search.scheduler import _score

    assert _score(float("nan"), 32) == math.inf
    assert _score(float("inf"), 32) == math.inf
    # identical per-sample total error ranks equal across widths: a
    # 128-wide cohort's MSE mean is 4x diluted vs a 32-wide one
    assert _score(0.01, 128) == pytest.approx(_score(0.04, 32))
    assert _score(0.02, 32) < _score(0.01, 128)


def test_sweep_single_candidate_wins():
    """Degenerate sweep: one candidate survives every round and wins."""
    specs = _specs(E=1)
    x, t, _ = paper_dataset(n=96, seed=1)
    x = x[:, :256]
    cfg = SweepConfig(rounds=2, steps_per_round=1, batch_size=32,
                      eval_samples=32, engine="jnp")
    result = run_sweep(specs, x[:64], t[:64], x[64:], t[64:], cfg)
    w = result.ledger.winner()
    assert w is not None and w.member == 0 and w.rounds_survived == 2


# ------------------------------------------------ program cache (scheduler)
def _cache_specs(act="sigmoid", diverging=False):
    """Two cohorts (densities 0.5 and 0.25) of two members each; with
    ``diverging`` the first cohort holds a third member at lr=inf."""
    specs = [CandidateSpec(lr=lr, density=d, layers=(128, 64), block=32,
                           act=act, init_seed=i)
             for i, (d, lr) in enumerate((d, lr) for d in (0.5, 0.25)
                                         for lr in (0.05, 0.2))]
    if diverging:
        specs.insert(2, dataclasses.replace(specs[0], lr=float("inf"),
                                            init_seed=9))
    return specs


def _cache_data():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((96, 128)).astype(np.float32)
    t = np.eye(64, dtype=np.float32)[rng.integers(0, 64, 96)]
    return x[:64], t[:64], x[64:], t[64:]


def _sweep_outputs(result):
    """Everything a sweep returns but the artifact stamp: the members'
    records (loss curves, eval losses, pruning, quarantine, winner) and
    every cohort's final params and slots."""
    members = json.dumps([m.to_dict() for m in result.ledger.members])
    leaves = [np.asarray(a) for st in result.states
              for a in jax.tree_util.tree_leaves((st.params, st.mom))]
    return members, leaves


@pytest.mark.parametrize("engine,fused,quarantine",
                         [("pallas", True, True), ("jnp", False, False)])
def test_cached_programs_match_a_cold_build(engine, fused, quarantine):
    """Two run_sweep calls with different seeds, the second on the first
    one's cached programs, give the same ledgers, eval losses and final
    weights, bit for bit, as the same two calls each built cold; the
    warm call traces nothing and takes every cohort's programs from the
    cache."""
    specs = _cache_specs(diverging=quarantine)
    data = _cache_data()
    cfg = SweepConfig(rounds=2, steps_per_round=2, batch_size=16,
                      eval_samples=32, engine=engine, fused=fused,
                      quarantine=quarantine)
    cfgs = [dataclasses.replace(cfg, seed=s) for s in (11, 2**31 - 5)]
    clear_program_cache()
    warm = [run_sweep(specs, *data, c) for c in cfgs]
    cold = []
    for c in cfgs:
        clear_program_cache()
        cold.append(run_sweep(specs, *data, c))
    cohorts = len(bucket(specs))
    assert [(r.ledger.meta["step_traces"], r.ledger.meta["eval_traces"],
             r.ledger.meta["programs_reused"]) for r in warm] == \
        [(cohorts, cohorts, cohorts - 1), (0, 0, cohorts)]
    assert all(r.ledger.meta["step_traces"] == cohorts for r in cold)
    if quarantine:
        assert warm[1].ledger.meta["quarantined"] == 1
    for w, c in zip(warm, cold):
        (wm, wl), (cm, cl) = _sweep_outputs(w), _sweep_outputs(c)
        assert wm == cm
        assert len(wl) == len(cl)
        for a, b in zip(wl, cl):
            np.testing.assert_array_equal(a, b)
    # the two seeds trained different weights: the comparison has teeth
    assert _sweep_outputs(warm[0])[0] != _sweep_outputs(warm[1])[0]


@pytest.mark.parametrize("change", ["act", "fused", "quarantine", "factory"])
def test_program_cache_key_separates_structure(change, monkeypatch):
    """A different activation, update path, quarantine setting or step
    factory gets programs of its own, never a cached one built for
    another; the first structure's programs stay cached beside it.  The
    update path is fused on the pallas engine only, so that case runs
    there."""
    data = _cache_data()
    cfg = SweepConfig(rounds=1, steps_per_round=1, batch_size=16,
                      eval_samples=32,
                      engine="pallas" if change == "fused" else "jnp",
                      fused=False, quarantine=False)
    specs = _cache_specs()
    cohorts = len(bucket(specs))
    clear_program_cache()
    run_sweep(specs, *data, cfg)
    built = []
    if change == "act":
        specs2, cfg2 = _cache_specs(act="relu"), cfg
    elif change == "factory":
        real = pop.make_population_step

        def factory(*a, **kw):
            built.append(a)
            return real(*a, **kw)
        monkeypatch.setattr(pop, "make_population_step", factory)
        specs2, cfg2 = specs, cfg
    else:
        specs2, cfg2 = specs, dataclasses.replace(cfg, **{change: True})
    meta = run_sweep(specs2, *data, cfg2).ledger.meta
    assert (meta["step_traces"], meta["programs_reused"]) == \
        (cohorts, cohorts - 1)
    assert built == ([("sigmoid",)] if change == "factory" else [])
    monkeypatch.undo()
    meta = run_sweep(specs, *data, cfg).ledger.meta
    assert (meta["step_traces"], meta["programs_reused"]) == (0, cohorts)


def test_program_cache_keys_the_update_path_not_the_flag():
    """On the jnp engine ``fused`` changes nothing the step traces (the
    update is two-pass either way), so both settings share one entry."""
    data = _cache_data()
    cfg = SweepConfig(rounds=1, steps_per_round=1, batch_size=16,
                      eval_samples=32, engine="jnp", fused=False,
                      quarantine=False)
    specs = _cache_specs()
    cohorts = len(bucket(specs))
    clear_program_cache()
    run_sweep(specs, *data, cfg)
    meta = run_sweep(specs, *data,
                     dataclasses.replace(cfg, fused=True)).ledger.meta
    assert (meta["step_traces"], meta["programs_reused"]) == (0, cohorts)


def test_sweep_trace_counts_ignore_other_threads():
    """A call's trace counts are its own: a cold sweep run by another
    thread while the call is under way (here between its set-up and its
    first step, where its own traces happen) adds nothing to them."""
    data = _cache_data()
    cfg = SweepConfig(rounds=1, steps_per_round=1, batch_size=16,
                      eval_samples=32, engine="jnp", fused=False,
                      quarantine=False)
    specs = _cache_specs()
    cohorts = len(bucket(specs))
    other = {}

    class SideSweep(Recorder):
        def count(self, name, n=1):
            super().count(name, n)
            if name == "sweep.programs_reused":
                th = threading.Thread(target=lambda: other.update(
                    run_sweep(_cache_specs(act="relu"), *data,
                              cfg).ledger.meta))
                th.start()
                th.join()

    clear_program_cache()
    meta = run_sweep(specs, *data, cfg, recorder=SideSweep()).ledger.meta
    assert other["step_traces"] == other["eval_traces"] == cohorts
    assert (meta["step_traces"], meta["eval_traces"]) == (cohorts, cohorts)
