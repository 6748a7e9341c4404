"""Divergence guardian (ISSUE 6): in-kernel health flags, checkpoint
rollback with lr backoff, cohort quarantine, serve-side logit guard.

Layers under test, bottom-up:
  * kernels/ops — the update kernels' [E] health output: zero on clean
    updates (and numerically inert), > 0 the moment an update writes
    non-finite parameters in place;
  * search/population — per-member health isolation: one diverged member
    flags ONLY its own slot, on both the fused (in-kernel flags) and
    two-pass (materialized-grad scan) paths;
  * train/steps + train_loop — lr_scale equivalence (hyp-table fold vs
    delta interpolation) and the full trip -> rollback -> backoff ->
    skip -> recover loop against a NaN/inf-poisoned data stream;
  * search/scheduler — mid-round quarantine leaves the survivors'
    parameter trajectories BITWISE identical to a cohort that never
    contained the diverged member;
  * serve/engine — a slot whose logits go non-finite is EOS-terminated
    while every other slot's output is untouched.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SweepConfig
from repro.core import sparse_linear as sl
from repro.core.sparsity import SparsityConfig
from repro.kernels import ops
from repro.search import CandidateSpec, run_sweep
from repro.search import population as pop
from repro.train import checkpoint as ckpt_mod
from repro.train import steps as steps_mod
from repro.optim import constant_schedule, fused_sgd
from repro.train.train_loop import (GuardianConfig, GuardianTripped,
                                    TrainLoopConfig, run)

N_IN, N_OUT, BATCH = 128, 64, 32
_SP = SparsityConfig(density=0.5, block=32, where="all")


def _junction(seed=0):
    return sl.init_sparse(jax.random.PRNGKey(seed), N_IN, N_OUT, _SP,
                          bias=True)


# ------------------------------------------------------------ kernel level
def test_health_flags_zero_and_inert_on_clean_update():
    """Clean update: health == 0 AND riding the health operand changes no
    numerics (same updated params/momenta as the plain fused call)."""
    p = _junction()
    pat = (p["idx"], p["rev_ob"], p["rev_t"], p["rev_cnt"])
    hyp = jnp.asarray([0.05, 0.9], jnp.float32)
    mom = jnp.zeros(p["w"].shape, jnp.float32)
    mom_b = jnp.zeros(p["b"].shape, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, N_IN))

    def loss_h(w, b, m, mb, h):
        y = ops.junction_train_update(x, w, *pat, bias=b, act="sigmoid",
                                      hyp=hyp, mom=m, mom_b=mb, health=h)
        return jnp.sum(y)

    def loss_plain(w, b, m, mb):
        y = ops.junction_train_update(x, w, *pat, bias=b, act="sigmoid",
                                      hyp=hyp, mom=m, mom_b=mb)
        return jnp.sum(y)

    h0 = jnp.zeros((1,), jnp.float32)
    w_h, b_h, m_h, mb_h, h = jax.grad(loss_h, (0, 1, 2, 3, 4))(
        p["w"], p["b"], mom, mom_b, h0)
    w_p, b_p, m_p, mb_p = jax.grad(loss_plain, (0, 1, 2, 3))(
        p["w"], p["b"], mom, mom_b)
    assert float(h[0]) == 0.0
    for a, b in [(w_h, w_p), (b_h, b_p), (m_h, m_p), (mb_h, mb_p)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_health_flags_fire_on_nonfinite_update():
    """NaN in the input -> NaN dw -> the in-kernel update writes
    non-finite parameters -> the flushed health count goes positive."""
    p = _junction()
    pat = (p["idx"], p["rev_ob"], p["rev_t"], p["rev_cnt"])
    hyp = jnp.asarray([0.05, 0.9], jnp.float32)
    mom = jnp.zeros(p["w"].shape, jnp.float32)
    mom_b = jnp.zeros(p["b"].shape, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, N_IN))
    x = x.at[0, 0].set(jnp.nan)

    def loss(w, b, m, mb, h):
        y = ops.junction_train_update(x, w, *pat, bias=b, act="sigmoid",
                                      hyp=hyp, mom=m, mom_b=mb, health=h)
        return jnp.sum(jnp.where(jnp.isfinite(y), y, 0.0))

    h0 = jnp.zeros((1,), jnp.float32)
    w, b, m, mb, h = jax.grad(loss, (0, 1, 2, 3, 4))(
        p["w"], p["b"], mom, mom_b, h0)
    assert float(h[0]) > 0.0
    assert not bool(jnp.all(jnp.isfinite(w)))


@pytest.mark.parametrize("gated", [False, True], ids=["biased", "gated"])
def test_health_flags_per_unit_at_e3(gated):
    """E=3 Adam units: poisoning unit 1's rows flags unit 1 only (the
    [E, 1, 128] health rows are per unit), and on the clean run the flags
    are zero and the health operand changes no numerics."""
    from repro.core.sparsity import make_block_pattern
    E, bs, M = 3, 32, 24
    pat = make_block_pattern(N_IN, N_OUT, 0.5, bs)
    patt = tuple(jnp.asarray(a) for a in (pat.idx, pat.rev_ob, pat.rev_t,
                                          pat.rev_cnt))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    shape = (E, pat.n_out_blocks, pat.fan_in_blocks, bs, bs)
    w = jax.random.normal(ks[0], shape) * 0.1
    wi = jax.random.normal(ks[1], shape) * 0.1 if gated else None
    b = None if gated else jnp.zeros((E, N_OUT))
    zeros = lambda a: None if a is None else jnp.zeros(a.shape, jnp.float32)
    hyp = jnp.asarray([0.01, 0.9, 0.95, 1e-8, 0.0, 1.0, 1.0], jnp.float32)
    clean = jax.random.normal(ks[2], (E, M, N_IN))

    def updated(x, health):
        def loss(w, wi, b, h):
            y = ops.junction_train_update(
                x, w, *patt, wi=wi, bias=b,
                act="none" if gated else "sigmoid", hyp=hyp,
                mom=zeros(w), mom_wi=zeros(wi), mom_b=zeros(b),
                vel=zeros(w), vel_wi=zeros(wi), vel_b=zeros(b), health=h)
            return jnp.sum(jnp.where(jnp.isfinite(y), y, 0.0))
        if health is None:
            return jax.grad(lambda w: loss(w, wi, b, None))(w), None
        gw, gh = jax.grad(lambda w, h: loss(w, wi, b, h), (0, 1))(
            w, jnp.zeros((E,), jnp.float32))
        return gw, gh

    w_h, h = updated(clean, health=True)
    w_p, _ = updated(clean, health=None)
    np.testing.assert_array_equal(np.asarray(h), np.zeros(E))
    np.testing.assert_array_equal(np.asarray(w_h), np.asarray(w_p))
    _, h_bad = updated(clean.at[1, 0, 0].set(jnp.nan), health=True)
    h_bad = np.asarray(h_bad)
    assert h_bad[1] > 0 and h_bad[0] == 0 and h_bad[2] == 0, h_bad


# ------------------------------------------------------- population level
@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_population_health_isolates_bad_member(engine):
    """One member with a poisoned weight flags ONLY its own slot."""
    specs = [CandidateSpec(lr=0.05, momentum=0.0, density=0.5,
                           layers=(N_IN, N_OUT), block=32, init_seed=i)
             for i in range(3)]
    params = pop.init_population(jax.random.PRNGKey(0), specs)
    mom = pop.init_momentum(params, specs)
    hyp = pop.hyp_table(specs)
    mask = jnp.ones((3,), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, N_IN))
    t = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(2), (BATCH,), 0, N_OUT), N_OUT)
    step = pop.make_population_step(engine=engine, with_health=True,
                                    donate=False)

    _, _, losses, health = step(params, mom, hyp, mask, x, t)
    assert np.asarray(health).tolist() == [0.0, 0.0, 0.0]

    params[0]["w"] = params[0]["w"].at[1, 0, 0, 0, 0].set(jnp.nan)
    new_params, _, losses, health = step(params, mom, hyp, mask, x, t)
    health = np.asarray(health)
    assert health[1] > 0.0
    assert health[0] == 0.0 and health[2] == 0.0
    # the clean members' updates stayed finite
    for e in (0, 2):
        for layer in pop.member_slice(new_params, e):
            assert bool(jnp.all(jnp.isfinite(layer["w"])))


# -------------------------------------------------- guardian loop (e2e)
@dataclasses.dataclass
class PoisonPipeline:
    """Deterministic (seed, step) regression stream — targets are a
    learnable function t = sigmoid(x @ W_true) — with chosen data steps
    poisoned by a non-finite input value."""
    w_true: np.ndarray
    poison_steps: frozenset = frozenset()
    poison_value: float = np.inf
    seed: int = 0
    step: int = 0

    def state(self):
        return {"seed": self.seed, "step": self.step}

    def __iter__(self):
        return self

    def __next__(self):
        rng = np.random.default_rng((self.seed << 20) ^ self.step)
        x = rng.standard_normal((BATCH, N_IN)).astype(np.float32)
        t = 1.0 / (1.0 + np.exp(-(x @ self.w_true)))
        if self.step in self.poison_steps:
            x[0, 0] = self.poison_value
        self.step += 1
        return {"x": x, "t": t.astype(np.float32)}


def _make_regression_step(engine, lr=0.2, momentum=0.9):
    """A train_step honouring the 5-arg (params, opt, batch, step,
    lr_scale) contract on a single junction: the fused path mirrors
    steps._make_fused_train_step (hyp-table fold, in-kernel health),
    the two-pass path mirrors the reference (delta interpolation,
    materialized-grad scan)."""
    opt = fused_sgd(constant_schedule(lr), momentum=momentum)

    if engine == "pallas":
        def train_step(params, opt_state, batch, step, lr_scale=None):
            from repro.kernels import block_sparse_matmul as bsm
            hyp = opt.hyp(step)
            if lr_scale is not None:
                hyp = hyp.at[bsm.COL_LR].multiply(jnp.float32(lr_scale))
            aug = sl.inject_update_ctx(params, opt.slots(opt_state), hyp)

            def loss(aug):
                y = sl.apply(aug, batch["x"], engine="pallas", act="sigmoid")
                return jnp.mean(jnp.square(y - batch["t"]))

            l, grads = jax.value_and_grad(loss, allow_int=True)(aug)
            new_params, new_opt = opt.merge(grads, opt_state, params, step,
                                            lr_scale=lr_scale)
            return new_params, new_opt, {
                "loss": l,
                "nonfinite": steps_mod.collect_junction_health(grads)}
    else:
        def train_step(params, opt_state, batch, step, lr_scale=None):
            def loss(params):
                y = sl.apply(params, batch["x"], engine="jnp", act="sigmoid")
                return jnp.mean(jnp.square(y - batch["t"]))

            l, grads = jax.value_and_grad(loss, allow_int=True)(params)
            new_params, new_opt = opt.update(grads, opt_state, params, step)
            if lr_scale is not None:
                new_params = steps_mod.scale_params_delta(params, new_params,
                                                          lr_scale)
            return new_params, new_opt, {
                "loss": l,
                "nonfinite": steps_mod.count_nonfinite_grads(grads)}

    return opt, jax.jit(train_step)


def _w_true():
    return np.asarray(jax.random.normal(jax.random.PRNGKey(7),
                                        (N_IN, N_OUT))) * 0.1


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_lr_scale_matches_true_lr(engine):
    """Backed-off lr via the lr_scale operand == actually running at the
    scaled lr: exact on two-pass (delta interpolation), kernel round-off
    on fused (hyp-table fold)."""
    params = _junction()
    batch = jax.tree.map(jnp.asarray, next(PoisonPipeline(_w_true())))
    opt, step_scaled = _make_regression_step(engine, lr=0.2)
    _, step_half = _make_regression_step(engine, lr=0.1)
    st = opt.init(params)
    p1, _, _ = step_scaled(params, st, batch, jnp.asarray(0),
                           jnp.float32(0.5))
    p2, _, _ = step_half(params, st, batch, jnp.asarray(0))
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_guardian_rollback_recovers_poisoned_run(engine, tmp_path):
    """Acceptance e2e: a poisoned batch trips the guardian (finite loss,
    non-finite update — the health-flag sentinel, not the loss one),
    training rolls back to the last healthy checkpoint, the offending
    batch is skipped, lr is backed off, and the run finishes with finite
    params and a loss close to the clean run's.  Without the guardian the
    same stream ends with non-finite parameters."""
    w_true = _w_true()
    params = _junction()
    opt, train_step = _make_regression_step(engine)
    quiet = lambda s: None
    total, poison_at = 30, 12

    # clean reference
    clean = run(TrainLoopConfig(total, str(tmp_path / "clean"),
                                ckpt_every=5, log_every=5),
                train_step, params, opt.init(params),
                PoisonPipeline(w_true), log=quiet)
    clean_loss = clean["history"][-1]["loss"]

    # guarded run over the poisoned stream (+ keep_last_k retention and
    # full-checksum saves riding the same loop)
    g = GuardianConfig(health_window=5, lr_backoff=0.5, max_retries=3,
                       min_history=4)
    res = run(TrainLoopConfig(total, str(tmp_path / "guard"), ckpt_every=5,
                              log_every=5, guardian=g, keep_last_k=3,
                              full_checksum=True),
              train_step, params, opt.init(params),
              PoisonPipeline(w_true, frozenset([poison_at])), log=quiet)
    assert res["step"] == total
    info = res["guardian"]
    assert len(info["trips"]) == 1
    trip = info["trips"][0]
    assert trip["data_step"] == poison_at
    assert "health" in trip["reason"] or "non-finite update" in trip["reason"]
    assert info["lr_scale"] == 0.5
    assert info["skipped_data_steps"] == [poison_at]
    for leaf in jax.tree.leaves(res["params"]):
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            assert bool(jnp.all(jnp.isfinite(leaf)))
    final_loss = res["history"][-1]["loss"]
    assert np.isfinite(final_loss)
    assert abs(final_loss - clean_loss) < 0.05, (final_loss, clean_loss)
    # retention honoured the healthy floor
    steps_left = ckpt_mod.complete_steps(tmp_path / "guard")
    assert ckpt_mod.latest_healthy_step(tmp_path / "guard") in steps_left

    # no guardian: the poisoned update is adopted and params go non-finite
    bare = run(TrainLoopConfig(total, str(tmp_path / "bare"),
                               ckpt_every=50, log_every=50),
               train_step, params, opt.init(params),
               PoisonPipeline(w_true, frozenset([poison_at])), log=quiet)
    assert not all(bool(jnp.all(jnp.isfinite(l)))
                   for l in jax.tree.leaves(bare["params"])
                   if jnp.issubdtype(l.dtype, jnp.inexact))


def test_guardian_exhausts_retries(tmp_path):
    """An unrecoverable stream (every step poisoned) raises
    GuardianTripped with the full trip history after max_retries."""
    w_true = _w_true()
    params = _junction()
    opt, train_step = _make_regression_step("jnp")
    g = GuardianConfig(max_retries=2, health_window=2)
    with pytest.raises(GuardianTripped) as ei:
        run(TrainLoopConfig(20, str(tmp_path), ckpt_every=5, log_every=5,
                            guardian=g),
            train_step, params, opt.init(params),
            PoisonPipeline(w_true, frozenset(range(2, 20)),
                           poison_value=np.nan), log=lambda s: None)
    assert len(ei.value.trips) == 3        # max_retries + the final straw


# -------------------------------------------------- scheduler quarantine
@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_quarantine_leaves_survivors_bitwise_identical(engine, tmp_path):
    """Acceptance: a cohort with a diverging (lr=inf) member, quarantined
    mid-round, produces BITWISE identical survivor parameters to a cohort
    that never contained it — and still names a finite winner."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, N_IN)).astype(np.float32)
    t = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, 256)]
    xe = rng.standard_normal((64, N_IN)).astype(np.float32)
    te = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, 64)]

    def spec(lr, i):
        return CandidateSpec(lr=lr, momentum=0.0, density=0.5,
                             layers=(N_IN, N_OUT), block=32, init_seed=i)

    good = [spec(0.05, 0), spec(0.1, 1)]
    bad = spec(float("inf"), 2)
    cfg = SweepConfig(rounds=2, steps_per_round=4, batch_size=32,
                      eval_samples=64, keep_fraction=1.0, engine=engine,
                      fused=(engine == "pallas"))

    r_with = run_sweep(good + [bad], x, t, xe, te, cfg)
    r_without = run_sweep(good, x, t, xe, te, cfg)

    qrec = r_with.ledger.members[2]
    assert qrec.quarantined_at is not None
    assert qrec.pruned_at == qrec.quarantined_at["round"]
    assert r_with.ledger.meta["quarantined"] == 1
    for m in r_with.ledger.members[:2]:
        assert m.quarantined_at is None and m.pruned_at is None

    # survivors' parameter trajectories: bitwise equal
    for e in range(2):
        with_l = pop.member_slice(r_with.states[0].params, e)
        wo_l = pop.member_slice(r_without.states[0].params, e)
        for lw, lo in zip(with_l, wo_l):
            for k in ("w", "b"):
                assert np.asarray(lw[k]).tobytes() == \
                    np.asarray(lo[k]).tobytes(), (e, k)

    w1, w2 = r_with.ledger.winner(), r_without.ledger.winner()
    assert w1 is not None and w1.member == w2.member
    assert np.isfinite(w1.eval_losses[-1])


# ------------------------------------------------------------ serve guard
def _toy_model():
    from repro.configs import registry
    from repro.models import model as M
    cfg = registry.get("stablelm-3b").reduced()
    return cfg, M.init(cfg, jax.random.PRNGKey(0))


def test_serve_guard_terminates_nonfinite_slot():
    """Non-finite logits in one slot: that slot is EOS-filled from the
    poisoned tick on and counted; every other slot's output is untouched
    (greedy decode, bit-identical)."""
    from repro.serve.engine import Engine, ServeConfig
    cfg, params = _toy_model()
    eos = 5
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=6, temperature=0.0,
                                          eos_token=eos))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(3, 8)).astype(np.int32)
    clean = eng.generate(prompts)
    assert eng.nonfinite_terminated == 0

    orig, calls = eng._decode, {"n": 0}

    def poisoned(params, cache, tok, pos):
        logits, cache = orig(params, cache, tok, pos)
        calls["n"] += 1
        if calls["n"] >= 2:                 # poison slot 0 from tick 2 on
            logits = logits.at[0].set(jnp.nan)
        return logits, cache

    eng._decode = poisoned
    out = eng.generate(prompts)
    assert eng.nonfinite_terminated == 1
    # decode call #2 yields output column 2: slot 0 EOS-filled from there
    assert (out[0, 2:] == eos).all()
    np.testing.assert_array_equal(out[1:], clean[1:])


def test_serve_guard_without_eos_masks_slot():
    """eos_token < 0 (never stop early): the guard must still be able to
    terminate a poisoned slot — filled with token 0."""
    from repro.serve.engine import Engine, ServeConfig
    cfg, params = _toy_model()
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=5, temperature=0.0))
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab, size=(2, 8)).astype(np.int32)
    clean = eng.generate(prompts)

    orig = eng._decode

    def poisoned(params, cache, tok, pos):
        logits, cache = orig(params, cache, tok, pos)
        return logits.at[1].set(jnp.inf), cache

    eng._decode = poisoned
    out = eng.generate(prompts)
    assert eng.nonfinite_terminated == 1
    assert (out[1, 1:] == 0).all()
    np.testing.assert_array_equal(out[0], clean[0])
