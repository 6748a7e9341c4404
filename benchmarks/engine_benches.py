"""Engine-comparison benchmarks: jnp gather+einsum vs fused Pallas engine.

Three junction shapes anchor the perf trajectory from this PR onward:

* ``engine.mnist.*`` — the paper's MNIST junction in block form
  (1024 -> 512 @ density 0.25, the TPU-native analogue of the 1024x64
  d_out=8 junction the FPGA implements).
* ``engine.ffn.*``   — a transformer FFN up-projection
  (1024 -> 4096 @ density 0.25), the shape the ROADMAP north-star cares
  about.
* ``engine.moe.*``   — a full sparse-expert MoE layer (4 experts, top-2,
  1024 -> 512 per expert @ density 0.25) through ``moe_apply``: routing +
  dispatch identical per engine, the expert FFNs either through the
  unified junction engine (E-batched grid (E, M/bm, nob/bn), SwiGLU gate
  in one pass) or the reference gather+einsum loop.

Each row times one jit'd forward+backward (loss = sum(y)) per engine.

``engine.update.*`` rows (ISSUE 4) time the full train-update cycle —
fwd + bwd + SGD-momentum update: the ``jnp`` rows run the two-pass
reference (materialized dw, tree-mapped update), the ``pallas`` rows the
fused BP+UP path (update applied in the backward kernels' epilogue,
params donated through input_output_aliasing — the dw HBM round-trip the
fused path exists to delete).  ``engine.update.adam.*`` rows (ISSUE 7)
run the same cycle under the in-kernel Adam epilogue: a second fp32
accumulator (vel) aliased in place and a full ``(HYP_K,)`` registry row
instead of the legacy (2,) [lr, momentum] pair.

``bench.guard.overhead`` (ISSUE 6) times the fused MNIST update cycle
with the in-kernel [E] divergence-flag output (the guardian's detector)
against the plain fused cycle; the row's ``derived`` field carries the
with/without ratio.

``bench.sweep.mnist.*`` rows (ISSUE 5) time the population engine: one
E-batched population train step (E MNIST candidates with distinct
learning rates advancing in single kernel launches via the [E, 2] hyp
table) against E sequential single-model steps doing the same total
work — the resource-vs-training-time trade the sweep subsystem
(src/repro/search/) turns into a user-facing knob.

``engine.infer.int8.{mnist,moe}.*`` rows (ISSUE 8) time the quantized
inference datapath: the same MNIST junction / MoE layer forwards with
int8 weight codes + per-block scales (core/quantize.py) through the
quantized kernels (``pallas``) or their op-for-op jnp sims (``jnp``) —
forward-only, since the quantized specs are inference-only by contract.
``bench.quant.sweep`` times the quant sweep's inner loop: one E=4
stacked quantized population (four int8 configs sharing one cohort)
evaluated in a single E-batched launch.

Off-TPU the Pallas rows run in interpret mode — an emulator, so their
absolute numbers only become meaningful on real hardware; the jnp rows
are the portable baseline.  ``BENCH_*.json`` (benchmarks/run.py --json)
makes the trajectory machine-trackable.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.core import sparse_linear as sl
from repro.core.sparsity import SparsityConfig, make_block_pattern
from repro.kernels import block_sparse_matmul as bsm
from repro.models import moe as moe_mod
from repro.optim import constant_schedule, fused_adam, fused_sgd

SHAPES = {
    # name: (n_in, n_out, density, block, M_fast, M_full)
    "mnist": (1024, 512, 0.25, 128, 256, 12544),
    "ffn": (1024, 4096, 0.25, 128, 256, 4096),
}

# MoE bench: (E, top_k, d_model, d_expert, density, block, tok_fast, tok_full)
MOE_SHAPE = (4, 2, 1024, 512, 0.25, 128, 128, 2048)


def _junction_params(n_in, n_out, density, block):
    sp = SparsityConfig(density=density, block=block, where="ffn")
    return sl.init_sparse(jax.random.PRNGKey(0), n_in, n_out, sp, bias=True)


def _time_fwd_bwd(params, x, engine, n=3):
    @jax.jit
    def step(params, x):
        def loss(w, x):
            return jnp.sum(sl.apply(dict(params, w=w), x,
                                    engine=engine, act="sigmoid"))
        l, gw = jax.value_and_grad(loss)(params["w"], x)
        return l, gw

    out = step(params, x)           # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = step(params, x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


_UPDATE_LR, _UPDATE_BETA = 1e-3, 0.9
_UPDATE_B2, _UPDATE_EPS = 0.95, 1e-8


def _time_junction_update(params, x, mode, n=3, with_health=False,
                          optim="sgd"):
    """One full junction train step — fwd + bwd + in-kernel update.
    mode "jnp": two-pass reference (dw materialized, update tree-mapped);
    mode "pallas": fused BP+UP (ops.junction_train_update, dw consumed by
    the in-kernel update, params/accumulators aliased in place).  optim
    picks the epilogue rule — "sgd" (momentum) rides the legacy (2,) hyp
    pair, "adam" a full (HYP_K,) registry row plus the second (vel) fp32
    accumulator.  with_health additionally rides the [E] divergence-flag
    output through the update kernels' flush epilogue (the guardian's
    in-kernel detector)."""
    from repro.kernels import ops as kops

    if optim == "adam":
        hyp = (jnp.zeros((bsm.HYP_K,), jnp.float32)
               .at[bsm.COL_LR].set(_UPDATE_LR)
               .at[bsm.COL_B1].set(_UPDATE_BETA)
               .at[bsm.COL_B2].set(_UPDATE_B2)
               .at[bsm.COL_EPS].set(_UPDATE_EPS)
               .at[bsm.COL_T].set(1.0)
               .at[bsm.COL_GS].set(1.0))
    else:
        hyp = jnp.asarray([_UPDATE_LR, _UPDATE_BETA], jnp.float32)
    pat = (params["idx"], params["rev_ob"], params["rev_t"],
           params["rev_cnt"])
    mom = jnp.zeros(params["w"].shape, jnp.float32)
    mom_b = jnp.zeros(params["b"].shape, jnp.float32)
    vel = jnp.zeros(params["w"].shape, jnp.float32)
    vel_b = jnp.zeros(params["b"].shape, jnp.float32)

    if mode == "pallas" and optim == "adam":
        @jax.jit
        def step(w, b, mom, mom_b, x):
            def loss(w, b, m, mb, v, vb):
                return jnp.sum(kops.junction_train_update(
                    x, w, *pat, bias=b, act="sigmoid", hyp=hyp,
                    mom=m, mom_b=mb, vel=v, vel_b=vb))
            return jax.grad(loss, (0, 1, 2, 3, 4, 5))(
                w, b, mom, mom_b, vel, vel_b)
    elif mode == "jnp" and optim == "adam":
        c1 = 1.0 - _UPDATE_BETA         # bias correction at t = 1
        c2 = 1.0 - _UPDATE_B2

        @jax.jit
        def step(w, b, mom, mom_b, x):
            def loss(w, b):
                return jnp.sum(sl.apply(dict(params, w=w, b=b), x,
                                        engine="jnp", act="sigmoid"))
            gw, gb = jax.grad(loss, (0, 1))(w, b)
            m = _UPDATE_BETA * mom + (1 - _UPDATE_BETA) * gw
            v = _UPDATE_B2 * vel + (1 - _UPDATE_B2) * gw * gw
            mb_ = _UPDATE_BETA * mom_b + (1 - _UPDATE_BETA) * gb
            vb_ = _UPDATE_B2 * vel_b + (1 - _UPDATE_B2) * gb * gb
            nw = w - _UPDATE_LR * (m / c1) / (jnp.sqrt(v / c2) + _UPDATE_EPS)
            nb = b - _UPDATE_LR * (mb_ / c1) / (jnp.sqrt(vb_ / c2)
                                                + _UPDATE_EPS)
            return nw, nb, m, mb_, v, vb_
    elif mode == "pallas" and with_health:
        h0 = jnp.zeros((1,), jnp.float32)

        @jax.jit
        def step(w, b, mom, mom_b, x):
            def loss(w, b, m, mb, h):
                return jnp.sum(kops.junction_train_update(
                    x, w, *pat, bias=b, act="sigmoid", hyp=hyp,
                    mom=m, mom_b=mb, health=h))
            return jax.grad(loss, (0, 1, 2, 3, 4))(w, b, mom, mom_b, h0)
    elif mode == "pallas":
        @jax.jit
        def step(w, b, mom, mom_b, x):
            def loss(w, b, m, mb):
                return jnp.sum(kops.junction_train_update(
                    x, w, *pat, bias=b, act="sigmoid", hyp=hyp,
                    mom=m, mom_b=mb))
            return jax.grad(loss, (0, 1, 2, 3))(w, b, mom, mom_b)
    else:
        @jax.jit
        def step(w, b, mom, mom_b, x):
            def loss(w, b):
                return jnp.sum(sl.apply(dict(params, w=w, b=b), x,
                                        engine="jnp", act="sigmoid"))
            gw, gb = jax.grad(loss, (0, 1))(w, b)
            mv = _UPDATE_BETA * mom + gw
            mbv = _UPDATE_BETA * mom_b + gb
            return (w - _UPDATE_LR * mv, b - _UPDATE_LR * mbv, mv, mbv)

    out = step(params["w"], params["b"], mom, mom_b, x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = step(params["w"], params["b"], mom, mom_b, x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _time_moe_update(params, x, mode, n=3, optim="sgd"):
    """Full MoE layer train-update cycle through the inject/merge plumbing
    the fused train step uses (core/sparse_linear.inject_update_ctx +
    optim.FusedOptimizer.merge) vs the two-pass optimizer.update
    reference.  optim "adam" swaps in fused_adam (second vel accumulator
    per junction, (HYP_K,) registry row)."""
    cfg = _moe_cfg("pallas" if mode == "pallas" else "jnp")
    if optim == "adam":
        opt = fused_adam(constant_schedule(_UPDATE_LR), b1=_UPDATE_BETA,
                         b2=_UPDATE_B2, eps=_UPDATE_EPS)
    else:
        opt = fused_sgd(constant_schedule(_UPDATE_LR),
                        momentum=_UPDATE_BETA)
    st = opt.init(params)
    step0 = jnp.zeros((), jnp.int32)

    def loss(p):
        y, aux, _ = moe_mod.moe_apply(p, x, cfg)
        return jnp.sum(y) + aux

    if mode == "pallas":
        @jax.jit
        def step(params, st, x):
            aug = sl.inject_update_ctx(params, opt.slots(st),
                                       opt.hyp(step0))
            grads = jax.grad(loss, allow_int=True)(aug)
            return opt.merge(grads, st, params, step0)
    else:
        @jax.jit
        def step(params, st, x):
            grads = jax.grad(loss, allow_int=True)(params)
            return opt.update(grads, st, params, step0)

    out = step(params, st, x)
    jax.block_until_ready(jax.tree.leaves(out))
    t0 = time.perf_counter()
    for _ in range(n):
        out = step(params, st, x)
    jax.block_until_ready(jax.tree.leaves(out))
    return (time.perf_counter() - t0) / n


def _moe_cfg(engine: str) -> ArchConfig:
    E, K, d, f, density, block, _, _ = MOE_SHAPE
    return ArchConfig(
        name="bench-moe", family="moe", n_layers=1, d_model=d, n_heads=8,
        kv_heads=8, head_dim=d // 8, d_ff=4 * d, vocab=256, dtype="float32",
        moe=MoEConfig(num_experts=E, top_k=K, d_expert=f),
        sparsity=SparsityConfig(density=density, block=block, where="ffn"),
        engine=engine)


def _time_moe_fwd_bwd(params, x, engine, n=1):
    cfg = _moe_cfg(engine)

    @jax.jit
    def step(params, x):
        def loss(p, x):
            y, aux, _ = moe_mod.moe_apply(p, x, cfg)
            return jnp.sum(y) + aux
        # allow_int: the shared block pattern rides in int32 param leaves
        return jax.value_and_grad(loss, allow_int=True)(params, x)

    out = step(params, x)           # compile
    jax.block_until_ready(jax.tree.leaves(out))
    t0 = time.perf_counter()
    for _ in range(n):
        out = step(params, x)
    jax.block_until_ready(jax.tree.leaves(out))
    return (time.perf_counter() - t0) / n


def bench(fast=True):
    on_tpu = jax.default_backend() == "tpu"
    rows = []
    for name, (n_in, n_out, density, block, m_fast, m_full) in SHAPES.items():
        M = m_fast if fast else m_full
        params = _junction_params(n_in, n_out, density, block)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, n_in), jnp.float32)
        pat = make_block_pattern(n_in, n_out, density, block)
        grid = bsm.fwd_grid(M, pat.n_out_blocks, pat.fan_in_blocks, block,
                            pat.n_in_blocks, 4)
        # n=1 off-TPU proved too noisy for the ci.sh baseline comparison
        # (single-call jitter looked like a 3x regression); 3 calls of the
        # fast shapes stay well under a second per row
        n = 3
        for engine in ("jnp", "pallas"):
            dt = _time_fwd_bwd(params, x, engine, n=n)
            mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
            rows.append({
                "name": f"engine.{name}.{engine}",
                "us_per_call": dt * 1e6,
                "derived": f"M={M} {n_in}->{n_out} d={density} bs={block} "
                           f"grid={grid[0]}x{grid[1]} mode={mode}",
            })

    # MoE expert FFNs through the expert-batched engine (ISSUE 2 tentpole)
    E, K, d, f, density, block, tok_fast, tok_full = MOE_SHAPE
    T = tok_fast if fast else tok_full
    cfg0 = _moe_cfg("jnp")
    moe_params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, d), jnp.float32)
    ebm, M_e = moe_mod.expert_rows(T)              # buffer rows per expert
    kb = moe_params["idx_in"].shape[1]
    _, ebn = bsm.choose_tiles(M_e, f // block, kb, block, d // block, 4,
                              E=E, n_weight_operands=2)
    n = 3
    for engine in ("jnp", "pallas"):
        dt = _time_moe_fwd_bwd(moe_params, x, engine, n=n)
        mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
        rows.append({
            "name": f"engine.moe.{engine}",
            "us_per_call": dt * 1e6,
            "derived": f"T={T} E={E} top{K} {d}->{f} d={density} bs={block} "
                       f"rows={M_e} tiles={ebm}x{ebn} mode={mode}",
        })

    # fused BP+UP vs two-pass train-update cycle (ISSUE 4 tentpole):
    # MNIST junction fwd+bwd+sgd-momentum ...
    n_in, n_out, density, block, m_fast, m_full = (*SHAPES["mnist"],)
    Mu = m_fast if fast else m_full
    up_params = _junction_params(n_in, n_out, density, block)
    xu = jax.random.normal(jax.random.PRNGKey(2), (Mu, n_in), jnp.float32)
    for engine in ("jnp", "pallas"):
        dt = _time_junction_update(up_params, xu, engine, n=3)
        mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
        rows.append({
            "name": f"engine.update.mnist.{engine}",
            "us_per_call": dt * 1e6,
            "derived": f"M={Mu} {n_in}->{n_out} d={density} bs={block} "
                       f"sgd-momentum {'fused' if engine == 'pallas' else 'two-pass'} "
                       f"mode={mode}",
        })
    # ... the same cycle under the in-kernel Adam epilogue (ISSUE 7):
    # second fp32 accumulator (vel) aliased in place, (HYP_K,) hyp row
    for engine in ("jnp", "pallas"):
        dt = _time_junction_update(up_params, xu, engine, n=3, optim="adam")
        mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
        rows.append({
            "name": f"engine.update.adam.mnist.{engine}",
            "us_per_call": dt * 1e6,
            "derived": f"M={Mu} {n_in}->{n_out} d={density} bs={block} "
                       f"adam {'fused' if engine == 'pallas' else 'two-pass'} "
                       f"mode={mode}",
        })
    # divergence-guard overhead (ISSUE 6): the fused MNIST update cycle
    # with the in-kernel [E] health output riding the flush epilogue vs
    # without — the cost of always-on non-finite detection
    dt_plain = _time_junction_update(up_params, xu, "pallas", n=3)
    dt_guard = _time_junction_update(up_params, xu, "pallas", n=3,
                                     with_health=True)
    mode = "compiled" if on_tpu else "interpret"
    rows.append({
        "name": "bench.guard.overhead",
        "us_per_call": dt_guard * 1e6,
        "derived": f"M={Mu} {n_in}->{n_out} d={density} bs={block} "
                   f"fused+health vs fused "
                   f"ratio={dt_guard / max(dt_plain, 1e-12):.3f} "
                   f"mode={mode}",
    })
    # ... and the full sparse-expert MoE layer through inject/merge
    for engine in ("jnp", "pallas"):
        dt = _time_moe_update(moe_params, x, engine, n=3)
        mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
        rows.append({
            "name": f"engine.update.moe.{engine}",
            "us_per_call": dt * 1e6,
            "derived": f"T={T} E={E} top{K} {d}->{f} d={density} bs={block} "
                       f"sgd-momentum {'fused' if engine == 'pallas' else 'two-pass'} "
                       f"mode={mode}",
        })
    for engine in ("jnp", "pallas"):
        dt = _time_moe_update(moe_params, x, engine, n=3, optim="adam")
        mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
        rows.append({
            "name": f"engine.update.adam.moe.{engine}",
            "us_per_call": dt * 1e6,
            "derived": f"T={T} E={E} top{K} {d}->{f} d={density} bs={block} "
                       f"adam {'fused' if engine == 'pallas' else 'two-pass'} "
                       f"mode={mode}",
        })
    rows.extend(_quant_rows(fast, on_tpu))
    rows.extend(_sweep_rows(fast, on_tpu))
    return rows


# --------------------------------------------- quantized-inference rows
def _time_infer(step, args, n=3):
    out = step(*args)               # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = step(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _quant_rows(fast, on_tpu):
    """engine.infer.int8.* (quantized forwards per engine, ISSUE 8) and
    bench.quant.sweep (one E-batched quantized-population eval)."""
    from repro.core import quantize as qz

    rows = []
    n_in, n_out, density, block, m_fast, m_full = (*SHAPES["mnist"],)
    M = m_fast if fast else m_full
    params = _junction_params(n_in, n_out, density, block)
    x = jax.random.normal(jax.random.PRNGKey(3), (M, n_in), jnp.float32)
    qp = qz.quantize_junction(params, qz.QuantConfig(mode="int8"))
    for engine in ("jnp", "pallas"):
        step = jax.jit(lambda p, x, e=engine: sl.apply(p, x, engine=e,
                                                       act="sigmoid"))
        dt = _time_infer(step, (qp, x))
        mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
        rows.append({
            "name": f"engine.infer.int8.mnist.{engine}",
            "us_per_call": dt * 1e6,
            "derived": f"M={M} {n_in}->{n_out} d={density} bs={block} "
                       f"int8 fwd-only mode={mode}",
        })

    E, K, d, f, density, block, tok_fast, tok_full = MOE_SHAPE
    T = tok_fast if fast else tok_full
    moe_params = moe_mod.moe_init(jax.random.PRNGKey(0), _moe_cfg("jnp"))
    moe_q = qz.quantize_tree(moe_params, qz.QuantConfig(mode="int8"))
    xm = jax.random.normal(jax.random.PRNGKey(4), (1, T, d), jnp.float32)
    for engine in ("jnp", "pallas"):
        cfg = _moe_cfg(engine)

        @jax.jit
        def step(p, x, cfg=cfg):
            y, _, _ = moe_mod.moe_apply(p, x, cfg)
            return y

        dt = _time_infer(step, (moe_q, xm))
        mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
        rows.append({
            "name": f"engine.infer.int8.moe.{engine}",
            "us_per_call": dt * 1e6,
            "derived": f"T={T} E={E} top{K} {d}->{f} d={density} bs={block} "
                       f"int8 fwd-only mode={mode}",
        })

    # one cohort of the PTQ sweep (launch/quant_sweep.py): four int8
    # configs stacked on the member axis, one E-batched quantized eval
    Eq = 4
    configs = [qz.QuantConfig(mode="int8", bits=b, granularity=g)
               for b, g in ((8, "block"), (6, "block"), (4, "block"),
                            (8, "unit"))]
    members = [qz.quantize_junction(params, q) for q in configs]
    popq = {k: members[0][k] for k in sl.PATTERN_LEAVES}
    for k in ("wq", "w_scale", "b"):
        popq[k] = jnp.stack([m[k] for m in members])
    Ms = 256 if fast else 1024
    xs = jnp.broadcast_to(x[:Ms][None], (Eq, Ms, n_in))
    engine = sl.resolve_engine("auto")
    mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
    step = jax.jit(lambda p, x: sl.apply(p, x, engine=engine, act="sigmoid"))
    dt = _time_infer(step, (popq, xs))
    rows.append({
        "name": "bench.quant.sweep",
        "us_per_call": dt * 1e6,
        "derived": f"E={Eq} M={Ms} {n_in}->{n_out} d={density} bs={block} "
                   f"one E-batched int8 cohort eval engine={engine} "
                   f"mode={mode}",
    })
    return rows


# ------------------------------------------------- population-sweep rows
def _time_population_steps(step_fns, states, xb, tb, n=3):
    """Mean wall time of one 'generation': every (step, state) pair
    advanced once — ONE call for the E-batched population, E calls for
    the sequential baseline."""
    def run(states):
        out = []
        for fn, (p, m, h, k) in zip(step_fns, states):
            out.append(fn(p, m, h, k, xb, tb))
        jax.block_until_ready([o[2] for o in out])
        return [(p, m, h, k) for (p, m, _), (_, _, h, k) in zip(out, states)]

    states = run(states)            # compile
    t0 = time.perf_counter()
    for _ in range(n):
        states = run(states)
    return (time.perf_counter() - t0) / n


def _sweep_rows(fast, on_tpu):
    """bench.sweep.mnist.{population,sequential}: E=4 MNIST candidates,
    distinct lrs, one E-batched step vs E sequential single-model steps
    (same structure, same data, same update math)."""
    from repro.search import CandidateSpec, hyp_table, init_population
    from repro.search import population as pop

    E = 4
    layers = (1024, 512, 128)
    M = 256 if fast else 12544
    engine = sl.resolve_engine("auto")
    mode = "compiled" if (on_tpu or engine == "jnp") else "interpret"
    specs = [CandidateSpec(lr=0.02 * (i + 1), momentum=0.9, density=0.25,
                           layers=layers, block=128, init_seed=i)
             for i in range(E)]
    key = jax.random.PRNGKey(0)
    xb = jax.random.uniform(jax.random.PRNGKey(1), (M, layers[0]))
    tb = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(2), (M,), 0, 10), layers[-1])

    pop_params = init_population(key, specs)
    batched = [(pop_params, pop.init_momentum(pop_params), hyp_table(specs),
                jnp.ones((E,), jnp.float32))]
    step = pop.make_population_step(engine=engine, donate=False)
    dt = _time_population_steps([step], batched, xb, tb)
    rows = [{
        "name": "bench.sweep.mnist.population",
        "us_per_call": dt * 1e6,
        "derived": f"E={E} M={M} layers={'x'.join(map(str, layers))} "
                   f"one E-batched step engine={engine} mode={mode}",
    }]

    seq = []
    for i in range(E):
        p1 = init_population(key, specs[i:i + 1])
        seq.append((p1, pop.init_momentum(p1), hyp_table(specs[i:i + 1]),
                    jnp.ones((1,), jnp.float32)))
    step1 = pop.make_population_step(engine=engine, donate=False)
    dt = _time_population_steps([step1] * E, seq, xb, tb)
    rows.append({
        "name": "bench.sweep.mnist.sequential",
        "us_per_call": dt * 1e6,
        "derived": f"E={E} M={M} layers={'x'.join(map(str, layers))} "
                   f"{E} sequential single-model steps engine={engine} "
                   f"mode={mode}",
    })
    return rows
