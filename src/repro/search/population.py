"""Population-parallel candidate training on the junction engine's E axis.

A *population* is E candidate MLPs that share one network structure —
the same layer widths, block size, pattern seed and per-junction fan-in
(so the SAME scalar-prefetched block patterns) — stacked member-by-member
into the engine's expert dimension: junction weights ``[E, nob, kb, bs,
bs]``, biases ``[E, n_out]``, one pattern riding once in scalar prefetch
for all members.  One fused E-batched train step then advances ALL E
candidates: the forward/backward kernels iterate the expert grid axis,
and the fused BP+UP epilogue reads each member's own registry row from
the per-unit ``[E, HYP_K]`` hyp table (kernels/block_sparse_matmul
.HYP_COLS: lr, b1, b2, eps, wd, t, gs) — E distinct hyperparameter
settings, SGD+momentum or Adam (one optimizer kind per population: the
accumulator-slot layout is static), one kernel launch per junction per
pass.

Because members never interact (the loss is a live-mask-weighted SUM of
per-member losses and every parameter leaf is E-leading), training the
population is mathematically identical to training E single models
independently — the parity contract tests/test_search.py pins down.

Batches are shared: x ``[M, n_in]`` is broadcast to ``[E, M, n_in]``, so
every member sees the same data and differs only in init, structure
cohort, and hyp row.  Pruning (search/scheduler.py) zeroes a member's
mask entry AND its hyp row: masked loss makes its gradients exact zeros,
lr = momentum = 0 freezes its parameters — fixed shapes, no recompiles,
the serve-engine slot-masking pattern applied to training.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import sparse_linear as sl
from repro.core.sparsity import SparsityConfig, block_fan_in


@dataclasses.dataclass(frozen=True)
class CandidateSpec:
    """One candidate network + its training hyperparameters.

    (layers, block, seed, act, opt, density-derived fan-ins) define the
    *structure* — candidates agreeing on all of those share patterns AND
    accumulator-slot layout, so they can ride one population
    (search/cohorts.py buckets by exactly that key); lr / momentum / b2 /
    eps / weight_decay / init_seed vary freely WITHIN a population.

    ``momentum`` is the hyp row's slot-0 decay column: SGD momentum, or
    Adam's b1 when ``opt="adam"`` — the kernels make no distinction.
    """
    lr: float
    momentum: float = 0.0      # slot-0 decay: SGD momentum / Adam b1
    density: float = 0.25
    layers: tuple[int, ...] = (1024, 512, 128)   # widths incl. in/out
    block: int = 128
    act: str = "sigmoid"       # every junction's epilogue (paper Sec. III)
    seed: int = 0              # pattern seed (structure, not init)
    init_seed: int = 0         # weight-init stream for this member
    opt: str = "sgd"           # "sgd" | "adam" (structural: slot layout)
    b2: float = 0.95           # Adam only
    eps: float = 1e-8          # Adam only
    weight_decay: float = 0.0  # Adam only

    def fan_in_blocks(self) -> tuple[int, ...]:
        """kb per junction at this density — the structure the density
        quantizes to (core/sparsity.block_fan_in)."""
        return tuple(block_fan_in(n_in // self.block, self.density)
                     for n_in, _ in zip(self.layers[:-1], self.layers[1:]))

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["layers"] = list(self.layers)   # JSON-canonical (round-trips)
        return d


def structure_key(spec: CandidateSpec) -> tuple:
    """The shared-pattern cohort key: everything that shapes the stacked
    arrays, scalar-prefetch patterns and accumulator-slot layout, nothing
    that doesn't.  ``opt`` is structural: an Adam member needs the v slot
    allocated and the kernels' optimizer switch is static per launch."""
    return (spec.layers, spec.block, spec.seed, spec.act, spec.opt,
            spec.fan_in_blocks())


def _init_member(key, spec: CandidateSpec):
    """Single-model params for one candidate: a list of 4-D junction
    dicts (one per layer pair), patterns deterministic in the spec."""
    sp = SparsityConfig(density=spec.density, block=spec.block, where="all")
    layers = []
    for i, (n_in, n_out) in enumerate(zip(spec.layers[:-1], spec.layers[1:])):
        key, sub = jax.random.split(key)
        layers.append(sl.init_sparse(sub, n_in, n_out, sp, bias=True,
                                     seed=spec.seed))
    return layers


def init_population(key, specs: Sequence[CandidateSpec]):
    """Stack E candidates into population params: a list of junction
    dicts with E-leading trainable leaves and SHARED pattern leaves.

    Each member is initialized exactly as its standalone single model
    would be (fold_in by init_seed) — ``member_slice`` recovers it
    bit-for-bit, which is what makes population-vs-independent parity a
    meaningful test rather than a tautology."""
    if not specs:
        raise ValueError("empty population")
    key0 = structure_key(specs[0])
    for s in specs[1:]:
        if structure_key(s) != key0:
            raise ValueError(
                f"population members must share structure: {structure_key(s)} "
                f"!= {key0} — bucket with search/cohorts.py first")
    members = [_init_member(jax.random.fold_in(key, s.init_seed), s)
               for s in specs]
    pop = []
    for li in range(len(members[0])):
        layer = {k: members[0][li][k] for k in sl.PATTERN_LEAVES}
        layer["w"] = jnp.stack([m[li]["w"] for m in members])
        layer["b"] = jnp.stack([m[li]["b"] for m in members])
        pop.append(layer)
    return pop


def member_slice(params, e: int):
    """Member e's standalone single-model params (4-D junction dicts) —
    the squeeze-path view of one population slot."""
    return [{k: (v[e] if k in ("w", "b") else v) for k, v in layer.items()}
            for layer in params]


def population_size(params) -> int:
    p0 = params[0]
    return (p0["w"] if "w" in p0 else p0["wq"]).shape[0]


def hyp_table(specs: Sequence[CandidateSpec]) -> jax.Array:
    """The per-member [E, HYP_K] registry table the fused update kernels
    index by expert grid coordinate.  Adam members get t = 1 as a
    placeholder — the scheduler stamps the real per-step time into
    COL_T before every step (harmless on SGD/zeroed rows: t is dead
    there)."""
    from repro.kernels import block_sparse_matmul as bsm
    rows = []
    for s in specs:
        row = [0.0] * bsm.HYP_K
        row[bsm.COL_LR] = s.lr
        row[bsm.COL_B1] = s.momentum
        row[bsm.COL_GS] = 1.0
        if s.opt == "adam":
            row[bsm.COL_B2] = s.b2
            row[bsm.COL_EPS] = s.eps
            row[bsm.COL_WD] = s.weight_decay
            row[bsm.COL_T] = 1.0
        rows.append(row)
    return jnp.asarray(rows, jnp.float32)


def _zeros_like_slots(params):
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32)
        if jnp.issubdtype(p.dtype, jnp.inexact) else jnp.zeros((), jnp.float32),
        params)


def init_slots(params, specs: Sequence[CandidateSpec] | None = None):
    """The population's fp32 accumulator-slot trees, kernel slot order:
    () for plain SGD, (mom,) with momentum, (mom, vel) for Adam.  The
    kernels' optimizer switch is static, so opt must be homogeneous
    (structure_key / cohorts enforce this upstream).  Plain SGD returns
    () — skipping a weight-sized fp32 read+write per junction per step
    (zeros-with-beta-0 computes the same numbers, just slower)."""
    if specs is not None:
        kinds = {s.opt for s in specs}
        if len(kinds) > 1:
            raise ValueError(
                f"population mixes optimizer kinds {sorted(kinds)} — the "
                "slot layout is static; bucket with search/cohorts.py first")
        if kinds == {"adam"}:
            return (_zeros_like_slots(params), _zeros_like_slots(params))
        if not any(s.momentum for s in specs):
            return ()
    return (_zeros_like_slots(params),)


def init_momentum(params, specs: Sequence[CandidateSpec] | None = None):
    """Back-compat shim for the pre-Adam API: the slot-0 tree or None.
    New code should use :func:`init_slots` (handles the Adam v slot)."""
    slots = init_slots(params, specs)
    return slots[0] if slots else None


# ------------------------------------------------------------------ forward
def _apply_jnp(layer, x):
    """E-batched junction reference: core/sparse_linear.apply_jnp (the
    ONE gather+einsum reduction) vmapped over the member axis — trainable
    leaves map per member, the shared pattern leaves broadcast
    (x [E, M, n_in] -> [E, M, n_out], bias included, no activation)."""
    in_axes = ({k: (0 if k in ("w", "b") else None) for k in layer}, 0)
    return jax.vmap(sl.apply_jnp, in_axes=in_axes)(layer, x)


def _layer_apply(layer, x, act: str, engine: str):
    if engine == "pallas" or sl.is_quantized(layer):
        # sl.apply dispatches junction_matmul / junction_train_update
        # (when the fused ctx rides in the dict) on the 5-D expert path;
        # quantized layers (launch/quant_sweep.py populations) route
        # through it on EITHER engine — it owns the int8/fxp dispatch
        return sl.apply(layer, x, engine=engine, act=act)
    from repro.kernels import block_sparse_matmul as bsm
    y = _apply_jnp(layer, x)
    return bsm.act_fwd(y, act).astype(y.dtype) if act != "none" else y


def population_forward(params, x, *, act: str, engine: str):
    """y [E, M, n_out] for shared input x [M, n_in] (or pre-broadcast
    [E, M, n_in]) through every junction of the stacked population."""
    E = population_size(params)
    if x.ndim == 2:
        x = jnp.broadcast_to(x[None], (E, *x.shape))
    for layer in params:
        x = _layer_apply(layer, x, act, engine)
    return x


def member_losses(y, targets):
    """Per-member mean-squared error [E] against the shared one-hot
    targets [M, n_out] — the paper's output-MSE objective, one scalar per
    candidate.  Members are independent, so d(sum_e mask_e*loss_e)/d w_e
    = mask_e * d loss_e / d w_e: the population gradient IS the stacked
    single-model gradients."""
    t = targets[None].astype(y.dtype)
    return jnp.mean(jnp.square(y - t), axis=(1, 2))


# --------------------------------------------------------------- train step
def _two_pass_update(params, slots, grads, hyp):
    """Per-member optimizer step over the E-leading leaves: every column
    comes from each member's [E, HYP_K] hyp row, broadcast over the
    trailing dims — the materialized-gradient reference of the fused
    in-kernel epilogue.  len(slots) picks the rule: 0/1 slots = SGD
    (+momentum), 2 slots = Adam, with the SAME t/den guards as the kernel
    so a zeroed hyp row freezes a member EXACTLY on this path too."""
    from repro.kernels import block_sparse_matmul as bsm
    is_adam = len(slots) == 2

    def _row(col, p):
        return hyp[:, col].reshape((-1,) + (1,) * (p.ndim - 1))

    def upd(p, g, *ms):
        if not jnp.issubdtype(p.dtype, jnp.inexact):
            return (p,) + ms
        gf = _row(bsm.COL_GS, p) * g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        lr = _row(bsm.COL_LR, p)
        if is_adam:
            b1, b2 = _row(bsm.COL_B1, p), _row(bsm.COL_B2, p)
            eps, wd = _row(bsm.COL_EPS, p), _row(bsm.COL_WD, p)
            t = _row(bsm.COL_T, p)
            m = b1 * ms[0] + (1.0 - b1) * gf
            v = b2 * ms[1] + (1.0 - b2) * jnp.square(gf)
            c1 = 1.0 - jnp.power(b1, t)
            c2 = 1.0 - jnp.power(b2, t)
            c1 = jnp.where(c1 == 0.0, 1.0, c1)
            c2 = jnp.where(c2 == 0.0, 1.0, c2)
            den = jnp.sqrt(v / c2) + eps
            step_ = jnp.where(den == 0.0, 0.0, (m / c1) / den) + wd * p32
            return (p32 - lr * step_).astype(p.dtype), m, v
        if slots:
            mv = _row(bsm.COL_B1, p) * ms[0] + gf
            return (p32 - lr * mv).astype(p.dtype), mv
        return ((p32 - lr * gf).astype(p.dtype),)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_ms = [treedef.flatten_up_to(s) for s in slots]
    out = [upd(*a) for a in zip(flat_p, flat_g, *flat_ms)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_slots = tuple(treedef.unflatten([o[1 + i] for o in out])
                      for i in range(len(slots)))
    return new_params, new_slots


def _merge_updated(grads, params, slots):
    """Fused-step merge: the cotangents of the augmented tree's junction
    leaves ARE the updated params / slot buffers (every population leaf
    is a junction leaf — no dense remainder to tree-map)."""
    new_params = []
    new_slots = tuple([] for _ in slots)
    for li, (g, p) in enumerate(zip(grads, params)):
        layer = dict(p)
        slayers = tuple(dict(s[li]) for s in slots)
        for k in sl.FUSED_MOM:
            if k in p and not isinstance(p[k], dict):
                layer[k] = g[k]
                for i, names in enumerate(sl.FUSED_SLOT_NAMES[:len(slots)]):
                    slayers[i][k] = g[names[k]]
        new_params.append(layer)
        for i in range(len(slots)):
            new_slots[i].append(slayers[i])
    return new_params, new_slots


def _member_health_fused(grads) -> jax.Array:
    """[E] per-member non-finite-update counts from the injected health
    leaves' cotangents (the update kernels' in-kernel detector) — summed
    across layers."""
    h = None
    for g in grads:
        v = g[sl.UPDATE_HEALTH_LEAF].astype(jnp.float32)
        h = v if h is None else h + v
    return h


def _member_health_jnp(grads) -> jax.Array:
    """[E] two-pass twin: per-member any-non-finite flags over the
    materialized E-leading gradient leaves (one count per bad leaf)."""
    h = None
    for g in grads:
        for k in ("w", "b"):
            f = jnp.any(~jnp.isfinite(g[k].reshape(g[k].shape[0], -1)),
                        axis=1).astype(jnp.float32)
            h = f if h is None else h + f
    return h


def _repack_slots(new_slots: tuple, like):
    """Return the updated slots in the caller's convention: None in =
    None out, single tree in = single tree out, tuple in = tuple out."""
    if like is None:
        return None
    if isinstance(like, tuple):
        return new_slots
    return new_slots[0]


def make_population_step(act: str = "sigmoid", *, engine: str = "auto",
                         fused: bool = True, jit: bool = True,
                         donate: bool = True, with_health: bool = False,
                         traces: dict | None = None):
    """step(params, slots, hyp, mask, x, t) -> (params, slots, losses[E])
    — or (params, slots, losses, health[E]) with ``with_health``.

    One call trains ALL E members on the shared batch (x [M, n_in],
    t [M, n_out] one-hot): objective sum(mask * member_losses).  On the
    pallas engine with ``fused`` the junction custom_vjp applies each
    member's update in the backward kernels against its own hyp row (dw
    never in HBM); otherwise the two-pass reference materializes grads
    and applies the identical per-member formula here.  ``slots`` is the
    accumulator-slot convention of :func:`init_slots` — None/() = plain
    SGD end to end (no buffers allocated or streamed), a single tree =
    SGD momentum (back-compat), (mom, vel) = Adam — and comes back in
    the same convention.  hyp (legacy [E, 2] pair or [E, HYP_K] registry
    table) and mask [E] are traced operands — pruning a member (zero
    mask + zero hyp row) never recompiles.

    ``with_health`` adds the per-member divergence signal the scheduler's
    quarantine uses: health[e] > 0 ⇔ member e's update just went
    non-finite.  Fused path: the in-kernel [E] health flags (the grads
    never exist in HBM to inspect); two-pass path: a non-finite scan over
    the materialized per-member grads.  Member independence means a bad
    member flags ONLY its own slot.

    ``traces`` (a dict) counts the step's traces under ``"step"``: a
    traced-time side effect, like ``ContinuousEngine.decode_traces``."""
    engine = sl.resolve_engine(engine)
    use_fused = fused and engine == "pallas"

    def step(params, mom, hyp, mask, x, t):
        if traces is not None:
            traces["step"] = traces.get("step", 0) + 1
        slots = sl.normalize_slots(mom)
        if use_fused:
            aug = sl.inject_update_ctx(params, slots, hyp)

            def loss_fn(aug):
                y = population_forward(aug, x, act=act, engine=engine)
                losses = member_losses(y, t)
                return jnp.sum(losses * mask), losses

            grads, losses = jax.grad(loss_fn, has_aux=True,
                                     allow_int=True)(aug)
            new_params, new_slots = _merge_updated(grads, params, slots)
            new_mom = _repack_slots(new_slots, mom)
            if with_health:
                return new_params, new_mom, losses, _member_health_fused(grads)
            return new_params, new_mom, losses

        def loss_fn(params):
            y = population_forward(params, x, act=act, engine=engine)
            losses = member_losses(y, t)
            return jnp.sum(losses * mask), losses

        from repro.kernels import block_sparse_matmul as bsm
        hyp_k = bsm.normalize_hyp(hyp, population_size(params))
        grads, losses = jax.grad(loss_fn, has_aux=True, allow_int=True)(params)
        new_params, new_slots = _two_pass_update(params, slots, grads, hyp_k)
        new_mom = _repack_slots(new_slots, mom)
        if with_health:
            return new_params, new_mom, losses, _member_health_jnp(grads)
        return new_params, new_mom, losses

    if jit:
        return jax.jit(step, donate_argnums=(0, 1) if donate else ())
    return step


def make_population_eval(act: str = "sigmoid", *, engine: str = "auto",
                         jit: bool = True, traces: dict | None = None):
    """eval(params, x, t) -> per-member losses [E] (no update, no mask —
    the scheduler ranks live members and ignores pruned slots).
    ``traces`` counts its traces under ``"eval"``, as the step's."""
    engine = sl.resolve_engine(engine)

    def evaluate(params, x, t):
        if traces is not None:
            traces["eval"] = traces.get("eval", 0) + 1
        y = population_forward(params, x, act=act, engine=engine)
        return member_losses(y, t)

    return jax.jit(evaluate) if jit else evaluate
