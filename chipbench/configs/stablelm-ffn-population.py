"""Plain reference of a successive-halving sweep over junction MLPs, in
float32 jax.numpy at the highest matmul precision.

A member is an MLP of pre-defined block-sparse junctions,
``y = sigmoid(junction(x) + b)`` after every junction, trained by plain
SGD (``p <- p - lr * dL/dp``) on the mean squared error over the batch
and the outputs.  A junction keeps, for each output block ``o``, the
input blocks ``idx[o, :]``.

The sweep (the semantics of ``search/scheduler.run_sweep``): every step
takes the minibatch of rows ``step*batch .. step*batch+batch-1`` (mod the
train rows), the same for every member; after each round every live
member's loss on the eval rows is its score (times the output width);
after every round but the last, all but the best ``ceil(live *
keep_fraction)`` members are pruned, and a pruned member never changes
again.

``sweep`` takes the program's pruned members for each round, scores
them against this reference's scores (``rank_gap``: how much worse,
relatively, the worst member the program kept scores than the best it
pruned; 0 or less when the program pruned exactly the worst ones) and
follows them, so that the remaining trajectories stay comparable member
by member.  A scheduler that keeps the worse members moves ``rank_gap``;
the lower precision of the control does not.

``lowp`` rounds both operands of every junction product to that float
type (one scale per tensor): the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _round(t, dtype):
    if dtype is None:
        return t
    if jnp.dtype(dtype) == jnp.bfloat16:
        q = jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    else:
        top = float(jnp.finfo(dtype).max)
        s = jax.lax.stop_gradient(jnp.max(jnp.abs(t)) / top + 1e-30)
        q = (t / s).astype(dtype).astype(jnp.float32) * s
    return t + jax.lax.stop_gradient(q - t)


def junction(x, w, idx, lowp):
    nob, kb, bs, _ = w.shape
    xb = x.reshape(x.shape[0], -1, bs)[:, idx]
    y = jnp.einsum("mokb,okbc->moc", _round(xb, lowp), _round(w, lowp))
    return y.reshape(x.shape[0], nob * bs)


def member_loss(params, idxs, x, t, lowp):
    for (w, b), idx in zip(params, idxs):
        x = jax.nn.sigmoid(junction(x, w, idx, lowp) + b)
    return jnp.mean(jnp.square(x - t))


def make_cohort_fns(idxs, lowp=None):
    """step(params, lr[E], live[E], x, t) -> (params, losses[E]) and
    evaluate(params, x, t) -> losses[E], vmapped over the members."""
    idxs = tuple(jnp.asarray(i) for i in idxs)
    vg = jax.vmap(jax.value_and_grad(
        lambda p, x, t: member_loss(p, idxs, x, t, lowp)),
        in_axes=(0, None, None))

    @jax.jit
    def step(params, lr, live, x, t):
        with jax.default_matmul_precision("highest"):
            losses, g = vg(params, x, t)
        scale = (lr * live).reshape(-1)

        def upd(p, d):
            return p - scale.reshape((-1,) + (1,) * (p.ndim - 1)) * d
        return jax.tree.map(upd, params, g), losses

    @jax.jit
    def evaluate(params, x, t):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda p: member_loss(p, idxs, x, t, lowp))(params)

    return step, evaluate


def sweep(cohorts, x_train, t_train, x_eval, t_eval, traffic, out_width,
          program_pruned, lowp=None, rows=None):
    """cohorts: [(member ids, lrs, params, idxs)], params a list of
    (w [E, ...], b [E, n_out]) per junction.  program_pruned: {round:
    set of member ids}.  Returns per member: step losses while live, eval
    losses, final params; and the worst rank_gap over the rounds."""
    R, S = traffic["rounds"], traffic["steps_per_round"]
    B, keep = traffic["batch"], traffic["keep_fraction"]
    n = x_train.shape[0]
    fns = [make_cohort_fns(c[3], lowp) for c in cohorts]
    params = [c[2] for c in cohorts]
    live = {m for c in cohorts for m in c[0]}
    losses = {m: [] for m in live}
    evals = {m: [] for m in live}
    rank_gap = -math.inf
    step_no = 0
    for r in range(R):
        for _ in range(S):
            start = (step_no * B) % n
            bi = jnp.asarray(np.arange(start, start + B) % n)
            xb, tb = x_train[bi][:rows], t_train[bi][:rows]
            for ci, (ids, lrs, _, _) in enumerate(cohorts):
                mask = jnp.asarray([float(m in live) for m in ids])
                if not float(mask.sum()):
                    continue
                params[ci], ls = fns[ci][0](params[ci], jnp.asarray(lrs),
                                            mask, xb, tb)
                for m, l in zip(ids, np.asarray(ls)):
                    if m in live:
                        losses[m].append(float(l))
            step_no += 1
        scores = {}
        for ci, (ids, _, _, _) in enumerate(cohorts):
            ev = np.asarray(fns[ci][1](params[ci], x_eval, t_eval))
            for m, l in zip(ids, ev):
                if m in live:
                    evals[m].append(float(l))
                    scores[m] = float(l) * out_width
        if r < R - 1 and len(scores) > 1:
            pruned = set(program_pruned.get(r, set()))
            n_keep = max(1, int(math.ceil(len(scores) * keep)))
            if pruned - set(scores) or len(scores) - len(pruned) != n_keep:
                rank_gap = math.inf
            else:
                kept = set(scores) - pruned
                worst_kept = max(scores[m] for m in kept)
                best_pruned = min(scores[m] for m in pruned)
                rank_gap = max(rank_gap, (worst_kept - best_pruned)
                               / max(abs(best_pruned), 1e-30))
            live -= pruned
    finals = {}
    for ci, (ids, _, _, _) in enumerate(cohorts):
        for e, m in enumerate(ids):
            finals[m] = [(w[e], b[e]) for w, b in params[ci]]
    return {"losses": losses, "evals": evals, "finals": finals,
            "rank_gap": max(rank_gap, 0.0)}
