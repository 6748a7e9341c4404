"""Faults planted under the timed path, for the tests that see a run's
``correct`` come out false.  Each returns a replacement for the program
function it is given (a step factory, or the scheduler's score)."""
from __future__ import annotations


def train_state_unchanged(make_train_step):
    import jax

    def make(cfg, opt, *a, **kw):
        real = make_train_step(cfg, opt, jit=False)

        def step(p, o, b, s):
            return (p, o, real(p, o, b, s)[2])
        return jax.jit(step)
    return make


def train_half_batch(make_train_step):
    import jax

    def make(cfg, opt, *a, **kw):
        real = make_train_step(cfg, opt, jit=False)

        def step(p, o, b, s):
            half = b["tokens"].shape[0] // 2
            return real(p, o, {"tokens": b["tokens"][:half]}, s)
        return jax.jit(step, donate_argnums=(0, 1))
    return make


def _sweep(make_population_step, wrap):
    def make(act, **kw):
        kw["donate"] = False
        real = make_population_step(act, **kw)
        return lambda *args: wrap(real, *args)
    return make


def sweep_state_unchanged(make_population_step):
    def wrap(real, params, mom, hyp, mask, x, t):
        out = real(params, mom, hyp, mask, x, t)
        return (params, mom) + tuple(out[2:])
    return _sweep(make_population_step, wrap)


def sweep_half_batch(make_population_step):
    def wrap(real, params, mom, hyp, mask, x, t):
        half = x.shape[0] // 2
        return real(params, mom, hyp, mask, x[:half], t[:half])
    return _sweep(make_population_step, wrap)


def sweep_answer_altered(make_population_step):
    """One member's loss is reported 1% high where the step makes it."""
    def wrap(real, params, mom, hyp, mask, x, t):
        out = list(real(params, mom, hyp, mask, x, t))
        out[2] = out[2].at[0].multiply(1.01)
        return tuple(out)
    return _sweep(make_population_step, wrap)


def sweep_prune_inverted(score):
    """The scheduler ranks by the negated score: it keeps the worst
    members and prunes the best (a diverged member still ranks last)."""
    def inverted(loss, out_width):
        s = score(loss, out_width)
        return -s if s != float("inf") else s
    return inverted
