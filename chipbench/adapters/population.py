"""The population MLP of ``search/`` (CandidateSpec, run_sweep) built from
a configuration file and a sweep traffic file.

``run_sweep`` makes its members' weights inside, from its own seed, and
the reference may take no weights the program made.  For the sweep that
the check compares, the benchmark makes them instead, from the run's
seed, in one jitted call per cohort, with the benchmark's block
patterns: ``stand_in_init`` replaces ``search.population.init_population``
for the length of that one ``run_sweep`` call, in set-up, and the
reference starts from the same weights.  The window's sweeps run the
program as it is, its own init included.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import patterns as pat_mod
from chipbench import work


def junction_shapes(conf, density) -> list[work.Junction]:
    ls = conf["layers"]
    return [work.junction(a, b, density, conf["block"])
            for a, b in zip(ls[:-1], ls[1:])]


def pattern(conf, density) -> list[dict]:
    ls = conf["layers"]
    return [pat_mod.block_pattern(a, b, density, conf["block"],
                                  conf["pattern_seed"] + i)
            for i, (a, b) in enumerate(zip(ls[:-1], ls[1:]))]


def specs(conf, traffic):
    from repro.search import CandidateSpec
    grid = [(d, lr) for d in traffic["densities"] for lr in traffic["lrs"]]
    return [CandidateSpec(lr=lr, momentum=traffic["momentum"], density=d,
                          layers=tuple(conf["layers"]), block=conf["block"],
                          act=conf["activation"], opt=traffic["optimizer"],
                          init_seed=i)
            for i, (d, lr) in enumerate(grid)]


def _std(p, block) -> float:
    fan_in = p["idx"].shape[1] * block
    fan_out = p["rev_ob"].shape[1] * block
    return float(np.sqrt(2.0 / (fan_in + fan_out)))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _member_blocks(shapes, stds, members, key):
    """Float32 weights of members ``members`` (init indices), stacked:
    per junction ([E, nob, kb, bs, bs], [E, n_out])."""
    out = []
    for j, ((nob, kb, bs), std) in enumerate(zip(shapes, stds)):
        ws = [jax.random.normal(jax.random.fold_in(
            jax.random.fold_in(key, m), j), (nob, kb, bs, bs), jnp.float32)
            * std for m in members]
        out.append((jnp.stack(ws), jnp.zeros((len(members), nob * bs),
                                             jnp.float32)))
    return out


def member_weights(conf, density, members, key):
    pats = pattern(conf, density)
    shapes = tuple((p["idx"].shape[0], p["idx"].shape[1], conf["block"])
                   for p in pats)
    stds = tuple(_std(p, conf["block"]) for p in pats)
    return pats, _member_blocks(shapes, stds, tuple(members), key)


def bench_weights(conf, key):
    """A stand-in for ``init_population(key, specs)``: the same layout
    (per junction: E-leading ``w`` and ``b``, shared pattern leaves),
    weights from the benchmark's ``key``."""
    def init_population(_program_key, cohort_specs):
        s0 = cohort_specs[0]
        pats, ws = member_weights(conf, s0.density,
                                  [s.init_seed for s in cohort_specs], key)
        return [{"w": w, "b": b, **{k: jnp.asarray(v) for k, v in p.items()}}
                for p, (w, b) in zip(pats, ws)]
    return init_population


@contextlib.contextmanager
def stand_in_init(conf, key):
    from repro.search import population as pop
    saved = pop.init_population
    pop.init_population = bench_weights(conf, key)
    try:
        yield
    finally:
        pop.init_population = saved


@functools.partial(jax.jit, static_argnums=(1, 2))
def teacher_data(key, n, widths):
    """Inputs N(0, 1) and the teacher's targets, float32."""
    kx, k1, k2 = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, widths[0]), jnp.float32)
    t1 = jax.random.normal(k1, (widths[0], widths[1]), jnp.float32) \
        / np.sqrt(widths[0])
    t2 = jax.random.normal(k2, (widths[1], widths[2]), jnp.float32) \
        / np.sqrt(widths[1])
    with jax.default_matmul_precision("highest"):
        t = jax.nn.sigmoid(jax.nn.sigmoid(x @ t1) @ t2)
    return x, t
