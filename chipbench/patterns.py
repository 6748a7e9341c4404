"""Block patterns the benchmark makes for the configurations it runs.

A junction of ``n_in`` by ``n_out`` features in blocks of ``block`` keeps
``kb`` input blocks for every output block (the configuration states the
density; ``work.block_fan_in`` turns it into ``kb``).  The benchmark
picks which ones from a fixed pattern seed: output block ``o`` reads the
``kb`` consecutive entries ``o*kb .. o*kb+kb-1`` (mod ``nib``) of a seeded
permutation of the input blocks, so no output block reads an input block
twice and every input block is read by as many output blocks as any
other, give or take one.  The reverse pattern (for the input gradient)
follows from it.  These arrays are structure, made here and handed to
both the program and the reference.
"""
from __future__ import annotations

import numpy as np

from chipbench.work import block_fan_in


def block_pattern(n_in: int, n_out: int, density: float, block: int,
                  seed: int) -> dict:
    nib, nob = n_in // block, n_out // block
    kb = block_fan_in(nib, density)
    perm = np.random.default_rng(seed).permutation(nib)
    flat = (np.arange(nob)[:, None] * kb + np.arange(kb)[None, :]) % nib
    idx = perm[flat].astype(np.int32)
    counts = np.bincount(idx.reshape(-1), minlength=nib)
    fb = int(counts.max())
    rev_ob = np.zeros((nib, fb), np.int32)
    rev_t = np.zeros((nib, fb), np.int32)
    fill = np.zeros(nib, np.int32)
    for o in range(nob):
        for t in range(kb):
            i = idx[o, t]
            rev_ob[i, fill[i]] = o
            rev_t[i, fill[i]] = t
            fill[i] += 1
    return {"idx": idx, "rev_ob": rev_ob, "rev_t": rev_t, "rev_cnt": fill}

