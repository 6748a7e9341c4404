"""Continuous-batching serve engine (ISSUE 9): paged flash-decode kernel
vs reference, continuous-vs-static greedy parity, the compile-once
(fixed-shape) contract, page-pool accounting / memory-bounding, arrival
traces with EOS early-free, and the stale nonfinite_terminated
regression."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.sparsity import SparsityConfig
from repro.models import model as M
from repro.serve.engine import (ContinuousEngine, Engine, Request,
                                ServeConfig)
from repro.serve.paged import PagePool


def _cfg(engine="jnp", **kw):
    base = dict(
        name="cont-test", family="dense", n_layers=2, d_model=128,
        n_heads=4, kv_heads=2, head_dim=32, d_ff=256, vocab=128,
        act="silu", max_seq=64, attn_chunk=32, dtype="float32",
        sparsity=SparsityConfig(density=0.25, block=32, where="ffn"),
        engine=engine)
    base.update(kw)
    return ArchConfig(**base)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, size=(5, 12)).astype(np.int32)
    return cfg, params, prompts


# ------------------------------------------------------ flash_decode kernel
@pytest.mark.parametrize("lens", [
    [0, 1, 7, 8, 23, 24],       # ragged incl. zero-length and page edges
    [5, 16, 24],    # full-capacity slot (maxp * ps tokens exactly)
])
def test_flash_decode_matches_reference(lens):
    """Pallas paged-decode kernel vs the gather+masked-softmax reference
    on ragged per-slot lengths; a zero-length slot returns exact zeros."""
    from repro.kernels.flash_attention import flash_decode, paged_decode_ref
    B, Hkv, rep, D, ps = len(lens), 2, 2, 32, 8
    maxp = 3
    P = 1 + B * maxp
    ks = jax.random.split(jax.random.PRNGKey(len(lens)), 3)
    q = jax.random.normal(ks[0], (B, Hkv, rep, D), jnp.float32)
    k_pool = jax.random.normal(ks[1], (P, ps, Hkv * D), jnp.float32)
    v_pool = jax.random.normal(ks[2], (P, ps, Hkv * D), jnp.float32)
    pt = np.zeros((B, maxp), np.int32)
    nxt = 1
    for b, n in enumerate(lens):
        for j in range(-(-max(n, 1) // ps)):
            pt[b, j] = nxt
            nxt += 1
    pt = jnp.asarray(pt)
    sl = jnp.asarray(lens, jnp.int32)
    got = flash_decode(q, k_pool, v_pool, pt, sl, interpret=True)
    want = paged_decode_ref(q, k_pool, v_pool, pt, sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert not np.any(np.asarray(got)[np.asarray(sl) == 0])


@pytest.mark.parametrize("Hkv,rep", [(4, 1), (2, 3)], ids=["mha", "gqa"])
def test_flash_decode_matches_reference_hd80(Hkv, rep):
    """head_dim 80 (stablelm-3b): the merged [P, ps, Hkv*80] page layout
    keeps the kernel off 80-lane head slices, and it still matches the
    reference, MHA and GQA, bf16 pools included."""
    from repro.kernels.flash_attention import flash_decode, paged_decode_ref
    lens = [0, 3, 16, 21]
    B, D, ps, maxp = len(lens), 80, 8, 3
    P = 1 + B * maxp
    ks = jax.random.split(jax.random.PRNGKey(80), 3)
    q = jax.random.normal(ks[0], (B, Hkv, rep, D), jnp.float32)
    pools = [jax.random.normal(k, (P, ps, Hkv * D), jnp.float32)
             for k in ks[1:]]
    pt = jnp.asarray(1 + np.arange(B * maxp, dtype=np.int32).reshape(B, maxp))
    sl = jnp.asarray(lens, jnp.int32)
    for dt, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        qd, kp, vp = q.astype(dt), *(p.astype(dt) for p in pools)
        got = flash_decode(qd, kp, vp, pt, sl, interpret=True)
        want = paged_decode_ref(qd, kp, vp, pt, sl)
        assert got.shape == (B, Hkv, rep, D) and got.dtype == dt
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)
        assert not np.any(np.asarray(got, np.float32)[0])


# ------------------------------------------------ flash_decode page groups
def _tail_sentinel_table(lens, ps, maxp):
    """Page table giving each slot its own pages for its length, the
    rest of each row the sentinel page 0."""
    pt = np.zeros((len(lens), maxp), np.int32)
    nxt = 1
    for b, n in enumerate(lens):
        for j in range(-(-n // ps)):
            pt[b, j] = nxt
            nxt += 1
    return jnp.asarray(pt)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("Hkv,rep,D", [(4, 1, 80), (2, 4, 64)],
                         ids=["mha_hd80", "gqa"])
@pytest.mark.parametrize("G", [1, 2, 3, "maxp"])
def test_flash_decode_page_groups_match_reference(G, Hkv, rep, D, dtype):
    """G pages a grid step, maxp = 7 not a multiple of G = 2 or 3: lengths
    at every group edge match the reference.  The TPU interpreter fills
    unwritten VMEM with NaN and page 0 (every row's tail) is NaN too, so
    a page read past a slot's length, or a stale buffer row that reaches
    the accumulator, shows as a NaN."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.flash_attention import flash_decode, paged_decode_ref
    ps, maxp = 8, 7
    G = maxp if G == "maxp" else G
    lens = sorted({min(n, maxp * ps) for n in (
        0, 1, ps, G * ps - 1, G * ps, G * ps + 1, maxp * ps)})
    B = len(lens)
    P = 1 + sum(-(-n // ps) for n in lens)
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(G), 3)
    q = jax.random.normal(ks[0], (B, Hkv, rep, D)).astype(dt)
    k_pool, v_pool = (jax.random.normal(k, (P, ps, Hkv * D)).astype(dt)
                      for k in ks[1:])
    pt = _tail_sentinel_table(lens, ps, maxp)
    sl = jnp.asarray(lens, jnp.int32)
    want = paged_decode_ref(q, k_pool, v_pool, pt, sl)
    got = flash_decode(q, k_pool.at[0].set(jnp.nan),
                       v_pool.at[0].set(jnp.nan), pt, sl, pages_per_step=G,
                       interpret=pltpu.InterpretParams())
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert got.shape == q.shape and got.dtype == dt
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    assert not np.any(np.asarray(got, np.float32)[0])   # length 0


def test_decode_pages_per_step():
    """The group size from shapes alone: 8 pages of stablelm-3b's KV
    (16 x 32 heads x 80, bf16) a step, more for narrower rows, fewer for
    f32, never more than the page table nor fewer than one."""
    from repro.kernels.flash_attention import decode_pages_per_step
    assert decode_pages_per_step(16, 2560, 2, 160) == 8
    assert decode_pages_per_step(16, 1024, 2, 160) == 32
    assert decode_pages_per_step(16, 2560, 4, 160) == 4
    assert decode_pages_per_step(16, 2560, 2, 5) == 5
    assert decode_pages_per_step(16, 64, 4, 3) == 3
    assert decode_pages_per_step(1024, 8192, 4, 160) == 1


def test_decode_group_counters(setup, monkeypatch):
    """stats["decode_live_groups"] and ["decode_grid_groups"] (and the
    Recorder's counts of the same names) are the arithmetic of each
    tick's decoding slots: ceil((position + 1) / (G * ps)) a slot, and
    slots x ceil(maxp / G) a tick, here with G pinned to 2 through the
    byte budget the kernel and the engine share."""
    from repro.kernels import flash_attention as fa
    from repro.obs.telemetry import Recorder
    cfg, params, prompts = setup
    ps, max_seq = 8, 40                     # maxp 5: groups of 2 pages
    page = ps * cfg.kv_heads * cfg.head_dim * 4
    monkeypatch.setattr(fa, "DECODE_GROUP_BYTES", 2 * page)
    rec = Recorder()
    ce = ContinuousEngine(
        dataclasses.replace(cfg, engine="pallas"), params,
        ServeConfig(eos_token=-1, slots=2, page_size=ps, prefill_chunk=8,
                    max_seq=max_seq), recorder=rec)
    seen = []
    tick = ce._tick

    def spy(params, pool, token, positions, page_table, key):
        seen.append((np.asarray(positions), np.asarray(page_table)))
        return tick(params, pool, token, positions, page_table, key)

    ce._tick = spy
    ce.serve([Request(rid=i, prompt=prompts[i][: 3 + 4 * i],
                      max_new_tokens=6 + 5 * i) for i in range(4)])
    live = grid = 0
    for pos, pt in seen:
        dec = pt[:, 0] > 0                  # free slots read scratch page 0
        live += sum(-(-(int(p) + 1) // (2 * ps)) for p in pos[dec])
        grid += 2 * 3
    assert live > len(seen)                 # some slots span two groups
    st = ce.stats
    assert (st["decode_live_groups"], st["decode_grid_groups"]) == (live,
                                                                    grid)
    assert rec.counters["serve.decode_live_groups"] == live
    assert rec.counters["serve.decode_grid_groups"] == grid


# -------------------------------------------------------- engine semantics
@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_continuous_matches_static_greedy(setup, engine):
    """Token-identical greedy outputs per request vs the static engine —
    uniform prompt lengths (the static engine attends prompt padding, so
    ragged prompts aren't comparable), more requests than slots, through
    both the reference and the flash_decode paged attention."""
    cfg, params, prompts = setup
    NEW = 8
    static = Engine(cfg, params,
                    ServeConfig(max_new_tokens=NEW, eos_token=-1)
                    ).generate(prompts)
    ce = ContinuousEngine(
        dataclasses.replace(cfg, engine=engine), params,
        ServeConfig(max_new_tokens=NEW, eos_token=-1, slots=2, page_size=8,
                    prefill_chunk=8, max_seq=32))
    outs = ce.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=NEW)
                     for i in range(len(prompts))])
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs[i], static[i])


def test_decode_compiles_once(setup):
    """Slot refill and page-table swap change integers, never shapes: the
    decode tick and prefill chunk each trace exactly once per engine even
    across multiple serve() calls with different traces."""
    cfg, params, prompts = setup
    ce = ContinuousEngine(cfg, params, ServeConfig(
        max_new_tokens=6, eos_token=-1, slots=2, page_size=8,
        prefill_chunk=8, max_seq=32))
    ce.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=6)
              for i in range(4)])
    assert (ce.decode_traces, ce.prefill_traces) == (1, 1)
    # a second trace with different prompt lengths / arrivals / counts
    ce.serve([Request(rid=i, prompt=prompts[i][: 5 + i],
                      max_new_tokens=2 + i, arrival=i) for i in range(3)])
    assert (ce.decode_traces, ce.prefill_traces) == (1, 1)


def test_mixed_arrival_trace_completes(setup):
    """Staggered arrivals with mixed prompt/output lengths: every request
    completes with exactly its asked-for token count, and per-request
    latency stats cover every rid."""
    cfg, params, prompts = setup
    reqs = [Request(rid=i, prompt=prompts[i][: 4 + 2 * i],
                    max_new_tokens=3 + i, arrival=2 * i) for i in range(5)]
    ce = ContinuousEngine(cfg, params, ServeConfig(
        max_new_tokens=8, eos_token=-1, slots=2, page_size=8,
        prefill_chunk=8, max_seq=32))
    outs = ce.serve(reqs)
    assert set(outs) == set(range(5))
    assert [len(outs[i]) for i in range(5)] == [3 + i for i in range(5)]
    st = ce.stats
    assert set(st["latency"]) == set(range(5))
    assert all(st["latency"][r.rid]["admitted"] >= r.arrival for r in reqs)


def test_eos_frees_slot_early(setup):
    """A request hitting EOS ends there (eos is the last token, emitted
    once) and its slot is refilled — the run takes fewer decode ticks
    than the no-EOS run of the same trace."""
    cfg, params, prompts = setup
    NEW = 8
    base = Engine(cfg, params, ServeConfig(max_new_tokens=NEW, eos_token=-1)
                  ).generate(prompts)
    # pick a token greedy decode actually emits mid-stream
    eos = int(base[2][0])
    mk = lambda: [Request(rid=i, prompt=prompts[i], max_new_tokens=NEW)
                  for i in range(len(prompts))]
    scfg = dict(max_new_tokens=NEW, slots=2, page_size=8, prefill_chunk=8,
                max_seq=32)
    ce_free = ContinuousEngine(cfg, params,
                               ServeConfig(eos_token=eos, **scfg))
    outs = ce_free.serve(mk())
    ticks_eos = ce_free.stats["decode_ticks"]
    assert any(len(outs[i]) < NEW for i in outs)
    for o in outs.values():
        if eos in o:
            assert o[-1] == eos and eos not in o[:-1]
    ce_full = ContinuousEngine(cfg, params,
                               ServeConfig(eos_token=-1, **scfg))
    ce_full.serve(mk())
    assert ticks_eos < ce_full.stats["decode_ticks"]


# ------------------------------------------- paged layer scan vs per-layer
def _per_layer_scan(fn, x, layer_params, pool, page_table):
    """Reference for models/model._scan_layers_paged: each layer's pool is
    sliced out of the stacked pool, run with the un-offset page table, and
    written back whole."""
    L = jax.tree.leaves(pool)[0].shape[0]

    def body(carry, inp):
        x, pool = carry
        lp, i = inp
        ci = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            pool)
        x, nc, _ = fn(x, lp, ci, page_table)
        pool = jax.tree.map(
            lambda t, u: jax.lax.dynamic_update_index_in_dim(t, u, i, 0),
            pool, nc)
        return (x, pool), None

    (x, pool), _ = jax.lax.scan(body, (x, pool), (layer_params, jnp.arange(L)))
    return x, pool


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_paged_scan_matches_per_layer_reference(monkeypatch, engine, step):
    """The paged tick and prefill chunk address each layer's pages in the
    flat stacked pool (page ids offset by i*P): logits and the whole pool
    come out exactly as when each layer's pool is sliced out and written
    back.  Slot 1 points at the scratch page; slot 2 writes the first row
    of a page, and the chunk crosses a page boundary."""
    cfg = _cfg(engine=engine, n_layers=3)
    params = M.init(cfg, jax.random.PRNGKey(1))
    B, ps, maxp = 3, 8, 3
    P = 1 + B * maxp
    pool = M.make_paged_cache(cfg, P, ps)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    pool = {k: jax.random.normal(key, v.shape, v.dtype)
            for (k, v), key in zip(sorted(pool.items()), ks[:2])}
    pt = jnp.asarray([[1, 2, 3], [0, 0, 0], [7, 5, 9]], jnp.int32)
    if step == "decode":
        args = (jnp.asarray([[3], [4], [5]], jnp.int32),
                jnp.asarray([13, 0, 8], jnp.int32), pt)
        run = M.paged_decode_step
    else:
        tokens = jax.random.randint(ks[2], (1, 8), 1, cfg.vocab, jnp.int32)
        args = (tokens, jnp.int32(5), pt[2], jnp.int32(6))
        run = M.paged_prefill_chunk
    call = lambda: jax.jit(functools.partial(run, cfg))(params, pool, *args)
    got_logits, got_pool = call()
    monkeypatch.setattr(M, "_scan_layers_paged", _per_layer_scan)
    want_logits, want_pool = call()
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    for k in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_pool[k]),
                                      np.asarray(want_pool[k]))
        assert not np.array_equal(np.asarray(got_pool[k]),
                                  np.asarray(pool[k]))


# ------------------------------------------------------ page-pool accounting
def test_page_pool_accounting():
    pool = PagePool(num_pages=8, page_size=4)
    assert pool.free_pages == 7                 # page 0 reserved
    assert pool.pages_for(1) == 1 and pool.pages_for(9) == 3
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert pool.alloc(1) is None                # exhausted, not an error
    assert 0 not in a + b and len(set(a + b)) == 7
    assert (pool.in_use, pool.peak_in_use) == (7, 7)
    pool.release(a)
    assert pool.free_pages == 3 and pool.in_use == 4
    assert pool.peak_in_use == 7                # high-water mark sticks
    with pytest.raises(ValueError):
        PagePool(num_pages=1, page_size=4)


def test_peak_pages_track_tokens_not_slots(setup):
    """Memory-bound contract: short requests through a wide engine leave
    the peak page footprint at ceil(tokens/page) per live request, far
    under the slots x max-capacity worst case, and a pool sized to that
    peak still completes the trace (admission queues, never fails)."""
    cfg, params, prompts = setup
    scfg = ServeConfig(max_new_tokens=4, eos_token=-1, slots=4, page_size=8,
                       prefill_chunk=8, max_seq=32)
    reqs = [Request(rid=i, prompt=prompts[i][:8], max_new_tokens=4)
            for i in range(5)]
    ce = ContinuousEngine(cfg, params, scfg)
    ce.serve(list(reqs))
    # each live request spans ceil((8+4)/8)=2 pages; 4 slots -> peak 8,
    # while full residency would claim 4 slots x 4 pages = 16
    assert ce.stats["peak_pages"] <= 8
    assert ce.stats["peak_pages"] < scfg.slots * ce.pages_per_slot
    # rerun with the pool clamped to that peak (+scratch): admission must
    # queue on pool pressure and still finish everything
    tight = dataclasses.replace(scfg, num_pages=5)   # 2 live requests max
    ce2 = ContinuousEngine(cfg, params, tight)
    outs = ce2.serve(list(reqs))
    assert set(outs) == set(range(5))
    assert ce2.stats["peak_pages"] <= 4
    for i in range(5):
        np.testing.assert_array_equal(outs[i], ce.serve([reqs[i]])[i])


def test_admission_rejects_oversized_request(setup):
    cfg, params, prompts = setup
    ce = ContinuousEngine(cfg, params, ServeConfig(
        max_new_tokens=4, slots=2, page_size=8, max_seq=16))
    with pytest.raises(ValueError, match="exceeds"):
        ce.serve([Request(rid=0, prompt=prompts[0], max_new_tokens=8)])


def test_paged_refused_for_unsupported_families(setup):
    _, params, _ = setup
    cfg = _cfg(family="ssm", attn_kind="ssm")
    ok, why = M.paged_supported(cfg)
    assert not ok
    with pytest.raises(ValueError, match="static engine only"):
        ContinuousEngine(cfg, M.init(cfg, jax.random.PRNGKey(0)),
                         ServeConfig())


# --------------------------------------------------------------- regression
def test_nonfinite_counter_resets_per_call(setup):
    """Engine.generate() used to leave nonfinite_terminated stale when the
    guard was disabled — a prior guarded call's count survived into
    guard-off calls.  The counter is refreshed-per-call now."""
    cfg, params, prompts = setup
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=2, eos_token=-1,
                                          guard_nonfinite=False))
    eng.nonfinite_terminated = 7        # simulate a stale guarded call
    eng.generate(prompts[:2])
    assert eng.nonfinite_terminated == 0
