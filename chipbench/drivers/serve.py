"""Serving cells (traffic ``"kind": "serve"``): offline batch generation
through ``serve/engine.ContinuousEngine.serve``, called back to back on
the same batch of requests, all arriving at tick 0, greedy decoding.

Lengths are a fixed set (stratified quantiles of the stated lognormals,
clipped), the same for every seed; the seed draws the prompts' token
ids.  Set-up makes the weights from the seed, builds the engine and
serves a two-request warm-up that compiles the decode tick and the
prefill chunk.  The window then calls ``serve`` until ``--seconds`` have
passed; every call finishes.

The harness times the engine's own calls: the decode tick is wrapped so
that its completion time is taken on the host (the engine reads the
tick's tokens at once anyway), and an inter-token gap is the time
between two ticks in which a slot decoded twice in a row.  The gap from
a request's first token (sampled off its last prefill chunk) to its
first decode tick is not seen.

The check, once the window has closed and the engine is freed: a sample
of the last call's requests, drawn from the seed, with the longest
output in it, run through the plain reference over prompt and served
tokens: the widest gap by which a served token's logit lies below the
reference's best.
"""
from __future__ import annotations

import gc
import math
import statistics
import sys
import time

import numpy as np

from chipbench import bench, reftrain


def _adapter(conf):
    return bench.load_module(bench.HERE / "adapters" / f"{conf['adapter']}.py")


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the stratified quantiles (i + 0.5) / n of a lognormal
    with the stated median and sigma, clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([statistics.NormalDist().inv_cdf(float(x)) for x in q])
    out = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def request_sizes(tr: dict) -> list[tuple[int, int]]:
    """(prompt length, output length) of each request of a call: both
    sets stratified, paired by a fixed permutation (``pairing_seed``) so
    long prompts do not always come with long outputs."""
    n = tr["requests_per_call"]
    p, o = lengths(tr["prompt"], n), lengths(tr["output"], n)
    perm = np.random.default_rng(tr["pairing_seed"]).permutation(n)
    return [(int(p[i]), int(o[perm[i]])) for i in range(n)]


class Session:
    def __init__(self, cell: bench.Cell):
        bench.use_program_sources()
        import jax
        import jax.numpy as jnp
        self.cell = cell
        self.conf, self.tr = cell.config, cell.traffic
        self.ad = _adapter(self.conf)
        self.pats = self.ad.patterns(self.conf)
        self.dtype = jnp.dtype(self.tr["param_dtype"])
        self.arch = self.ad.arch_config(self.conf,
                                        param_dtype=self.tr["param_dtype"],
                                        fused_update=False)
        conf, pats, ad, pd = self.conf, self.pats, self.ad, self.dtype
        self.init = jax.jit(lambda key: ad.to_program(
            conf, pats, ad.flat_weights(conf, pats, key), pd))
        self.sizes = request_sizes(self.tr)
        self.max_seq = (self.tr["prompt"]["max"] + self.tr["output"]["max"])
        self.ticks = []          # (completion time, positions) per tick
        self._gaps = {}

    def requests(self, seed):
        from repro.serve.engine import Request
        rng = np.random.default_rng([int(seed) & (2**63 - 1), 11])
        V = self.conf["vocab_size"]
        return [Request(rid=i, prompt=rng.integers(0, V, p, dtype=np.int32),
                        max_new_tokens=o)
                for i, (p, o) in enumerate(self.sizes)]

    def engine(self, params):
        from repro.serve.engine import ContinuousEngine, ServeConfig
        tr = self.tr
        eng = ContinuousEngine(self.arch, params, ServeConfig(
            max_new_tokens=tr["output"]["max"], temperature=0.0,
            engine="pallas", slots=tr["slots"], page_size=tr["page_size"],
            prefill_chunk=tr["prefill_chunk"], max_seq=self.max_seq))
        tick, chunk = eng._tick, eng._prefill_chunk

        def timed_tick(params, pool, token, positions, page_table, key):
            with bench.span("decode_tick"):
                out = tick(params, pool, token, positions, page_table, key)
                out[0].block_until_ready()
            self.ticks.append((time.perf_counter(), np.asarray(positions)))
            return out

        def spanned_chunk(*args):
            with bench.span("prefill_chunk"):
                return chunk(*args)

        eng._tick, eng._prefill_chunk = timed_tick, spanned_chunk
        return eng

    def serve(self, eng, reqs):
        with bench.span("serve_call"):
            t0 = time.perf_counter()
            out = eng.serve(reqs)
            return out, time.perf_counter() - t0

    # ------------------------------------------------------------- counts
    def gaps_ms(self) -> list[float]:
        """Inter-token gaps from the recorded ticks: a slot that decoded
        in two ticks in a row (its position one further) waited the time
        between their completions."""
        out = []
        for (t0, p0), (t1, p1) in zip(self.ticks, self.ticks[1:]):
            cont = (p1 > 0) & (p0 > 0) & (p1 == p0 + 1)
            out.extend([1e3 * (t1 - t0)] * int(cont.sum()))
        return out

    def work(self, calls: int, decode_ticks: int, ticks) -> dict:
        """Least-time work of the window: prefill at peak FLOP/s (every
        prompt's projections, junctions and causal scores, one
        unembedding row), decode at peak bandwidth (the weights once per
        tick, each live slot's keys and values at its length)."""
        shape = self.ad.shape(self.conf)
        item = self.dtype.itemsize
        d, V, L = shape.d_model, shape.vocab, shape.layers
        per_tok = shape.proj_flops_per_token() + shape.ffn_flops_per_token()
        prefill = sum(L * (p * per_tok + shape.causal_score_flops(p))
                      + 2.0 * d * V for p, _ in self.sizes)
        weights = (d * V                                  # unembedding
                   + L * (shape.proj_flops_per_token() / 2
                          + sum(j.weights for j in shape.ffn)))
        kv_row = 2 * L * shape.kv_heads * shape.head_dim * 2   # bf16 k, v
        kv_tokens = sum(int((p[p > 0] + 1).sum()) for _, p in ticks)
        return {"prefill_flops": calls * prefill,
                "decode_bytes": decode_ticks * weights * item
                + kv_tokens * kv_row,
                "kv_bytes": kv_tokens * kv_row}

    # ---------------------------------------------------------- reference
    def reference(self, seed, sample, lowp=None) -> float:
        import jax
        if lowp not in self._gaps:
            ref = bench.load_module(self.cell.reference_file)
            self._gaps[lowp] = ref.make_served_gaps(
                self.conf, self.pats, self.max_seq, self.tr["output"]["max"],
                lowp)
        gaps = self._gaps[lowp]
        conf, pats, ad, pd = self.conf, self.pats, self.ad, self.dtype
        params = jax.jit(lambda k: {n: reftrain.store(v, pd) for n, v in
                                    ad.flat_weights(conf, pats, k).items()})(
            bench.seed_key(seed))
        worst = 0.0
        for prompt, served in sample:
            g = gaps(params, prompt, served)
            worst = max(worst, float(np.max(g)) if np.all(np.isfinite(g))
                        else math.inf)
        return worst


def check_sample(seed, reqs, outputs, k: int):
    """k requests drawn from the seed, with the longest output among
    them: (prompt, served tokens) pairs."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 13])
    longest = max(reqs, key=lambda r: r.max_new_tokens).rid
    pick = [longest] + [int(i) for i in rng.choice(
        [r.rid for r in reqs if r.rid != longest], k - 1, replace=False)]
    by_id = {r.rid: r for r in reqs}
    return [(by_id[i].prompt, np.asarray(outputs[i])) for i in pick]


def run(ctx: bench.Context) -> bench.DriverResult:
    sess = Session(ctx.cell)
    tr = ctx.cell.traffic
    params = sess.init(bench.seed_key(ctx.seed))
    eng = sess.engine(params)
    reqs = sess.requests(ctx.seed)
    warm = [type(reqs[0])(rid=i, prompt=reqs[i].prompt[:tr["prefill_chunk"]
                                                        + 1],
                          max_new_tokens=2) for i in range(2)]
    sess.serve(eng, warm)
    compiles0 = ctx.meter.fresh
    setup_s = time.time() - ctx.process_start
    sess.ticks.clear()
    calls = tokens = decode_ticks = chunks = peak_pages = 0
    missing = 0
    wall = 0.0
    with bench.maybe_trace(ctx.trace, ctx.trace_dir):
        with bench.span("window"):
            t0 = time.perf_counter()
            while True:
                outputs, dt = sess.serve(eng, reqs)
                calls += 1
                wall += dt
                tokens += sum(len(v) for v in outputs.values())
                missing += sum(
                    len(outputs.get(r.rid, ())) != r.max_new_tokens
                    for r in reqs)
                decode_ticks += eng.stats["decode_ticks"]
                chunks += eng.stats["prefill_chunks"]
                peak_pages = max(peak_pages, eng.stats["peak_pages"])
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            window_s = time.perf_counter() - t0
    compiles = ctx.meter.fresh - compiles0
    peak = bench.memory_peak_bytes(ctx.devices)
    gaps = sess.gaps_ms()
    counters = sess.work(calls, decode_ticks, sess.ticks)
    counters.update(window_s=window_s, calls=calls, tokens=tokens,
                    decode_ticks=decode_ticks, prefill_chunks=chunks,
                    peak_pages=peak_pages, pool_pages=eng.stats["num_pages"])
    print(f"serve: set-up {setup_s:.3f} s ({ctx.meter}), {calls} calls, "
          f"{tokens} tokens "
          f"in {wall:.3f} s, {decode_ticks} ticks, {chunks} chunks, "
          f"{len(gaps)} gaps, {compiles} compiles in the window, memory "
          f"peak {peak}, KV pages in use at most {peak_pages} of "
          f"{eng.stats['num_pages']}", file=sys.stderr, flush=True)
    sample = check_sample(ctx.seed, reqs, outputs, tr["check_requests"])
    del eng, params, outputs
    gc.collect()
    gap = sess.reference(ctx.seed, sample)
    checks = [bench.Check("logit_gap", gap, float(tr["limits"]["logit_gap"])),
              bench.Check("requests_missing", float(missing), 0.0),
              bench.Check("window_compiles", float(compiles), 0.0)]
    return bench.DriverResult(
        end_to_end={"serve_tokens_per_s": tokens / wall,
                    "serve_itl_p95_ms": float(np.percentile(gaps, 95)),
                    "setup_s": setup_s},
        counters=counters, checks=checks, attempted=calls * len(reqs),
        failed=missing, memory_peak_bytes=peak)


def calibration(cell, seeds, control_seeds):
    """Sound readings: the served tokens of one call against the
    reference; control: the reference's float8 choice at the same
    positions (and a served token altered, a planted fault)."""
    sess = Session(cell)
    tr = cell.traffic
    state = {}

    def served(seed):
        if state.get("seed") != seed:
            state.clear()
            params = sess.init(bench.seed_key(seed))
            eng = sess.engine(params)
            reqs = sess.requests(seed)
            outputs, _ = sess.serve(eng, reqs)
            state.update(seed=seed, sample=check_sample(
                seed, reqs, outputs, tr["check_requests"]))
            del eng, params
            gc.collect()
        return state["sample"]

    def sound(seed):
        return {"logit_gap": sess.reference(seed, served(seed))}

    def control(seed):
        return {"logit_gap": sess.reference(seed, served(seed),
                                            lowp=tr["control"])}

    def altered(seed):
        sample = [(p, np.concatenate([(s[:1] + 1) % sess.conf["vocab_size"],
                                      s[1:]])) for p, s in served(seed)]
        return {"logit_gap": sess.reference(seed, sample)}

    for s in seeds:
        yield "sound", s, lambda s=s: sound(s)
    for s in control_seeds:
        yield "control", s, lambda s=s: control(s)
        yield "token_altered", s, lambda s=s: altered(s)
