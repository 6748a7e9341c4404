"""Serving engines: static step-locked batch decode and the
continuous-batching engine over a block-paged KV cache.

Two engines share the ServeConfig surface:

``Engine`` — the static-batch baseline: one prefill, then every slot
decodes in lockstep until the longest request finishes, with finished
slots burning compute into a masked scratch position.  Fixed shapes, no
recompiles — the right kernel pattern but the wrong scheduler for heavy
traffic (a batch is as slow as its longest member).

``ContinuousEngine`` — the production scheduler (PR 9).  Requests carry
their own prompt/max_new/arrival; an admission loop refills finished
slots from the queue mid-flight (the per-slot liveness masks from the
guard/EOS machinery become the free-slot signal), long prompts prefill
in fixed-size chunks interleaved with decode ticks, and the KV cache is
a block-paged pool (models/model.make_paged_cache) where a slot refill
is a page-table swap, never a cache copy.  Scheduler invariants:

* every jitted step has ONE shape: the decode tick is always
  (token [B,1], positions [B], page_table [B,maxp]) and the prefill
  chunk always [1, C] — admission, refill, and completion change only
  the integers riding scalar prefetch, so each step compiles exactly
  once (``decode_traces`` / ``prefill_traces`` count retraces);
* page accounting is all-or-nothing at admission (serve/paged.PagePool):
  a request is admitted only when its whole worst-case page span is
  free, so no mid-flight exhaustion and no preemption;
* pool page 0 is the scratch page — free and still-prefilling slots are
  pointed at it during a decode tick, so their masked garbage writes
  never touch live pages;
* decode attends through kernels/flash_attention.flash_decode under
  engine="pallas" (page table on scalar prefetch, a group of pages per
  grid step, double-buffered per-page HBM→VMEM DMA — the junction
  engine's prefetch+DMA idiom applied to attention) and the
  gather+masked-softmax reference on jnp.

Sampling is greedy or by temperature (one fold_in subkey per tick); a
slot whose logits go non-finite is terminated and counted
(``nonfinite_terminated``), like the static engine's guard.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.flash_attention import decode_pages_per_step
from repro.models import model as M
from repro.obs import telemetry as obs
from repro.serve.paged import PagePool
from repro.train.steps import (make_decode_step, make_paged_prefill_step,
                               make_prefill_step)


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_token: int = -1     # -1: never stop early
    seed: int = 0
    # execution engine override for the sparse linears ("pallas"|"jnp"|
    # "auto"); None keeps the ArchConfig's setting.  The step builders
    # resolve "auto" to the Pallas engine on TPU backends.
    engine: str | None = None
    # quantize-at-load: "int8" converts every sparse junction's weights
    # to int8 codes + per-block scales (core/quantize.quantize_tree) the
    # moment the engine takes the params — decode then runs the int8
    # junction kernels; dense layers (attention, embeddings) stay fp.
    # None serves full precision.  "fxp" is refused here: the LUT bakes
    # ONE activation per junction at quantize time, which fits the paper
    # MLP / population path (launch/quant_sweep.py), not a transformer
    # FFN stack.
    quantize: str | None = None
    # divergence guard: a slot whose logits go non-finite (corrupted
    # weights, poisoned cache) is terminated — EOS-filled and masked out
    # like a finished sequence — instead of sampling garbage into the
    # batch (categorical over NaN logits returns arbitrary token ids and
    # argmax propagates index 0 silently).  Other slots are untouched.
    guard_nonfinite: bool = True
    # ---- continuous-batching knobs (ContinuousEngine only) ----
    slots: int = 4          # decode batch width (fixed tick shape)
    page_size: int = 16     # tokens per KV page
    num_pages: int = 0      # pool budget; 0: full residency
                            # (slots * ceil(max_seq/page_size) + scratch)
    prefill_chunk: int = 32 # chunked-prefill width (fixed [1, C] shape)
    max_seq: int = 0        # per-request prompt+new cap; 0: cfg.max_seq


@dataclasses.dataclass
class Request:
    """One serving request.  ``arrival`` is in scheduler ticks (one tick
    per scheduler iteration): the request becomes admissible once the
    engine's tick counter reaches it."""
    rid: int
    prompt: np.ndarray          # [S] int32
    max_new_tokens: int
    arrival: int = 0


class Engine:
    def __init__(self, cfg: ArchConfig, params, serve_cfg: ServeConfig | None = None):
        self.scfg = serve_cfg or ServeConfig()
        if self.scfg.engine is not None:
            cfg = dataclasses.replace(cfg, engine=self.scfg.engine)
        self.cfg = cfg
        if self.scfg.quantize:
            if self.scfg.quantize != "int8":
                raise ValueError(
                    f"ServeConfig.quantize={self.scfg.quantize!r} — serving "
                    "supports 'int8' only (fxp bakes one LUT activation "
                    "per junction; see launch/quant_sweep.py for that "
                    "path)")
            from repro.core import quantize as qz
            params = qz.quantize_tree(params, qz.QuantConfig(mode="int8"))
        self.params = params
        self._prefill = jax.jit(make_prefill_step(cfg))
        self._decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
        # slots terminated by the non-finite-logit guard in the LAST
        # generate() call (host int, refreshed per call)
        self.nonfinite_terminated = 0

    def _sample(self, logits, key):
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)

    @staticmethod
    def _guard(logits2d):
        """(bad [B] bool, sanitized logits): a slot with ANY non-finite
        logit is flagged and its row zeroed so sampling stays defined."""
        bad = jnp.any(~jnp.isfinite(logits2d), axis=-1)
        safe = jnp.where(bad[:, None], jnp.zeros_like(logits2d), logits2d)
        return bad, safe

    def generate(self, prompts: np.ndarray, extra_inputs: dict | None = None):
        """prompts [B, S_prompt] int32 (right-aligned, padded with 0).
        Returns tokens [B, max_new_tokens]."""
        # refreshed-per-call contract: reset BEFORE the guard branch so a
        # guard-off engine never serves a stale count from a prior call
        self.nonfinite_terminated = 0
        B, S = prompts.shape
        total = S + self.scfg.max_new_tokens
        batch = {"tokens": jnp.asarray(prompts)}
        if extra_inputs:
            batch.update({k: jnp.asarray(v) for k, v in extra_inputs.items()})
        logits, cache = self._prefill(self.params, batch)
        # re-home the prefill cache into a decode-capacity cache
        cache = self._grow_cache(cache, B, total, S)
        # split before the first sample: the root key is only ever split,
        # never consumed (sampling the first token with `key` and then
        # splitting the same `key` reused it — correlated samples)
        key, sub = jax.random.split(jax.random.PRNGKey(self.scfg.seed))
        guard = self.scfg.guard_nonfinite
        # terminated slots are filled with eos (or 0 when eos is unset —
        # the guard must still be able to mask a slot out)
        fill = self.scfg.eos_token if self.scfg.eos_token >= 0 else 0
        nf_slots = jnp.zeros((B,), bool)
        step_logits = logits[:, -1]
        if guard:
            bad, step_logits = self._guard(step_logits)
            nf_slots = nf_slots | bad
        tok = self._sample(step_logits, sub)[:, None]
        if guard:
            tok = jnp.where(nf_slots[:, None], fill, tok)
        out = [tok]
        done = nf_slots if guard else jnp.zeros((B,), bool)
        for i in range(self.scfg.max_new_tokens - 1):
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, cache, tok,
                                         jnp.asarray(S + i, jnp.int32))
            step_logits = logits[:, -1]
            if guard:
                bad, step_logits = self._guard(step_logits)
                nf_slots = nf_slots | bad
                done = done | bad
            nxt = self._sample(step_logits, sub)[:, None]
            if self.scfg.eos_token >= 0:
                done = done | (tok[:, 0] == self.scfg.eos_token)
            if self.scfg.eos_token >= 0 or guard:
                nxt = jnp.where(done[:, None], fill, nxt)
            tok = nxt
            out.append(tok)
        if guard:
            self.nonfinite_terminated = int(np.asarray(nf_slots).sum())
        return np.asarray(jnp.concatenate(out, axis=1))

    def _grow_cache(self, cache, B, total, S):
        """Copy the prefill cache (seq length S) into a total-capacity one.

        Placement is driven by ``M.cache_seq_axes`` metadata: leaves with a
        seq axis are written at position 0 of that axis, same-shape state
        leaves (conv/ssm state, cross-attn KV) are copied wholesale.  (The
        previous shape-coincidence heuristic guessed axis 2 whenever
        ndim >= 3 and the leading dims matched.)"""
        full = M.make_cache(self.cfg, B, total)
        axes = M.cache_seq_axes(self.cfg)

        def place(ax, dst, src):
            src = src.astype(dst.dtype)
            if ax < 0:  # same-shape state leaf
                assert dst.shape == src.shape, (dst.shape, src.shape)
                return src
            if src.shape[ax] > dst.shape[ax]:  # sliding window: keep tail
                src = jax.lax.slice_in_dim(
                    src, src.shape[ax] - dst.shape[ax], src.shape[ax], axis=ax)
            return jax.lax.dynamic_update_slice_in_dim(dst, src, 0, ax)

        return jax.tree.map(place, axes, full, cache)


# =============================================================== continuous
_FREE, _PREFILL, _DECODE = 0, 1, 2


class _Slot:
    __slots__ = ("state", "req", "pages", "cache_len", "prefill_pos", "out",
                 "last_tok", "t_admit", "t_wall", "t_queue", "t_first",
                 "t_last", "first_tick", "chunks")

    def __init__(self):
        self.state = _FREE
        self.req: Request | None = None
        self.pages: list[int] = []
        self.cache_len = 0        # tokens written to the paged cache
        self.prefill_pos = 0      # prompt tokens prefilled so far
        self.out: list[int] = []
        self.last_tok = 0         # sampled, not yet fed through decode
        self.t_admit = 0
        self.t_wall = 0.0
        self.t_queue = 0.0        # arrival -> admission wall time
        # span bookkeeping (obs.RequestSpan): first-token wall time /
        # tick, previous-token wall time (inter-token latency), and the
        # number of fixed-shape prefill chunks this request consumed
        self.t_first = -1.0
        self.t_last = -1.0
        self.first_tick = -1
        self.chunks = 0


class ContinuousEngine:
    """Continuous-batching serve engine over the block-paged KV cache.

    ``serve(requests)`` drives the admission/prefill/decode loop until
    every request completes; returns {rid: np.ndarray of generated
    tokens} (variable length: a slot frees the moment its request hits
    EOS or its own max_new — that freed capacity is the throughput win
    over the static engine).  ``stats`` carries per-request latencies
    and the page accounting afterwards.

    ``stats["decode_slot_ticks"]`` sums the decoding slots over the
    decode ticks (occupancy is that over ``slots * decode_ticks``) and
    ``stats["peak_pages"]`` is the page high-water mark.
    ``stats["decode_live_groups"]`` sums, over the decode ticks and the
    decoding slots, the page groups ``flash_decode`` reads (a slot of n
    tokens reads ceil(n / (G*ps)), G from ``decode_pages_per_step``);
    ``stats["decode_grid_groups"]`` sums the groups its grid walks
    (slots * ceil(maxp / G) a tick): the first over the second is the
    share of the decode grid that does work.

    Each scheduler iteration runs inside the ``obs.span``s
    ``repro.serve.{admit,prefill,decode}`` (``fetch`` nested in the last
    two; obs/telemetry.py, "Spans"), so a profiler trace puts every gap
    between two device ticks down to the host work in it.  With a
    ``recorder`` (obs.Recorder) attached, every finished request emits
    one ``obs.RequestSpan`` reconstructing its whole lifecycle (enqueue
    → admit → prefill chunks → first token → finish, with the outcome
    eos | max_new | guard, and its queue wait), TTFT, inter-token
    latencies and span durations land in histograms.  All of it rides
    values the scheduler already pulled to host (the sampled token, the
    guard flag) — no extra syncs, no traced ops, and the
    ``decode_traces == 1`` / ``prefill_traces == 1`` compile-once
    contract holds with telemetry on (regression-tested)."""

    def __init__(self, cfg: ArchConfig, params,
                 serve_cfg: ServeConfig | None = None,
                 recorder: "obs.Recorder | None" = None):
        self.rec = recorder
        self.scfg = serve_cfg or ServeConfig()
        if self.scfg.engine is not None:
            cfg = dataclasses.replace(cfg, engine=self.scfg.engine)
        ok, why = M.paged_supported(cfg)
        if not ok:
            raise ValueError(f"ContinuousEngine: {why}")
        if self.scfg.quantize:
            if self.scfg.quantize != "int8":
                raise ValueError("ContinuousEngine supports quantize='int8' "
                                 "only (same contract as Engine)")
            from repro.core import quantize as qz
            params = qz.quantize_tree(params, qz.QuantConfig(mode="int8"))
        self.cfg = cfg
        self.params = params
        self.max_seq = self.scfg.max_seq or cfg.max_seq
        ps = self.scfg.page_size
        self.pages_per_slot = -(-self.max_seq // ps)
        # retrace counters: the fixed-shape contract says each stays 1
        # across an entire serve() run (asserted by tests and CI)
        self.decode_traces = 0
        self.prefill_traces = 0
        self.nonfinite_terminated = 0
        self.stats: dict = {}

        decode_fn = make_decode_step(cfg, paged=True)
        prefill_fn = make_paged_prefill_step(cfg)
        greedy = self.scfg.temperature <= 0.0
        temp = self.scfg.temperature

        def tick(params, pool, token, positions, page_table, key):
            self.decode_traces += 1     # traced-time side effect
            logits, pool = decode_fn(params, pool, token, positions,
                                     page_table)
            lg = logits[:, -1].astype(jnp.float32)
            bad = jnp.any(~jnp.isfinite(lg), axis=-1)
            lg = jnp.where(bad[:, None], jnp.zeros_like(lg), lg)
            if greedy:
                tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            else:
                tok = jax.random.categorical(key, lg / temp,
                                             axis=-1).astype(jnp.int32)
            return tok, bad, pool

        def prefill_chunk(params, pool, tokens, base, ptrow, chunk_len):
            self.prefill_traces += 1    # traced-time side effect
            logits, pool = prefill_fn(params, pool, tokens, base, ptrow,
                                      chunk_len)
            return logits[:, -1].astype(jnp.float32), pool

        self._tick = jax.jit(tick, donate_argnums=(1,))
        self._prefill_chunk = jax.jit(prefill_chunk, donate_argnums=(1,))

    # ---------------------------------------------------------- sampling
    def _sample_host(self, logits_row: np.ndarray, key) -> int:
        if self.scfg.temperature <= 0.0:
            return int(np.argmax(logits_row))
        draw = jax.random.categorical(
            key, jnp.asarray(logits_row) / self.scfg.temperature, axis=-1)
        return int(draw)

    # ---------------------------------------------------------- scheduler
    def serve(self, requests: list[Request]) -> dict[int, np.ndarray]:
        scfg = self.scfg
        B, ps = scfg.slots, scfg.page_size
        maxp = self.pages_per_slot
        num_pages = scfg.num_pages or (B * maxp + 1)
        rec = self.rec
        t_serve0 = time.perf_counter()
        for r in requests:
            need = len(r.prompt) + r.max_new_tokens
            if need > self.max_seq:
                raise ValueError(
                    f"request {r.rid}: prompt+max_new = {need} exceeds "
                    f"max_seq {self.max_seq}")
            if -(-need // ps) > num_pages - 1:
                raise ValueError(
                    f"request {r.rid} needs more pages than the pool holds")
        with obs.span("serve.setup", rec):
            pool_acct = PagePool(num_pages, ps)
            pool = M.make_paged_cache(self.cfg, num_pages, ps)
            leaf = jax.tree.leaves(pool)[0]
            G = decode_pages_per_step(ps, leaf.shape[-1],
                                      leaf.dtype.itemsize, maxp)
            rows, grid_groups = G * ps, B * -(-maxp // G)
            slots = [_Slot() for _ in range(B)]
            # FIFO within arrival order (stable sort keeps submission order)
            queue = collections.deque(sorted(requests,
                                             key=lambda r: r.arrival))
            arrivals = list(queue)      # the tick stamps these in order
            root = jax.random.PRNGKey(scfg.seed)
        self.nonfinite_terminated = 0
        eos = scfg.eos_token
        guard = scfg.guard_nonfinite
        outputs: dict[int, np.ndarray] = {}
        lat: dict[int, dict] = {}
        t_arrive: dict[int, float] = {}   # rid -> wall time the tick reached it
        n_arrived = 0
        tick = 0
        decode_ticks = prefill_chunks = decode_slot_ticks = 0
        live_groups = all_groups = 0
        pf_cursor = 0               # round-robin over prefilling slots
        t_iter = t_serve0           # start of this scheduler iteration

        def finish(s: _Slot, outcome: str):
            r = s.req
            outputs[r.rid] = np.asarray(s.out, np.int32)
            ttft = s.t_first - s.t_wall if s.t_first >= 0 else -1.0
            lat[r.rid] = {"arrival": r.arrival, "admitted": s.t_admit,
                          "finished": tick, "outcome": outcome,
                          "ttft_s": ttft, "first_token_tick": s.first_tick,
                          "prefill_chunks": s.chunks,
                          "n_tokens": len(s.out),
                          "wall_s": time.perf_counter() - s.t_wall,
                          "queue_s": s.t_queue}
            if rec is not None:
                rec.count(f"serve.finish.{outcome}")
                if ttft >= 0:
                    rec.observe("serve.ttft_s", ttft)
                rec.emit(obs.RequestSpan(
                    rid=r.rid, outcome=outcome, enqueue_tick=r.arrival,
                    admit_tick=s.t_admit, first_token_tick=s.first_tick,
                    finish_tick=tick, prefill_chunks=s.chunks,
                    n_tokens=len(s.out), ttft_s=ttft,
                    wall_s=lat[r.rid]["wall_s"], queue_s=s.t_queue))
            pool_acct.release(s.pages)
            s.__init__()            # back to FREE

        def step_done(s: _Slot, tok: int) -> str | None:
            """Record one sampled token; the outcome string ("eos" |
            "max_new") when the request completed, else None."""
            now = time.perf_counter()
            if not s.out:           # first token of the request
                s.t_first = now
                s.first_tick = tick
            elif rec is not None and s.t_last >= 0:
                rec.observe("serve.itl_s", now - s.t_last)
            s.t_last = now
            s.out.append(tok)
            s.last_tok = tok
            if eos >= 0 and tok == eos:
                return "eos"
            return "max_new" if len(s.out) >= s.req.max_new_tokens else None

        while queue or any(s.state != _FREE for s in slots):
            # ---- admission: refill free slots from the arrival queue
            with obs.span("serve.admit", rec):
                while (n_arrived < len(arrivals)
                       and arrivals[n_arrived].arrival <= tick):
                    t_arrive[arrivals[n_arrived].rid] = t_iter
                    n_arrived += 1
                for s in slots:
                    if s.state != _FREE or not queue:
                        continue
                    if queue[0].arrival > tick:
                        break
                    need = pool_acct.pages_for(
                        len(queue[0].prompt) + queue[0].max_new_tokens)
                    pages = pool_acct.alloc(need)
                    if pages is None:
                        break       # pool full: stays queued, retry next tick
                    r = queue.popleft()
                    s.state = _PREFILL
                    s.req = r
                    s.pages = pages
                    s.cache_len = 0
                    s.prefill_pos = 0
                    s.out = []
                    s.t_admit = tick
                    s.t_wall = time.perf_counter()
                    s.t_queue = s.t_wall - t_arrive[r.rid]

            # ---- one prefill chunk (round-robin), interleaved with decode
            pf_slots = [i for i, s in enumerate(slots) if s.state == _PREFILL]
            if pf_slots:
                with obs.span("serve.prefill", rec):
                    i = pf_slots[pf_cursor % len(pf_slots)]
                    pf_cursor += 1
                    s = slots[i]
                    prompt = s.req.prompt
                    C = scfg.prefill_chunk
                    cl = min(C, len(prompt) - s.prefill_pos)
                    buf = np.zeros((1, C), np.int32)
                    buf[0, :cl] = prompt[s.prefill_pos:s.prefill_pos + cl]
                    ptrow = self._page_row(s, maxp)
                    last_logits, pool = self._prefill_chunk(
                        self.params, pool, jnp.asarray(buf),
                        jnp.asarray(s.prefill_pos, jnp.int32),
                        jnp.asarray(ptrow), jnp.asarray(cl, jnp.int32))
                    prefill_chunks += 1
                    s.chunks += 1
                    s.prefill_pos += cl
                    s.cache_len = s.prefill_pos
                    if s.prefill_pos == len(prompt):
                        with obs.span("serve.fetch", rec):
                            row = np.asarray(last_logits)[0]
                        bad = not np.all(np.isfinite(row))
                        if guard and bad:
                            self.nonfinite_terminated += 1
                            s.out.append(eos if eos >= 0 else 0)
                            finish(s, "guard")
                        else:
                            key = jax.random.fold_in(root, 2 * tick)
                            oc = step_done(s, self._sample_host(row, key))
                            if oc:
                                finish(s, oc)
                            else:
                                s.state = _DECODE

            # ---- decode tick: ONE fixed-shape call for the whole batch
            dec = [i for i, s in enumerate(slots) if s.state == _DECODE]
            if dec:
                with obs.span("serve.decode", rec):
                    tokens = np.zeros((B, 1), np.int32)
                    positions = np.zeros((B,), np.int32)
                    pt = np.zeros((B, maxp), np.int32)  # scratch page default
                    for i in dec:
                        s = slots[i]
                        tokens[i, 0] = s.last_tok
                        positions[i] = s.cache_len
                        pt[i] = self._page_row(s, maxp)
                    key = jax.random.fold_in(root, 2 * tick + 1)
                    tok, bad, pool = self._tick(
                        self.params, pool, jnp.asarray(tokens),
                        jnp.asarray(positions), jnp.asarray(pt), key)
                    decode_ticks += 1
                    decode_slot_ticks += len(dec)
                    # the kernel attends positions + 1 tokens a slot
                    live = int(np.sum(positions[dec] // rows + 1))
                    live_groups += live
                    all_groups += grid_groups
                    if rec is not None:
                        rec.count("serve.decode_live_groups", live)
                        rec.count("serve.decode_grid_groups", grid_groups)
                    with obs.span("serve.fetch", rec):
                        tok, bad = np.asarray(tok), np.asarray(bad)
                    for i in dec:
                        s = slots[i]
                        s.cache_len += 1
                        if guard and bad[i]:
                            self.nonfinite_terminated += 1
                            s.out.append(eos if eos >= 0 else 0)
                            finish(s, "guard")
                        else:
                            oc = step_done(s, int(tok[i]))
                            if oc:
                                finish(s, oc)
            elif not pf_slots and queue:
                # idle: jump the clock to the next arrival
                tick = max(tick, queue[0].arrival - 1)
            if rec is not None:
                rec.count("serve.ticks")
            tick += 1
            t_iter = time.perf_counter()

        self.stats = {
            "ticks": tick, "decode_ticks": decode_ticks,
            "prefill_chunks": prefill_chunks,
            "decode_slot_ticks": decode_slot_ticks,
            "decode_live_groups": live_groups,
            "decode_grid_groups": all_groups,
            "peak_pages": pool_acct.peak_in_use,
            "num_pages": num_pages, "page_size": ps,
            "wall_s": time.perf_counter() - t_serve0,
            "latency": lat,
            "decode_traces": self.decode_traces,
            "prefill_traces": self.prefill_traces,
        }
        return outputs

    @staticmethod
    def _page_row(s: _Slot, maxp: int) -> np.ndarray:
        row = np.zeros((maxp,), np.int32)       # sentinel: scratch page 0
        row[:len(s.pages)] = s.pages
        return row
