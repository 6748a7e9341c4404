"""Training cells (traffic ``"kind": "train"``).

Set-up builds one object, the program's compiled train step
(``train/steps.make_train_step``, jitted with params and optimizer state
donated) with its state, and drives it from the seed through the first
``check_steps`` steps, through the same call and feed as the window:
one ``{"tokens": ...}`` batch per step, every row different, the step
counter starting at ``optimizer.start_step``, and the loss fetched to
the host after each step as ``train/train_loop.run`` does.  Those steps
compile the step and give the numbers that the reference checks: each
step's loss, the first gradient as Adam got it (its first moment after
one step, over 1 - b1), and the change of the parameters after the
last check step.  The window then runs more steps of the same object
until ``--seconds`` have passed.

``train_loop.run`` itself is not the window's loop: it writes a full
checkpoint when it returns (5.7 GB of parameters and Adam state at this
size), which the benchmark's disk budget cannot take on every run.

The reference (the configuration's plain reference, float32, highest
matmul precision) runs after the window, on weights the benchmark made
from the same seed, over the same batches.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time

import numpy as np

from chipbench import bench, reftrain

FLOAT8 = "float8_e4m3fn"


def _adapter(conf):
    return bench.load_module(bench.HERE / "adapters" / f"{conf['adapter']}.py")


@dataclasses.dataclass
class Readings:
    losses: list
    grad_norms: dict
    grad_samples: dict
    change_norms: dict


class Session:
    """The program's train step for a cell, built once per process."""

    def __init__(self, cell: bench.Cell):
        bench.use_program_sources()
        import jax
        import jax.numpy as jnp
        from repro.optim import cosine_schedule, fused_adam
        from repro.train.steps import fused_update_eligible, make_train_step

        self.cell = cell
        self.conf, self.traffic = cell.config, cell.traffic
        self.adapter = _adapter(self.conf)
        self.pats = self.adapter.patterns(self.conf)
        tr, o = self.traffic, self.traffic["optimizer"]
        fused = tr["update"] == "fused"
        self.param_dtype = jnp.dtype(tr["param_dtype"])
        self.arch = self.adapter.arch_config(
            self.conf, param_dtype=tr["param_dtype"], fused_update=fused)
        self.opt = fused_adam(
            cosine_schedule(o["lr"], warmup=o["warmup"], total=o["total"]),
            b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], grad_clip=o["grad_clip"])
        ok, why = fused_update_eligible(self.arch, self.opt)
        if ok != fused:
            raise bench.HarnessError(
                f"traffic asks for update {tr['update']!r}, the program "
                f"resolves {'fused' if ok else 'two-pass'} ({why})")
        self.step = make_train_step(self.arch, self.opt)
        self.names = set(self.adapter.leaf_shapes(self.conf, self.pats))
        self.batch, self.seq = tr["batch"], tr["seq"]
        self.start = o["start_step"]
        conf, pats, ad, pd = self.conf, self.pats, self.adapter, self.param_dtype
        self.init = jax.jit(lambda key: ad.to_program(
            conf, pats, ad.flat_weights(conf, pats, key), pd))
        self.opt_init = jax.jit(self.opt.init)
        b1 = o["b1"]
        names = self.names
        index = ad.sample_index(ad.leaf_shapes(conf, pats))

        def first(flat):
            return ad.leaf_norms(flat), ad.leaf_samples(flat, index)
        self.first = jax.jit(first)
        self.grad_norms = jax.jit(lambda m: first(
            {k: v / (1 - b1) for k, v in ad.flatten(m, names).items()}))
        self.change_norms = jax.jit(lambda p, key: ad.change_norms(
            ad.flatten(p, names), ad.flatten(self.init(key), names)))

    def batches(self, seed):
        return self.adapter.TokenBatches(seed, self.batch, self.seq,
                                         self.conf["vocab_size"])

    # ------------------------------------------------------------ program
    def begin(self, seed):
        """Weights and optimizer state from the seed, then the check
        steps.  Returns (readings, live state for the window)."""
        key = bench.seed_key(seed)
        params = self.init(key)
        opt_state = self.opt_init(params)
        live = {"params": params, "opt_state": opt_state,
                "batches": self.batches(seed), "i": 0, "key": key}
        losses, first = [], None
        for _ in range(self.traffic["check_steps"]):
            losses.append(self.one(live))
            if first is None:
                first = self.grad_norms(live["opt_state"]["m"])
        cnorms = _host(self.change_norms(live["params"], key))
        return Readings(losses, _host(first[0]), _samples(first[1]),
                        cnorms), live

    def one(self, live) -> float:
        import jax.numpy as jnp
        with bench.span("batch"):
            tokens = jnp.asarray(live["batches"](live["i"]))
        with bench.span("step"):
            live["params"], live["opt_state"], metrics = self.step(
                live["params"], live["opt_state"], {"tokens": tokens},
                jnp.asarray(self.start + live["i"]))
        with bench.span("sync"):
            loss = float(metrics["loss"])
        live["i"] += 1
        return loss

    # ---------------------------------------------------------- reference
    def reference(self, seed, lowp=None, rows=None) -> Readings:
        """The plain reference over the check steps' batches (``rows``:
        keep only the first rows of each, a planted fault)."""
        import jax
        ref = bench.load_module(self.cell.reference_file)
        grad = ref.make_batch_grad(self.conf, self.pats, lowp)
        key = bench.seed_key(seed)
        conf, pats, ad, pd = self.conf, self.pats, self.adapter, self.param_dtype
        start = jax.jit(lambda k: {n: reftrain.store(v, pd) for n, v in
                                   ad.flat_weights(conf, pats, k).items()})
        batches = self.batches(seed)
        toks = [batches(i)[:rows] for i in range(self.traffic["check_steps"])]
        losses, first, params = reftrain.adam_steps(
            grad, start(key), toks, self.traffic["optimizer"], pd,
            self.start, self.first)
        cnorms = _host(jax.jit(ad.change_norms)(params, start(key)))
        return Readings(losses, _host(first[0]), _samples(first[1]), cnorms)


def _host(tree) -> dict:
    return {k: float(v) for k, v in tree.items()}


def _samples(tree) -> dict:
    return {k: np.asarray(v, np.float64) for k, v in tree.items()}


def sample_gaps(got: dict, want: dict) -> dict:
    """Per leaf: the norm of the difference of the two gradients at the
    sampled positions, against the reference's norm there or the median
    leaf's, whichever is larger."""
    import statistics
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    med = statistics.median(norms.values())
    return {k: float(np.linalg.norm(got[k] - want[k])) / max(norms[k], med,
                                                               1e-30)
            for k in want}


def compare(got: Readings, want: Readings) -> dict:
    """The numbers compared: the worst relative loss gap over the check
    steps; the worst leaf's gap of first-gradient norms and of update
    norms; and, since a norm averages away what a lower precision does
    to each element, the worst leaf's difference of the first gradients
    at a fixed sample of 4,096 positions per leaf (``grad_diff``).  Leaves whose reference gradient is under a thousandth
    of the median leaf's (rounding alone moves them) are left out of the
    update norms."""
    import statistics
    loss = max(bench.relative_gap(a, b)
               for a, b in zip(got.losses, want.losses))
    med = statistics.median(want.grad_norms.values())
    keep = {k for k, v in want.grad_norms.items() if v >= 1e-3 * med}
    gk, gv = bench.worst(bench.norm_gaps(got.grad_norms, want.grad_norms))
    dk, dv = bench.worst(sample_gaps(got.grad_samples, want.grad_samples))
    ck, cv = bench.worst(bench.norm_gaps(got.change_norms, want.change_norms,
                                         keep))
    return {"loss_gap": loss, "grad_norm_gap": gv, "grad_diff": dv,
            "update_norm_gap": cv, "worst_grad_leaf": gk,
            "worst_diff_leaf": dk, "worst_update_leaf": ck,
            "left_out": sorted(set(want.grad_norms) - keep)}


def run(ctx: bench.Context) -> bench.DriverResult:
    cell, tr = ctx.cell, ctx.cell.traffic
    sess = Session(cell)
    got, live = sess.begin(ctx.seed)
    compiles0 = ctx.meter.fresh
    setup_s = time.time() - ctx.process_start
    losses = []
    with bench.maybe_trace(ctx.trace, ctx.trace_dir):
        with bench.span("window"):
            t0 = time.perf_counter()
            while True:
                losses.append(sess.one(live))
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            window_s = time.perf_counter() - t0
    compiles = ctx.meter.fresh - compiles0
    peak = bench.memory_peak_bytes(ctx.devices)
    print(f"train: set-up {setup_s:.3f} s ({ctx.meter}), {len(losses)} "
          f"steps in "
          f"{window_s:.3f} s, {compiles} compiles in the window, memory "
          f"peak {peak}", file=sys.stderr, flush=True)
    del live
    gc.collect()
    want = sess.reference(ctx.seed)
    cmp = compare(got, want)
    print(f"train: program losses {got.losses}, reference {want.losses}; "
          f"worst gradient leaf {cmp['worst_grad_leaf']}, worst update "
          f"leaf {cmp['worst_update_leaf']}, left out {cmp['left_out']}",
          file=sys.stderr)
    lim = tr["limits"]
    checks = [bench.Check(k, float(cmp[k]), float(lim[k]))
              for k in ("loss_gap", "grad_norm_gap", "grad_diff",
                        "update_norm_gap")]
    checks.append(bench.Check("window_compiles", float(compiles), 0.0))
    steps = len(losses)
    tokens = steps * sess.batch * sess.seq
    shape = sess.adapter.shape(sess.conf)
    item = np.dtype(sess.arch.dtype).itemsize
    jflops, jbytes = shape.junction_train_work(sess.batch * sess.seq, item,
                                               item)
    counters = {
        "window_s": window_s, "steps": steps, "tokens": tokens,
        "model_flops": tokens * shape.train_flops_per_token(sess.seq),
        "junction_flops": steps * jflops, "junction_bytes": steps * jbytes,
    }
    return bench.DriverResult(
        end_to_end={"train_tokens_per_s": tokens / window_s,
                    "setup_s": setup_s},
        counters=counters, checks=checks, attempted=steps,
        failed=sum(not math.isfinite(x) for x in losses),
        memory_peak_bytes=peak)


def calibration(cell, seeds, control_seeds):
    """(kind, seed, thunk) for calibrate.py: the program against the
    reference on ``seeds``; on ``control_seeds`` the reference in the
    control's precision, and with half of each batch left out (the mean
    taken over the rest), against the reference."""
    sess = Session(cell)
    cache = {}

    def ref(seed):
        if seed not in cache:
            cache.clear()
            cache[seed] = sess.reference(seed)
        return cache[seed]

    def sound(seed):
        got, live = sess.begin(seed)
        del live
        gc.collect()
        return compare(got, ref(seed))

    def control(seed):
        return compare(sess.reference(seed, lowp=cell.traffic["control"]),
                       ref(seed))

    def half(seed):
        return compare(sess.reference(seed, rows=sess.batch // 2), ref(seed))

    for s in seeds:
        yield "sound", s, lambda s=s: sound(s)
    for s in control_seeds:
        yield "control", s, lambda s=s: control(s)
        yield "half_batch", s, lambda s=s: half(s)
