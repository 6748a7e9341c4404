"""Expert-layer training cells (traffic ``"kind": "moe_train"``).

The same set-up, window and check as ``drivers/train.py``, whose
``Session`` and ``compare`` this driver loads and uses unchanged: the
program's compiled train step (``train/steps.make_train_step``, fused
Adam) driven from the seed through the check steps, then the window,
then the configuration's plain reference over the check steps' batches.
Beside them it sums the step's expert-layer counters (``moe_routed_rows``,
``moe_computed_rows``, ``moe_dropped_rows``; the largest
``moe_max_expert_rows``) over the window, and checks ``dropped_rows``
(token-slots routed to a held expert that did not land, over the check
steps and the window) against its limit of 0.

Work for ``mfu.moe_train`` and the expert junctions' roofline is counted
by ``work_moe.py`` on the rows routed to the held experts, never on the
rows the kernels cover.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from chipbench import bench

train = bench.load_module(bench.HERE / "drivers" / "train.py")

STATS = ("moe_routed_rows", "moe_computed_rows", "moe_max_expert_rows",
         "moe_dropped_rows")


def _add(acc: dict, got: dict) -> None:
    for k, v in got.items():
        acc[k] = (max(acc.get(k, 0), v) if k == "moe_max_expert_rows"
                  else acc.get(k, 0) + v)


class Session(train.Session):
    """``train.Session`` with the step's expert-layer counters kept per
    step (fetched after the loss, as ``train_loop.run`` fetches them),
    and token ids drawn from the held vocabulary slice."""

    def batches(self, seed):
        return self.adapter.TokenBatches(seed, self.batch, self.seq,
                                         self.conf["vocab_held"])

    def one(self, live) -> float:
        import jax
        import jax.numpy as jnp
        with bench.span("batch"):
            tokens = jnp.asarray(live["batches"](live["i"]))
        with bench.span("step"):
            live["params"], live["opt_state"], metrics = self.step(
                live["params"], live["opt_state"], {"tokens": tokens},
                jnp.asarray(self.start + live["i"]))
        with bench.span("sync"):
            loss = float(metrics["loss"])
            got = jax.device_get({k: metrics[k] for k in STATS})
        live.setdefault("moe", []).append({k: int(v) for k, v in got.items()})
        live["i"] += 1
        return loss


def _window_counters(steps: list) -> dict:
    out: dict = {}
    for s in steps:
        _add(out, s)
    return out


def run(ctx: bench.Context) -> bench.DriverResult:
    cell, tr = ctx.cell, ctx.cell.traffic
    sess = Session(cell)
    got, live = sess.begin(ctx.seed)
    check_moe = list(live.get("moe", []))
    compiles0 = ctx.meter.fresh
    setup_s = time.time() - ctx.process_start
    losses = []
    live["moe"] = []
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
    moe = _window_counters(live["moe"])
    dropped = sum(s["moe_dropped_rows"] for s in check_moe + live["moe"])
    print(f"moe_train: set-up {setup_s:.3f} s ({ctx.meter}), {len(losses)} "
          f"steps in {window_s:.3f} s, {compiles} compiles in the window, "
          f"memory peak {peak}; window counters {moe}", file=sys.stderr,
          flush=True)
    del live
    gc.collect()
    want = sess.reference(ctx.seed)
    cmp = train.compare(got, want)
    print(f"moe_train: program losses {got.losses}, reference "
          f"{want.losses}; worst gradient leaf {cmp['worst_grad_leaf']}, "
          f"worst update leaf {cmp['worst_update_leaf']}, left out "
          f"{cmp['left_out']}", file=sys.stderr)
    lim = tr["limits"]
    checks = [bench.Check(k, float(cmp[k]), float(lim[k]))
              for k in ("loss_gap", "grad_norm_gap", "grad_diff",
                        "update_norm_gap")]
    checks.append(bench.Check("dropped_rows", float(dropped),
                              float(lim["dropped_rows"])))
    checks.append(bench.Check("window_compiles", float(compiles), 0.0))
    steps = len(losses)
    tokens = steps * sess.batch * sess.seq
    shape = sess.adapter.shape(sess.conf)
    item = np.dtype(sess.arch.dtype).itemsize
    routed = moe.get("moe_routed_rows", 0)
    eflops, ebytes = shape.expert_train_work(routed, item, item, steps)
    counters = {
        "window_s": window_s, "steps": steps, "tokens": tokens,
        "model_flops": (steps * shape.train_flops(sess.seq, sess.batch, 0)
                        + 3.0 * routed * shape.expert_flops_per_row()),
        "expert_flops": eflops, "expert_bytes": ebytes, **moe,
    }
    return bench.DriverResult(
        end_to_end={"train_tokens_per_s": tokens / window_s,
                    "setup_s": setup_s},
        counters=counters, checks=checks, attempted=steps,
        failed=sum(not math.isfinite(x) for x in losses),
        memory_peak_bytes=peak)


# ---------------------------------------------------------- routing reading
def program_routes(arch, params, tokens):
    """The program's top-k experts of every token at every expert layer
    [L, B*S, k], through its own layers (``models/``), on ``params``."""
    import jax
    from repro.models import attention as attn
    from repro.models import model as M
    from repro.models import moe as moe_mod
    from repro.models.layers import norm_apply
    x, positions, _ = M._embed_in(arch, params, {"tokens": tokens})
    for i in range(arch.moe.first_dense_layers):
        lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
        x, _, _ = M._attn_mlp_block(lp, x, arch, positions)

    def layer(x, lp):
        h = norm_apply(lp["norm1"], x, arch.norm, arch.norm_eps)
        a, _ = attn.mla_forward(lp["attn"], h, arch, positions=positions)
        x = x + a
        h = norm_apply(lp["norm2"], x, arch.norm, arch.norm_eps)
        _, _, e = moe_mod.route(lp["moe"], h.reshape(-1, h.shape[-1]), arch)
        y, _, _ = moe_mod.moe_apply(lp["moe"], h, arch)
        return x + y, e

    _, routes = jax.lax.scan(layer, x, params["layers"])
    return routes


def route_mismatch(sess, seed) -> float:
    """Share of (token, layer) top-k sets where the program's routing and
    the reference's differ, over the check steps' batches, both on the
    program's weights at the start of each step."""
    import jax
    import jax.numpy as jnp
    ref = bench.load_module(sess.cell.reference_file)
    ref_routes = ref.make_routes(sess.conf, sess.pats)
    prog = jax.jit(lambda p, t: program_routes(sess.arch, p, t))
    key = bench.seed_key(seed)
    params = sess.init(key)
    opt_state = sess.opt_init(params)
    batches = sess.batches(seed)
    names, ad = sess.names, sess.adapter
    differ = total = 0
    for i in range(sess.traffic["check_steps"]):
        toks = batches(i)
        got = np.sort(np.asarray(prog(params, jnp.asarray(toks))), -1)
        flat = {k: v.astype(jnp.float32)
                for k, v in ad.flatten(params, names).items()}
        for b in range(toks.shape[0]):
            want = np.sort(np.asarray(ref_routes(flat, jnp.asarray(toks[b]))),
                           -1)
            rows = got[:, b * toks.shape[1]:(b + 1) * toks.shape[1]]
            differ += int(np.any(rows != want, axis=-1).sum())
            total += want.shape[0] * want.shape[1]
        del flat
        params, opt_state, _ = sess.step(params, opt_state,
                                         {"tokens": jnp.asarray(toks)},
                                         jnp.asarray(sess.start + i))
    return differ / max(total, 1)


def calibration(cell, seeds, control_seeds):
    """(kind, seed, thunk) for calibrate.py, as ``train.calibration``
    gives them (the program on ``seeds``; the control and the half batch
    on ``control_seeds``), with the sound readings' routing mismatch
    share and dropped rows beside the compared numbers."""
    sess = Session(cell)
    cache = {}

    def ref(seed):
        if seed not in cache:
            cache.clear()
            cache[seed] = sess.reference(seed)
        return cache[seed]

    def sound(seed):
        got, live = sess.begin(seed)
        dropped = sum(s["moe_dropped_rows"] for s in live.get("moe", []))
        del live
        gc.collect()
        out = train.compare(got, ref(seed))
        out["dropped_rows"] = dropped
        out["route_mismatch"] = route_mismatch(sess, seed)
        return out

    def control(seed):
        return train.compare(sess.reference(seed, lowp=cell.traffic["control"]),
                             ref(seed))

    def half(seed):
        return train.compare(sess.reference(seed, rows=sess.batch // 2),
                             ref(seed))

    for s in seeds:
        yield "sound", s, lambda s=s: sound(s)
    for s in control_seeds:
        yield "control", s, lambda s=s: control(s)
        yield "half_batch", s, lambda s=s: half(s)
