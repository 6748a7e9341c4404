"""Sweep cells (traffic ``"kind": "sweep"``): ``search/scheduler.run_sweep``
over a grid of densities and learning rates, whole sweeps back to back.

Set-up makes the data from the seed (inputs and a teacher's targets, on
the device, then handed to ``run_sweep`` as the arrays it takes) and
runs two sweeps through the same call as the window, on the same data,
cohorts, batch and rounds.  The first is the one the reference checks,
``check_steps_per_round`` steps a round, its members' weights made by
the benchmark from the run's seed (``adapters/population.stand_in_init``):
every step's loss while a member is live, its eval loss after every
round, which members were pruned (``rank_gap``), and the change of its
weights from start to end (a pruned member's must stop where it was
pruned).  The second, two steps a round, runs the program's own init
and is called from the same line as the window's sweeps, so that every
program the window uses is compiled or in the cache.

The window runs the program as a user calls it: whole ``run_sweep``
calls of ``steps_per_round`` steps a round, each building its own jitted
step and eval (fetched from the persistent cache) and its members'
weights from ``SweepConfig.seed`` (drawn from the run's seed), until
``--seconds`` have passed.  Every sweep of a run is the same work.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from chipbench import bench


def _adapter(conf):
    return bench.load_module(bench.HERE / "adapters" / f"{conf['adapter']}.py")


class Session:
    def __init__(self, cell: bench.Cell):
        bench.use_program_sources()
        self.cell = cell
        self.conf, self.tr = cell.config, cell.traffic
        self.ad = _adapter(self.conf)
        self.specs = self.ad.specs(self.conf, self.tr)
        self.out_width = self.conf["layers"][-1]

    def config(self, seed, steps):
        from repro.configs.base import SweepConfig
        tr = self.tr
        return SweepConfig(
            rounds=tr["rounds"], steps_per_round=steps,
            batch_size=tr["batch"], eval_samples=tr["eval_samples"],
            keep_fraction=tr["keep_fraction"], seed=program_seed(seed),
            engine="pallas", fused=True, quarantine=True)

    def data(self, seed):
        import jax
        key = bench.seed_key(seed)
        n, ne = self.tr["train_samples"], self.tr["eval_samples"]
        x, t = self.ad.teacher_data(jax.random.fold_in(key, 1), n + ne,
                                    tuple(self.conf["layers"]))
        x, t = np.asarray(x), np.asarray(t)
        return x[:n], t[:n], x[n:], t[n:]

    def weights_key(self, seed):
        import jax
        return jax.random.fold_in(bench.seed_key(seed), 2)

    def sweep(self, seed, data, steps):
        """One ``run_sweep`` call as the program runs it."""
        from repro.search import run_sweep
        with bench.span("sweep"):
            return run_sweep(self.specs, *data, self.config(seed, steps))

    def checked_sweep(self, seed, data):
        """The sweep the reference checks: weights from the benchmark."""
        from repro.search import run_sweep
        with self.ad.stand_in_init(self.conf, self.weights_key(seed)):
            with bench.span("checked_sweep"):
                return run_sweep(self.specs, *data, self.config(
                    seed, self.tr["check_steps_per_round"]))

    # -------------------------------------------------------------- counts
    def counts(self, result) -> dict:
        """Live and computed member-steps of one sweep, and their work."""
        live = computed = flops = jflops = jbytes = 0.0
        rows = self.tr["batch"]
        for st in result.states:
            d = st.cohort.specs[0].density
            shape = self.ad.work.PopulationShape(
                tuple(self.ad.junction_shapes(self.conf, d)))
            steps = max(len(r.loss_curve) for r in st.records)
            n_live = sum(len(r.loss_curve) for r in st.records)
            live += n_live
            computed += steps * len(st.records)
            flops += n_live * shape.member_step_flops(rows)
            jflops += steps * len(st.records) * shape.member_step_flops(rows)
            jbytes += steps * len(st.records) * shape.member_step_bytes(
                rows, 4, 4)
        return {"live_member_steps": live, "computed_member_steps": computed,
                "model_flops": flops, "junction_flops": jflops,
                "junction_bytes": jbytes}

    # ------------------------------------------------------------ program
    def claims(self, seed, result) -> dict:
        """What the checked sweep says, per member: step losses, eval
        losses, the round it was pruned in, and the change of each leaf
        from the benchmark's initial weights."""
        import jax.numpy as jnp
        wkey = self.weights_key(seed)
        out = {"losses": {}, "evals": {}, "pruned": {}, "change": {}}
        for r in result.ledger.members:
            out["losses"][r.member] = list(r.loss_curve)
            out["evals"][r.member] = list(r.eval_losses)
            if r.pruned_at is not None:
                out["pruned"].setdefault(r.pruned_at, set()).add(r.member)
        for st in result.states:
            ids = st.cohort.member_ids
            _, init = self.ad.member_weights(
                self.conf, st.cohort.specs[0].density,
                [s.init_seed for s in st.cohort.specs], wkey)
            for j, (layer, (w0, b0)) in enumerate(zip(st.params, init)):
                for leaf, x0 in (("w", w0), ("b", b0)):
                    d = (layer[leaf] - x0).reshape(len(ids), -1)
                    n = np.asarray(jnp.sqrt(jnp.sum(d * d, axis=1)))
                    for e, m in enumerate(ids):
                        out["change"][f"m{m}/j{j}/{leaf}"] = float(n[e])
        return out

    # ---------------------------------------------------------- reference
    def reference(self, seed, data, pruned, lowp=None, rows=None) -> dict:
        import jax.numpy as jnp
        from repro.search import bucket
        ref = bench.load_module(self.cell.reference_file)
        wkey = self.weights_key(seed)
        cohorts = []
        for c in bucket(self.specs):
            pats, ws = self.ad.member_weights(
                self.conf, c.specs[0].density,
                [s.init_seed for s in c.specs], wkey)
            cohorts.append((list(c.member_ids), [s.lr for s in c.specs],
                            [(w, b) for w, b in ws],
                            [p["idx"] for p in pats]))
        x, t, xe, te = (jnp.asarray(a) for a in data)
        tr = {**self.tr, "steps_per_round": self.tr["check_steps_per_round"]}
        out = ref.sweep(cohorts, x, t, xe, te, tr, self.out_width, pruned,
                        lowp, rows)
        change = {}
        for c in cohorts:
            for e, m in enumerate(c[0]):
                for j, ((w, b), (w0, b0)) in enumerate(zip(out["finals"][m],
                                                           c[2])):
                    change[f"m{m}/j{j}/w"] = float(jnp.linalg.norm(w - w0[e]))
                    change[f"m{m}/j{j}/b"] = float(jnp.linalg.norm(b - b0[e]))
        out["change"] = change
        return out


def program_seed(seed: int) -> int:
    """``SweepConfig.seed`` for a run's seed: 31 bits, the high ones
    folded in."""
    seed = int(seed)
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


def compare(got: dict, want: dict) -> dict:
    loss = evals = 0.0
    for kind, gap in (("losses", "loss"), ("evals", "eval")):
        worst = 0.0
        for m, w in want[kind].items():
            g = got[kind].get(m, [])
            if len(g) != len(w):
                worst = math.inf
                break
            for a, b in zip(g, w):
                worst = max(worst, bench.relative_gap(a, b))
        if gap == "loss":
            loss = worst
        else:
            evals = worst
    ck, cv = bench.worst(bench.norm_gaps(got["change"], want["change"]))
    return {"loss_gap": loss, "eval_gap": evals, "rank_gap": want["rank_gap"],
            "update_norm_gap": cv, "worst_update_leaf": ck}


def run(ctx: bench.Context) -> bench.DriverResult:
    sess = Session(ctx.cell)
    data = sess.data(ctx.seed)
    got = sess.claims(ctx.seed, sess.checked_sweep(ctx.seed, data))
    # The warm-up, two steps a round, and the window call run_sweep from
    # one line: run_sweep traces its step anew on every call, and the key
    # under which the step is compiled holds the Python lines it was
    # traced from (the same step traced from two lines gets two keys when
    # lowered for a v5e).  From two lines, the window compiled its own.
    totals = {}
    sweeps = failed = 0
    for window in (False, True):
        if window:
            compiles0 = ctx.meter.fresh
            setup_s = time.time() - ctx.process_start
        steps = ctx.cell.traffic["steps_per_round"] if window else 2
        with bench.maybe_trace(window and ctx.trace, ctx.trace_dir), \
                bench.span("window" if window else "warm_up"):
            t0 = time.perf_counter()
            while True:
                res = sess.sweep(ctx.seed, data, steps)
                if window:
                    sweeps += 1
                    failed += res.ledger.winner() is None
                    for k, v in sess.counts(res).items():
                        totals[k] = totals.get(k, 0.0) + v
                del res
                if not window or time.perf_counter() - t0 >= ctx.seconds:
                    break
            window_s = time.perf_counter() - t0
        gc.collect()
    compiled = ctx.meter.fresh_names[compiles0:]
    peak = bench.memory_peak_bytes(ctx.devices)
    want = sess.reference(ctx.seed, data, got["pruned"])
    cmp = compare(got, want)
    print(f"sweep: set-up {setup_s:.3f} s ({ctx.meter}), {sweeps} sweeps "
          f"in {window_s:.3f} s; pruned by round "
          f"{ {r: sorted(v) for r, v in got['pruned'].items()} }; worst "
          f"update leaf {cmp['worst_update_leaf']}; compiled in the "
          f"window: {compiled}", file=sys.stderr)
    lim = ctx.cell.traffic["limits"]
    checks = [bench.Check(k, float(cmp[k]), float(lim[k]))
              for k in ("loss_gap", "eval_gap", "update_norm_gap",
                        "rank_gap")]
    checks.append(bench.Check("window_compiles", float(len(compiled)), 0.0))
    totals["window_s"] = window_s
    totals["sweeps"] = sweeps
    return bench.DriverResult(
        end_to_end={"sweep_member_steps_per_s":
                    totals["live_member_steps"] / window_s,
                    "setup_s": setup_s},
        counters=totals, checks=checks, attempted=sweeps, failed=failed,
        memory_peak_bytes=peak)


def calibration(cell, seeds, control_seeds):
    """(kind, seed, thunk) for calibrate.py: the program's checked sweep
    against the reference on ``seeds``; on ``control_seeds`` the
    reference in the control's precision, and with half of each batch
    left out (the mean over the rest), against the reference, and the
    program's checked sweep with its ranking inverted (the scheduler
    keeps the worst members), against the reference."""
    from chipbench.tests import faults
    sess = Session(cell)
    state = {}

    def base(seed):
        if state.get("seed") != seed:
            state.clear()
            data = sess.data(seed)
            got = sess.claims(seed, sess.checked_sweep(seed, data))
            gc.collect()
            state.update(seed=seed, data=data, got=got,
                         want=sess.reference(seed, data, got["pruned"]))
        return state

    def sound(seed):
        st = base(seed)
        return compare(st["got"], st["want"])

    def control(seed):
        st = base(seed)
        return compare(sess.reference(seed, st["data"], st["got"]["pruned"],
                                      lowp=cell.traffic["control"]),
                       st["want"])

    def half(seed):
        st = base(seed)
        return compare(sess.reference(seed, st["data"], st["got"]["pruned"],
                                      rows=cell.traffic["batch"] // 2),
                       st["want"])

    def inverted(seed):
        from repro.search import scheduler
        st = base(seed)
        real = scheduler._score
        scheduler._score = faults.sweep_prune_inverted(real)
        try:
            got = sess.claims(seed, sess.checked_sweep(seed, st["data"]))
        finally:
            scheduler._score = real
        gc.collect()
        return compare(got, sess.reference(seed, st["data"], got["pruned"]))

    for s in seeds:
        yield "sound", s, lambda s=s: sound(s)
    for s in control_seeds:
        yield "control", s, lambda s=s: control(s)
        yield "half_batch", s, lambda s=s: half(s)
        yield "prune_inverted", s, lambda s=s: inverted(s)
