"""Distributed correctness via subprocess (forced host devices).

These spawn fresh interpreters because device count locks at jax init.
Covers: pipeline parallelism (gpipe exactness + async convergence), sharded
train step == single-device train step, sequence-parallel whisper anchor.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(ndev: int, body: str) -> str:
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        sys.path.insert(0, {SRC!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=dict(os.environ), timeout=600)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    return r.stdout


def test_gpipe_forward_exact_and_async_converges():
    out = _run(4, """
        from repro.parallel import pipeline as PP
        mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
        D = 16
        def stage_fn(p, x): return jnp.tanh(x @ p["w"] + p["b"])
        k = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(k, (4, D, D)) * 0.5,
                  "b": jnp.zeros((4, D))}
        xs = jax.random.normal(k, (8, 4, D))
        ys = jax.random.normal(jax.random.PRNGKey(1), (8, 4, D)) * 0.1
        outs = PP.gpipe_forward(stage_fn, params, xs, mesh)
        def seq(x):
            for s in range(4):
                x = stage_fn({"w": params["w"][s], "b": params["b"][s]}, x)
            return x
        assert jnp.allclose(outs, jax.vmap(seq)(xs), atol=1e-5)
        def lg(y, yt): return 2*(y-yt)/y.size, jnp.mean((y-yt)**2)
        p = params
        first = last = None
        for ep in range(25):
            p, losses = PP.async_pipeline_epoch(stage_fn, lg, p, xs, ys, mesh, 0.05)
            warm = losses[losses > 0]
            if ep == 0: first = float(warm.mean())
            last = float(warm.mean())
        assert last < 0.7 * first, (first, last)
        print("PIPE_OK")
    """)
    assert "PIPE_OK" in out


def test_sharded_train_matches_single_device():
    out = _run(8, """
        from repro.configs import registry
        from repro.models import model as M
        from repro.optim import adam, constant_schedule
        from repro.parallel import sharding as sh, hints
        from repro.train.steps import make_train_step
        from repro.launch.mesh import make_local_mesh
        from repro.launch.specs import concrete_batch

        cfg = registry.get("deepseek-7b").reduced()
        params = M.init(cfg, jax.random.PRNGKey(0))
        opt = adam(constant_schedule(1e-3), grad_clip=None)
        st = opt.init(params)
        batch = concrete_batch(cfg, 4, 64, jax.random.PRNGKey(3))
        fn = make_train_step(cfg, opt, jit=False)  # shardings jit below

        # single device reference
        p1, s1, m1 = jax.jit(fn)(params, st, batch, jnp.asarray(0))

        # 2x4 mesh
        mesh = make_local_mesh(2, 4)
        pspecs = sh.param_specs(cfg, params, mesh)
        psh = sh.to_shardings(pspecs, mesh)
        params_d = jax.tree.map(jax.device_put, params, psh)
        st_d = opt.init(params_d)
        with mesh, hints.use_mesh_hints(mesh):
            p2, s2, m2 = jax.jit(fn)(params_d, st_d, batch, jnp.asarray(0))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3, \
            (float(m1["loss"]), float(m2["loss"]))
        # parameters agree after one update
        l1 = jax.tree.leaves(p1); l2 = jax.tree.leaves(p2)
        worst = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(l1, l2)
                    if jnp.issubdtype(a.dtype, jnp.inexact))
        assert worst < 5e-3, worst
        print("SHARD_OK", worst)
    """)
    assert "SHARD_OK" in out


def test_data_parallel_pallas_step_matches_one_device():
    """launch/train.py --data 4: the junction kernels run per device under
    shard_map (XLA cannot partition a Pallas call), params and optimizer
    state live sharded, and two steps match the one-device step."""
    out = _run(4, """
        import dataclasses
        from repro.configs import registry
        from repro.core.sparsity import SparsityConfig
        from repro.data.pipeline import LMTokenPipeline
        from repro.launch.mesh import make_local_mesh
        from repro.launch.train import shard_train_step
        from repro.models import model as M
        from repro.optim import constant_schedule, fused_adam
        from repro.train.steps import make_train_step

        cfg = dataclasses.replace(
            registry.get("stablelm-3b").reduced().with_sparsity(
                SparsityConfig(density=0.25, block=32, where="ffn")),
            engine="pallas", dtype="float32")
        opt = fused_adam(constant_schedule(1e-3), grad_clip=1.0)
        batches = [jax.tree.map(jnp.asarray, b) for b, _ in
                   zip(LMTokenPipeline(cfg, 4, 32), range(2))]
        fresh = lambda: M.init(cfg, jax.random.PRNGKey(0))

        p1 = fresh()
        s1 = opt.init(p1)
        one = make_train_step(cfg, opt)
        mesh = make_local_mesh(4, 1)
        p4 = fresh()
        four, p4, s4 = shard_train_step(cfg, make_train_step(cfg, opt, jit=False),
                                        p4, opt.init(p4), mesh)
        w = p4["layers"]["mlp"]["wi"]["w"]
        assert len(w.sharding.device_set) == 4, w.sharding
        assert w.addressable_shards[0].data.size * 4 == w.size
        assert s4["m"]["layers"]["mlp"]["wi"]["w"].sharding == w.sharding
        for i, b in enumerate(batches):
            p1, s1, m1 = one(p1, s1, b, jnp.asarray(i))
            p4, s4, m4 = four(p4, s4, b, jnp.asarray(i))
            assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-4, (
                float(m1["loss"]), float(m4["loss"]))
        worst = max(float(jnp.max(jnp.abs(a - b)))
                    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4))
                    if jnp.issubdtype(a.dtype, jnp.inexact))
        assert worst < 1e-4, worst
        print("DP_OK", worst)
    """)
    assert "DP_OK" in out


def test_grad_compression_cross_pod():
    out = _run(4, """
        from repro.train import grad_compress as GC
        g = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 0.01
        err = jnp.zeros_like(g)
        restored, err2 = GC.compress_decompress(g, err)
        rel = float(jnp.linalg.norm(restored - g) / jnp.linalg.norm(g))
        assert rel < 0.02, rel
        # error feedback: two-step accumulated error stays bounded
        r2, err3 = GC.compress_decompress(g, err2)
        assert float(jnp.linalg.norm(err3)) <= float(jnp.linalg.norm(err2)) * 1.5 + 1e-6
        print("GC_OK")
    """)
    assert "GC_OK" in out
