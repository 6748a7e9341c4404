"""HLO walker correctness: scan trip-count multiplication, collectives."""
import jax
import jax.numpy as jnp

from repro.roofline import hlo as H


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_flops_multiplied():
    """cost_analysis counts a while body once; the walker multiplies."""
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = _compile(f, xs, ws)
    one = 2 * 128 * 128 * 128
    raw = c.cost_analysis()["flops"]
    assert raw < 2 * one                      # XLA undercounts
    costs = H.analyze(c.as_text())
    assert abs(costs.dot_flops - 10 * one) / (10 * one) < 0.05
    assert 10 in costs.trip_counts


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = _compile(f, xs, ws)
    costs = H.analyze(c.as_text())
    one = 2 * 64 * 64 * 64
    assert abs(costs.dot_flops - 12 * one) / (12 * one) < 0.05


def test_unrolled_matches_walker():
    def f(x, w):
        for _ in range(5):
            x = x @ w
        return x
    xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = _compile(f, xs, xs)
    costs = H.analyze(c.as_text())
    one = 2 * 128 ** 3
    assert abs(costs.dot_flops - 5 * one) / (5 * one) < 0.05


def test_collective_bytes_parsed():
    import subprocess, sys, textwrap, os
    from pathlib import Path
    src = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, {src!r})
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.roofline import hlo as H
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,), ("d",), axis_types=(AxisType.Auto,))
        def f(x):
            return jnp.sum(x * 2.0)
        xs = jax.ShapeDtypeStruct((1024, 256), jnp.float32,
                                  sharding=NamedSharding(mesh, P("d", None)))
        c = jax.jit(f).lower(xs).compile()
        costs = H.analyze(c.as_text())
        assert "all-reduce" in costs.coll_detail, costs.coll_detail
        b, n = costs.coll_detail["all-reduce"]
        assert n >= 1 and b >= 4.0, (b, n)     # scalar f32 all-reduce, 2x factor
        print("COLL_OK")
    """)], capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COLL_OK" in out.stdout


def test_type_bytes():
    assert H.type_bytes("bf16[64,256]{1,0}") == 64 * 256 * 2
    assert H.type_bytes("f32[]") == 4
    assert H.type_bytes("(s32[], bf16[8,8]{1,0})") == 4 + 128
    assert H.type_bytes("pred[16]") == 16
