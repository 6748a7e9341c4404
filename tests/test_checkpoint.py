"""Checkpointing: atomicity, bitwise restart, elastic reshard, async."""
import json
import os
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as C


def _tree(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": jax.random.normal(k, (17, 5)),
            "b": {"c": jnp.arange(7, dtype=jnp.int32),
                  "d": jax.random.normal(jax.random.fold_in(k, 1), (3,),
                                         jnp.bfloat16)}}


def test_bitwise_roundtrip(tmp_path):
    t = _tree()
    C.save(tmp_path, 5, t, extra={"step": 5, "data_state": {"seed": 1, "step": 9}})
    got, extra = C.restore(tmp_path, 5, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        assert a.dtype == b.dtype
        assert jnp.array_equal(a, b)
    assert extra["data_state"] == {"seed": 1, "step": 9}


def test_latest_skips_partial(tmp_path):
    C.save(tmp_path, 1, _tree())
    C.save(tmp_path, 2, _tree(1))
    # a partial (crashed) checkpoint: directory without manifest
    (tmp_path / "step_0000000003").mkdir()
    assert C.latest_step(tmp_path) == 2


def test_checksum_detects_corruption(tmp_path):
    C.save(tmp_path, 1, _tree())
    npz = tmp_path / "step_0000000001" / "arrays.npz"
    data = dict(np.load(npz))
    data["leaf_0"] = data["leaf_0"] + 1.0
    np.savez(npz, **data)
    with pytest.raises(IOError):
        C.restore(tmp_path, 1, _tree())


def test_async_saver(tmp_path):
    s = C.AsyncSaver()
    t = _tree()
    s.save(tmp_path, 7, t, extra={"step": 7})
    s.wait()
    assert C.latest_step(tmp_path) == 7
    got, _ = C.restore(tmp_path, 7, t)
    assert jnp.array_equal(jax.tree.leaves(got)[0], jax.tree.leaves(t)[0])


def test_restore_latest_falls_back_past_corruption(tmp_path):
    """A corrupted newest checkpoint must not kill auto-resume: fallback
    to the next-newest verifiable one, logged."""
    t = _tree()
    C.save(tmp_path, 1, t, extra={"step": 1})
    C.save(tmp_path, 2, _tree(1), extra={"step": 2})
    npz = tmp_path / "step_0000000002" / "arrays.npz"
    npz.write_bytes(npz.read_bytes()[:50])          # torn write
    logs = []
    s, tree, extra = C.restore_latest(tmp_path, t, log=logs.append)
    assert s == 1 and extra["step"] == 1
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(tree)):
        assert jnp.array_equal(a, b)
    assert any("falling back" in l for l in logs)
    # every candidate corrupt -> (None, None, None), no exception
    (tmp_path / "step_0000000001" / "arrays.npz").write_bytes(b"junk")
    s, tree, extra = C.restore_latest(tmp_path, t, log=logs.append)
    assert s is None and tree is None and extra is None


def test_full_checksum_catches_tail_corruption(tmp_path):
    """Head-mode digests only the first MiB per leaf: tail corruption in
    a >1MiB leaf slips through.  full_checksum=True catches it."""
    big = {"w": jnp.arange(600_000, dtype=jnp.float32)}   # 2.4 MB leaf

    def tamper(d):
        npz = d / "step_0000000001" / "arrays.npz"
        data = {k: v.copy() for k, v in np.load(npz).items()}
        data["leaf_0"][-1] += 1.0
        np.savez(npz, **data)

    C.save(tmp_path / "head", 1, big)
    tamper(tmp_path / "head")
    got, _ = C.restore(tmp_path / "head", 1, big)   # head digest misses it
    assert float(np.asarray(got["w"])[-1]) != 599_999.0

    C.save(tmp_path / "full", 1, big, full_checksum=True)
    tamper(tmp_path / "full")
    with pytest.raises(IOError):
        C.restore(tmp_path / "full", 1, big)


def test_kill_between_npz_write_and_rename(tmp_path, monkeypatch):
    """Hard kill after the npz/manifest writes but before the rename (no
    cleanup runs): the leftover .tmp dir must not shadow or corrupt the
    previous checkpoint."""
    t = _tree()
    C.save(tmp_path, 1, t, extra={"step": 1})

    def die(*a, **k):
        raise KeyboardInterrupt("simulated kill")

    monkeypatch.setattr(C.os, "rename", die)
    monkeypatch.setattr(C.shutil, "rmtree", lambda *a, **k: None)
    with pytest.raises(KeyboardInterrupt):
        C.save(tmp_path, 2, _tree(1), extra={"step": 2})
    monkeypatch.undo()

    leftovers = [d for d in tmp_path.iterdir() if d.name.startswith(".tmp_")]
    assert leftovers, "kill before rename should leave the tmp dir behind"
    assert C.latest_step(tmp_path) == 1
    s, tree, extra = C.restore_latest(tmp_path, t)
    assert s == 1 and extra["step"] == 1
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(tree)):
        assert jnp.array_equal(a, b)


def test_async_save_failure_surfaces_on_wait(tmp_path, monkeypatch):
    """A crash inside an in-flight AsyncSaver.save must surface on wait()
    and leave latest_step pointing at the previous good checkpoint —
    and the saver must stay usable afterwards."""
    s = C.AsyncSaver()
    s.save(tmp_path, 1, _tree(), extra={"step": 1})
    s.wait()

    def die(*a, **k):
        raise IOError("simulated disk failure")

    monkeypatch.setattr(C.np, "savez", die)
    s.save(tmp_path, 2, _tree(1), extra={"step": 2})
    with pytest.raises(IOError):
        s.wait()
    monkeypatch.undo()
    assert C.latest_step(tmp_path) == 1
    s.save(tmp_path, 3, _tree(2), extra={"step": 3})
    s.wait()
    assert C.latest_step(tmp_path) == 3


def test_gc_keeps_healthy_floor(tmp_path):
    """Retention never deletes the latest healthy mark: steps 1..5,
    step 2 healthy, keep_last_k=2 -> {2, 4, 5} remain."""
    for st in range(1, 6):
        C.save(tmp_path, st, _tree(st))
    C.mark_healthy(tmp_path, 2)
    assert C.is_healthy(tmp_path, 2)
    removed = C.gc_checkpoints(tmp_path, keep_last_k=2)
    assert removed == [1, 3]
    assert C.complete_steps(tmp_path) == [2, 4, 5]
    assert C.latest_healthy_step(tmp_path) == 2
    # idempotent: nothing further to delete
    assert C.gc_checkpoints(tmp_path, keep_last_k=2) == []


def test_elastic_reshard_subprocess(tmp_path):
    """Save on an 8-device mesh, restore onto a 4-device mesh (elastic)."""
    import subprocess, sys, textwrap
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + sys.argv[1]
        sys.path.insert(0, {str(Path(__file__).resolve().parents[1] / 'src')!r})
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as C
        n = int(sys.argv[1])
        from jax.sharding import AxisType
        mesh = jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
        sh = NamedSharding(mesh, P("data"))
        t = {{"w": jax.device_put(jnp.arange(32, dtype=jnp.float32), sh)}}
        if sys.argv[2] == "save":
            C.save({str(tmp_path)!r}, 1, t)
        else:
            got, _ = C.restore({str(tmp_path)!r}, 1, t, shardings={{"w": sh}})
            assert got["w"].sharding.num_devices == n, got["w"].sharding
            assert jnp.array_equal(got["w"], jnp.arange(32, dtype=jnp.float32))
            print("RESHARD_OK")
    """)
    env = dict(os.environ)
    r1 = subprocess.run([sys.executable, "-c", script, "8", "save"],
                        capture_output=True, text=True, env=env)
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run([sys.executable, "-c", script, "4", "load"],
                        capture_output=True, text=True, env=env)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "RESHARD_OK" in r2.stdout
