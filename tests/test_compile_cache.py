"""The persistent compilation cache key of a program holding a Pallas
kernel does not depend on where the checkout lives.

A kernel reaches XLA as a serialized Mosaic module with its debug
locations (absolute source paths) inside, which JAX's own key
canonicalization does not strip.  ``launch/compile_cache`` makes those
paths relative to the checkout.  Each case lowers the same kernel for a
TPU (no chip needed) from two copies of ``src/`` at different paths, in
subprocesses so the test process's JAX config is left alone.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = r"""
import hashlib
import jax, jax.numpy as jnp
from jax._src import cache_key
from repro.core.sparsity import make_block_pattern
from repro.kernels import block_sparse_matmul as bsm, ops
from repro.launch.compile_cache import enable_compile_cache

ops._auto_interpret = lambda: False
pat = make_block_pattern(256, 512, 0.5, 128)
args = (jax.ShapeDtypeStruct((1, 256, 256), jnp.bfloat16),
        jax.ShapeDtypeStruct((1,) + pat.idx.shape + (128, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct(pat.idx.shape, jnp.int32),
        jax.ShapeDtypeStruct((1, 1, 512), jnp.float32))

def key():
    low = jax.jit(lambda x, w, i, b: bsm.fwd(x, w, i, b)[0]).trace(
        *args).lower(lowering_platforms=("tpu",))
    ir = cache_key._canonicalize_ir(low.compiler_ir("stablehlo"),
                                    cache_key.IgnoreCallbacks.NO)
    return hashlib.sha256(ir).hexdigest()

raw = key()
enable_compile_cache()
print(raw, key())
"""


def _keys(tmp_path, name):
    checkout = tmp_path / name
    shutil.copytree(ROOT / "src", checkout / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, PYTHONPATH=str(checkout / "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         cwd=checkout, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_kernel_cache_key_is_independent_of_checkout_path(tmp_path):
    raw_a, key_a = _keys(tmp_path, "a")
    raw_b, key_b = _keys(tmp_path, "checkout_b")
    # the kernel's payload carries the checkout path ...
    assert raw_a != raw_b
    # ... which enable_compile_cache keeps out of the key
    assert key_a == key_b
