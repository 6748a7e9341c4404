"""Where JAX's persistent compilation cache lives — one rule for every
entry point (``chip_smoke.py``, ``launch/{train,serve,sweep}.py``).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no other
  directory is configured here.
* Not set: a fixed directory inside the checkout, ``<repo>/.jax_cache``
  (listed in ``.gitignore``).  Never a temporary, pid- or time-based path.

Either way the checkout's own path is kept out of the cache key.  A Pallas
kernel reaches XLA as a serialized Mosaic module that carries its debug
locations (source file and line), and JAX strips debug info only from the
outer program, so without this every checkout at a new path would miss
the cache for every program that holds a kernel.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    # source paths in locations become relative to the checkout
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{ROOT}{os.sep}"))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
