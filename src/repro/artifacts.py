"""The shared artifact stamp: one meta schema for every results file.

``BENCH_*.json`` (benchmarks/run.py) and ``SWEEP_*.json``
(search/ledger.py) carry the same ``meta`` block so artifacts are
commit-attributable and comparable across PRs regardless of kind:

    {git_sha, backend, jax_version, tag, timestamp}

Both writers stamp through :func:`artifact_meta` — the schema and the
-dirty detection live HERE, nowhere else.
"""
from __future__ import annotations

import subprocess
import time
from pathlib import Path


def git_sha() -> str:
    """Short HEAD sha, with a -dirty marker when the tree has uncommitted
    changes — numbers measured on a dirty tree must not be attributed to
    the clean commit.  "unknown" outside a git checkout (an exported
    tree), without starting a git process."""
    if not (Path(__file__).resolve().parents[2] / ".git").exists():
        return "unknown"
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
        return f"{sha}-dirty" if dirty else sha
    except Exception:
        return "unknown"


def artifact_meta(tag: str) -> dict:
    import jax  # deferred: keep --help paths jax-free
    return {
        "git_sha": git_sha(),
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "tag": tag,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
