import os
import sys
from pathlib import Path

# tests run on the single real CPU device (dryrun.py alone forces 512)
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")


def optional_hypothesis():
    """(given, settings, st) — real hypothesis when installed, otherwise
    stubs that skip only the property tests (plain tests in the same
    module still run)."""
    try:
        from hypothesis import given, settings, strategies as st
        return given, settings, st
    except ImportError:
        import pytest

        def given(*a, **k):
            return pytest.mark.skip(reason="hypothesis not installed")

        def settings(*a, **k):
            return lambda f: f

        class _StrategyStub:
            def __getattr__(self, name):
                return lambda *a, **k: None

        return given, settings, _StrategyStub()


@pytest.fixture
def cold_sweep_programs():
    """The sweep scheduler's program cache emptied first: a ``run_sweep``
    in the test builds, and traces, its cohorts' step and eval anew,
    whatever sweeps the process ran before (trace counts do not depend
    on test order)."""
    from repro.search import clear_program_cache
    clear_program_cache()
