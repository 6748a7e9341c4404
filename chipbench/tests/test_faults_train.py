"""A training run with the timed path broken underneath must come out
not correct.  The run is the harness's own (``run.run_cell``) at a size a
CPU test can hold, with the chip check skipped; the fault is planted in
the program's step factory.  The cell's limits are set for its own size,
where rounding reads lower than at this one, so each fault must fail a
check that the sound run at this size passes."""
from __future__ import annotations

import pytest

from chipbench import bench, run
from chipbench.tests import faults, tiny


def _run():
    import jax
    return run.run_cell(tiny.tiny_cell("stablelm3b-train-fused"),
                        seed=2**34 + 17, seconds=0.2, trace=False,
                        devices=jax.devices()[:1])


def _passed(result):
    return {k for k, c in result["checks"].items()
            if c["value"] <= c["limit"]}


@pytest.fixture(scope="module")
def sound():
    return _run()


@pytest.fixture(scope="module")
def steps_module():
    bench.use_program_sources()
    from repro.train import steps
    return steps


def test_sound_run_passes_the_size_free_checks(sound):
    assert {"grad_diff", "update_norm_gap", "window_compiles"} <= \
        _passed(sound), sound["checks"]
    assert list(sound)[-1] == "checks"
    assert sound["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["train_state_unchanged",
                                   "train_half_batch"])
def test_fault_is_caught(fault, sound, steps_module, monkeypatch):
    monkeypatch.setattr(steps_module, "make_train_step",
                        getattr(faults, fault)(steps_module.make_train_step))
    result = _run()
    assert not result["correct"], result["checks"]
    assert _passed(sound) - _passed(result), result["checks"]
