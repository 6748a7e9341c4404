"""A sweep run with the timed path broken underneath must come out not
correct: the harness's own run at a size a CPU test can hold, chip check
skipped, the fault planted in the population step the sweep calls."""
from __future__ import annotations

import pytest

from chipbench import bench, run
from chipbench.tests import faults, tiny


def _run(cell):
    import jax
    return run.run_cell(cell, seed=2**35 + 5, seconds=0.1, trace=False,
                        devices=jax.devices()[:1])


@pytest.fixture(scope="module", autouse=True)
def program_cache(tmp_path_factory):
    """The persistent compilation cache of a run (``bench.prepare_jax``),
    in a directory of the module's own: every sweep of the window builds
    its jitted step anew and fetches it from there."""
    import jax
    from jax._src import compilation_cache
    names = {"jax_compilation_cache_dir":
             str(tmp_path_factory.mktemp("jax_cache")),
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": 0}
    saved = {n: getattr(jax.config, n) for n in names}
    for n, v in names.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sound():
    return _run(tiny.tiny_cell("ffn-population-sweep"))


def test_sound_sweep_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["metrics"]["sweep_member_steps_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["sweep_state_unchanged",
                                   "sweep_half_batch",
                                   "sweep_answer_altered"])
def test_fault_is_caught(fault, sound, monkeypatch):
    bench.use_program_sources()
    from repro.search import population as pop
    monkeypatch.setattr(pop, "make_population_step",
                        getattr(faults, fault)(pop.make_population_step))
    result = _run(tiny.tiny_cell("ffn-population-sweep"))
    assert not result["correct"], result["checks"]


def test_inverted_pruning_is_caught(sound, monkeypatch):
    bench.use_program_sources()
    from repro.search import scheduler
    monkeypatch.setattr(scheduler, "_score",
                        faults.sweep_prune_inverted(scheduler._score))
    result = _run(tiny.tiny_cell("ffn-population-sweep"))
    assert result["checks"]["rank_gap"]["value"] > \
        result["checks"]["rank_gap"]["limit"], result["checks"]
    assert not result["correct"], result["checks"]
