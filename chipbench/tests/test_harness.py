"""The harness without a chip: it refuses to run off a TPU or on a chip
missing from the peaks table, every name in BENCHMARK.json finds its
files, and the traffic generators give the same inputs for a seed with
the distributions they state."""
from __future__ import annotations

import json
import re
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import bench, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def benchmark():
    return bench.load_json(bench.ROOT / "BENCHMARK.json")


@pytest.fixture
def jax_config_restored(monkeypatch):
    """run.main turns on the persistent compilation cache for the
    process; put every setting back for the tests that follow."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_hlo_source_file_canonicalization_regex")
    saved = {n: getattr(jax.config, n) for n in names}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_no_tpu_exits_nonzero_without_a_result(capsys, jax_config_restored):
    rc = run.main(["--workload", "stablelm3b-train-fused", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU found" in err
    assert out == ""


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax
    fake = NS(platform="tpu", device_kind="TPU v9 imaginary", id=0)
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(bench.HarnessError, match="no published peaks"):
        bench.chip_devices(1)
    monkeypatch.setattr(jax, "devices", lambda *a: [
        NS(platform="tpu", device_kind="TPU v5 lite", id=0)])
    with pytest.raises(bench.HarnessError, match="needs 4 chips"):
        bench.chip_devices(4)


def test_missing_files_fail_the_run(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "ROOT", tmp_path)
    (tmp_path / "BENCHMARK.json").write_text("{}")
    assert run.main(["--workload", "x", "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0


def test_every_name_finds_its_files(benchmark):
    used = set()
    for w in benchmark["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = bench.find_cell(w["name"], benchmark)
        used.add(cell.config_name)
        assert cell.reference_file.is_file()
        assert (bench.HERE / "drivers" / f"{cell.traffic['kind']}.py").is_file()
        assert (bench.HERE / "adapters" /
                f"{cell.config['adapter']}.py").is_file()
        assert cell.traffic["limits"] and cell.traffic["control"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
    assert used == {c["name"] for c in benchmark["configs"]}
    for m in benchmark["per_layer"]:
        assert NAME.match(m["name"])
        assert hasattr(bench.reader_for(m["name"]), "read")


def test_configs_state_their_cut(benchmark):
    for c in benchmark["configs"]:
        conf = bench.load_json(bench.ROOT / c["file"])
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        for key in c["reduced"]:
            assert key in conf["reduced"] and key in conf["published"]
            assert not key.endswith(("_dim", "_rank", "_size"))


def test_large_seed_gives_a_key():
    import jax
    k1 = bench.seed_key(2**31 + 12345)
    k2 = bench.seed_key(2**40 + 12345)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))


def _lm():
    return bench.load_module(bench.HERE / "adapters" / "dense_lm.py")


def test_token_batches_repeat_for_a_seed_and_differ_by_row():
    tb = _lm().TokenBatches(2**41 + 3, 8, 1024, 50304)
    a, b = tb(0), tb(0)
    assert a.dtype == np.int32 and a.shape == (8, 1024)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(tb(0), tb(1))
    assert len({r.tobytes() for r in a}) == 8
    assert a.min() >= 0 and a.max() < 50304
    other = _lm().TokenBatches(2**41 + 4, 8, 1024, 50304)(0)
    assert not np.array_equal(a, other)


def test_token_batches_state_their_distribution():
    tb = _lm().TokenBatches(99, 64, 1024, 50304)
    toks = np.concatenate([tb(i) for i in range(4)])
    # a position continues its row's arithmetic run unless it is noise
    run_ = np.diff(toks, axis=1) == 1
    assert 0.68 < run_.mean() < 0.76      # (1 - 0.15)^2 = 0.7225


def test_teacher_data_repeats_for_a_seed():
    import jax
    pop = bench.load_module(bench.HERE / "adapters" / "population.py")
    key = bench.seed_key(2**33 + 1)
    x, t = pop.teacher_data(key, 2048, (64, 96, 48))
    x2, t2 = pop.teacher_data(key, 2048, (64, 96, 48))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t2))
    x, t = np.asarray(x), np.asarray(t)
    assert x.shape == (2048, 64) and t.shape == (2048, 48)
    assert abs(x.mean()) < 0.02 and abs(x.std() - 1) < 0.02
    assert 0 < t.min() and t.max() < 1
    x3, _ = pop.teacher_data(jax.random.fold_in(key, 1), 2048, (64, 96, 48))
    assert not np.array_equal(x, np.asarray(x3))


def test_sweep_grid_matches_traffic():
    cell = bench.find_cell("ffn-population-sweep")
    pop = bench.load_module(bench.HERE / "adapters" / "population.py")
    specs = pop.specs(cell.config, cell.traffic)
    assert len(specs) == len(cell.traffic["densities"]) * \
        len(cell.traffic["lrs"])
    assert [s.init_seed for s in specs] == list(range(len(specs)))
    tr = cell.traffic
    assert 0 < tr["check_steps_per_round"] < tr["steps_per_round"]
    assert set(tr["limits"]) == {"loss_gap", "eval_gap", "update_norm_gap",
                                 "rank_gap"}


def test_sweep_program_seed_follows_the_run_seed():
    drv = bench.driver_for(bench.find_cell("ffn-population-sweep"))
    seeds = [0, 7, 2**31 + 5, 2**40 + 5, 2**40 + 6]
    got = [drv.program_seed(s) for s in seeds]
    assert got == [drv.program_seed(s) for s in seeds]
    assert all(0 <= g < 2**31 for g in got)
    assert len(set(got)) == len(seeds)


def test_result_line_ends_with_checks(capsys):
    result = {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
              "device": {"platform": "tpu"},
              "checks": {"loss_gap": {"value": 1e-4, "limit": 1e-3}}}
    run.print_result(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1] == "correct: True"
    assert "loss_gap" in err


def test_serve_requests_repeat_sizes_and_state_their_distribution():
    cell = bench.find_cell("stablelm3b-serve-batch")
    drv = bench.driver_for(cell)
    tr = cell.traffic
    sizes = drv.request_sizes(tr)
    assert sizes == drv.request_sizes(tr)          # no seed in the sizes
    assert len(sizes) == tr["requests_per_call"]
    prompts = np.array([p for p, _ in sizes])
    outs = np.array([o for _, o in sizes])
    for arr, spec in ((prompts, tr["prompt"]), (outs, tr["output"])):
        assert arr.min() >= spec["min"] and arr.max() <= spec["max"]
        assert abs(np.median(arr) - spec["median"]) <= 0.05 * spec["median"]
        # stratified lognormal: log-lengths spread as stated (clipping
        # only shortens the tails)
        assert 0.6 * spec["sigma"] < np.log(arr).std() <= spec["sigma"]
    # the pairing is fixed and does not sort outputs with prompts
    assert abs(np.corrcoef(prompts, outs)[0, 1]) < 0.5
    assert max(p + o for p, o in sizes) <= tr["prompt"]["max"] + \
        tr["output"]["max"]


def test_serve_prompts_follow_the_seed():
    cell = bench.find_cell("stablelm3b-serve-batch")
    drv = bench.driver_for(cell)
    import types
    sess = types.SimpleNamespace(conf=cell.config,
                                 sizes=drv.request_sizes(cell.traffic))
    a = drv.Session.requests(sess, 2**40 + 1)
    b = drv.Session.requests(sess, 2**40 + 1)
    c = drv.Session.requests(sess, 2**40 + 2)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert all(x.arrival == 0 for x in a)
