"""Work counters against numbers worked by hand for stablelm-3b's
published widths (d_model 2560, 32 heads x 80, d_ff 6912, vocab 50304)
at 8 layers, FFN junctions at density 0.25 in blocks of 128."""
from __future__ import annotations

import pytest

from chipbench import bench, peaks, work


def stablelm(layers=8):
    conf = bench.load_json(bench.HERE / "configs" /
                           "stablelm-3b-sparse-ffn.json")
    conf = dict(conf, num_hidden_layers=layers)
    return bench.load_module(bench.HERE / "adapters" / "dense_lm.py").shape(
        conf)


def test_fan_in_rounds_half_to_even():
    assert work.block_fan_in(20, 0.25) == 5
    assert work.block_fan_in(54, 0.25) == 14      # 13.5 -> 14
    assert work.block_fan_in(20, 0.125) == 2      # 2.5 -> 2
    assert work.block_fan_in(54, 0.125) == 7      # 6.75
    assert work.block_fan_in(54, 0.5) == 27
    assert work.block_fan_in(4, 0.01) == 1


def test_junction_products():
    gate = work.junction(2560, 6912, 0.25, 128)
    down = work.junction(6912, 2560, 0.25, 128)
    assert gate.weights == 54 * 5 * 128 * 128 == 4_423_680
    assert down.weights == 20 * 14 * 128 * 128 == 4_587_520
    assert gate.product_flops(1) == 8_847_360
    # one fwd over 8192 bf16 rows: x, W and y once each
    assert gate.product_bytes(8192, 2, 2) == 8192 * (2560 + 6912) * 2 \
        + 4_423_680 * 2


def test_stablelm_train_flops_per_token():
    s = stablelm()
    assert s.proj_flops_per_token() == 52_428_800
    assert s.ffn_flops_per_token() == 2 * 8_847_360 + 9_175_040
    # causal half of the scores: 4 * 32 * 80 * 1024 * 1025 / 2 a sequence
    assert s.causal_score_flops(1024) == 5_373_952_000
    fwd = s.forward_flops(1024)
    assert fwd == 8 * (1024 * (52_428_800 + 26_869_760) + 5_373_952_000) \
        + 1024 * 2 * 2560 * 50304
    per_token = s.train_flops_per_token(1024)
    assert per_token == pytest.approx(2_801_786_880)
    assert 2.79e9 < per_token < 2.81e9        # "about 2.8 GFLOP"


def test_junction_train_work_and_roofline():
    s = stablelm()
    flops, bytes_ = s.junction_train_work(8192, 2, 2)
    assert flops == 8 * 3 * 8192 * 26_869_760
    least, bound = peaks.least_time_s(flops, bytes_, peaks.peaks_for(
        "TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(flops / 197e12)


def test_population_member_step():
    shape = work.PopulationShape((work.junction(2560, 6912, 0.5, 128),
                                  work.junction(6912, 2560, 0.5, 128)))
    w1 = 54 * 10 * 128 * 128
    w2 = 20 * 27 * 128 * 128
    # the first junction reads the data: fwd and dw only
    assert shape.member_step_flops(512) == 2 * 512 * (2 * w1 + 3 * w2)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("n_in,n_out,density", [(2560, 6912, 0.25),
                                                (6912, 2560, 0.25),
                                                (6912, 2560, 0.5),
                                                (2560, 6912, 0.125)])
def test_benchmark_block_patterns(n_in, n_out, density):
    """Fixed fan-in, no input block twice per output block, fan-out
    balanced within one, and the reverse pattern lists every edge."""
    import numpy as np
    from chipbench import patterns
    p = patterns.block_pattern(n_in, n_out, density, 128, seed=3)
    idx = p["idx"]
    kb = work.block_fan_in(n_in // 128, density)
    assert idx.shape == (n_out // 128, kb)
    assert all(len(set(row)) == kb for row in idx)
    counts = np.bincount(idx.reshape(-1), minlength=n_in // 128)
    assert counts.max() - counts.min() <= 1
    np.testing.assert_array_equal(p["rev_cnt"], counts)
    for i in range(n_in // 128):
        for t in range(p["rev_cnt"][i]):
            assert idx[p["rev_ob"][i, t], p["rev_t"][i, t]] == i
    assert idx.size * 128 ** 2 == work.junction(n_in, n_out, density,
                                                128).weights
    np.testing.assert_array_equal(
        patterns.block_pattern(n_in, n_out, density, 128, seed=3)["idx"], idx)
