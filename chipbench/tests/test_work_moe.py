"""work_moe.py against hand arithmetic at the MoE training cell's sizes
(B=4 x S=8192, 7 layers of which 1 dense, 8 of 64 experts held, a
12,800-id head): about 24.5 TFLOP forward a step."""
from __future__ import annotations

import pytest

from chipbench import bench

CELL = "dsv2lite-train-fused-s8k"


@pytest.fixture(scope="module")
def shape():
    cell = bench.find_cell(CELL)
    ad = bench.load_module(bench.HERE / "adapters" / "moe_lm.py")
    return ad.shape(cell.config)


def test_junctions_at_the_published_widths(shape):
    gate, up, down = shape.expert
    assert (gate.n_in, gate.n_out, gate.kb) == (2048, 1408, 4)   # 4 of 16
    assert up == gate
    assert (down.n_in, down.n_out, down.kb) == (1408, 2048, 3)   # 3 of 11
    sg, si, so = shape.shared
    assert (sg.n_out, sg.kb, so.n_in, so.kb) == (2816, 4, 2816, 6)  # 6 of 22


def test_forward_parts_by_hand(shape):
    T, S, B = 32768, 8192, 4
    routed = 6 * 24576                 # 3/4 of each layer's 6 slots a token
    parts = shape.forward_parts(S, B, routed)
    # MLA: q 2048x3072, kv_a 2048x576, kv_b 512x(16*256), o 2048x2048
    proj = 2 * (2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048)
    assert parts["mla_proj"] == 7 * T * proj
    pairs = S * (S + 1) / 2
    assert parts["mla_scores"] == 7 * B * 2 * 16 * (192 + 128) * pairs
    assert parts["dense_ffn"] == T * 6 * 2048 * 10944
    assert parts["router"] == 6 * T * 2 * 2048 * 64
    shared = 2 * 128 * 128 * (22 * 4 * 2 + 16 * 6)
    assert parts["shared"] == 6 * T * shared
    per_row = 2 * 128 * 128 * (11 * 4 * 2 + 16 * 3)
    assert parts["routed"] == routed * per_row
    assert parts["head"] == T * 2 * 2048 * 12800
    fwd = shape.forward_flops(S, B, routed)
    assert fwd == pytest.approx(24.5e12, rel=0.01)
    assert shape.train_flops(S, B, routed) == 3 * fwd
    # MLA is most of the model's work, then the dense layer
    assert (parts["mla_proj"] + parts["mla_scores"]) / fwd == \
        pytest.approx(0.65, abs=0.01)
    assert parts["dense_ffn"] / fwd == pytest.approx(0.18, abs=0.01)


def test_expert_work_counts_routed_rows_not_padding(shape):
    f1, b1 = shape.expert_train_work(1000, 2, 2)
    f2, b2 = shape.expert_train_work(2000, 2, 2)
    assert f2 == 2 * f1
    per_row = sum(j.product_flops(1) for j in shape.expert)
    assert f1 == 3 * 1000 * per_row
    weights = sum(j.weights for j in shape.expert)
    # weights read once per product, layer, held expert and step
    assert b2 - b1 == 3 * 1000 * 2 * sum(j.n_in + j.n_out
                                         for j in shape.expert)
    assert b1 - 3 * 1000 * 2 * sum(j.n_in + j.n_out for j in shape.expert) \
        == 3 * 2 * weights * 6 * 8
