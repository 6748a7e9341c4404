"""The expert-layer training cell with the timed path broken underneath
must come out not correct: the harness's own run (``run.run_cell``) at
a size a CPU test holds, with the fault planted in the program.  The
cell's limits are set for its own size, where bfloat16 rounding reads
lower than at this one, so each fault must fail a check that the sound
run at this size passes."""
from __future__ import annotations

import pytest

from chipbench import bench, run
from chipbench.tests import faults_moe, tiny_moe

bench.use_program_sources()

# A cell in which held experts go without rows: 8 tokens a step, each to
# one of 8 experts, so an expert routed to in one check step is left out
# in another (what the skipped-update fault needs to show).
SPARSE_ROWS = {"num_experts_per_tok": 1, "n_routed_experts": 8}


def _run(**conf):
    import jax
    cell = tiny_moe.tiny_cell(**conf)
    if conf:
        cell.traffic.update({"batch": 1, "seq": 8})
    return run.run_cell(cell, seed=2**34 + 29, seconds=0.2, trace=False,
                        devices=jax.devices()[:1])


def _passed(result):
    return {k for k, c in result["checks"].items()
            if c["value"] <= c["limit"]}


@pytest.fixture(scope="module")
def sound():
    return _run()


def _modules():
    from repro.kernels import block_sparse_matmul as bsm
    from repro.models import attention, moe
    return moe, attention, bsm


def test_sound_run_passes_the_size_free_checks(sound):
    assert {"grad_diff", "update_norm_gap", "dropped_rows",
            "window_compiles"} <= _passed(sound), sound["checks"]
    assert sound["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["topk_renormalised",
                                   "yarn_mscale_left_out",
                                   "experts_at_wrong_offset"])
def test_fault_is_caught(fault, sound, monkeypatch):
    moe, attention, _ = _modules()
    if fault == "yarn_mscale_left_out":
        monkeypatch.setattr(attention, "mla_rope",
                            faults_moe.yarn_mscale_left_out(attention))
    elif fault == "topk_renormalised":
        monkeypatch.setattr(moe, "route", faults_moe.topk_renormalised(moe))
    else:
        monkeypatch.setattr(moe, "dispatch_index",
                            faults_moe.experts_at_wrong_offset(moe))
    result = _run()
    assert not result["correct"], result["checks"]
    assert _passed(sound) - _passed(result), result["checks"]


def test_update_skipped_without_rows_is_caught(monkeypatch):
    _, _, bsm = _modules()
    sound = _run(**SPARSE_ROWS)
    assert {"update_norm_gap", "dropped_rows"} <= _passed(sound), \
        sound["checks"]
    dw, gated = faults_moe.update_skipped_without_rows(bsm)
    monkeypatch.setattr(bsm, "update_dw", dw)
    monkeypatch.setattr(bsm, "update_gated_dw", gated)
    result = _run(**SPARSE_ROWS)
    assert not result["correct"], result["checks"]
    assert _passed(sound) - _passed(result), result["checks"]


def test_control_fails_a_limit_the_program_keeps(sound):
    """The reference in float8 (the traffic file's control) against the
    reference fails one of the cell's limits that the program's sound run
    passes."""
    cell = tiny_moe.tiny_cell()
    drv = bench.driver_for(cell)
    sess = drv.Session(cell)
    seed = 2**34 + 29
    cmp = drv.train.compare(sess.reference(seed, lowp=cell.traffic["control"]),
                            sess.reference(seed))
    limits = cell.traffic["limits"]
    failed = {k for k in limits if k in cmp and cmp[k] > limits[k]}
    assert failed & _passed(sound), (cmp, sound["checks"])
