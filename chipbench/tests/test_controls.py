"""The control of each cell's check: the plain reference put in the
program's place and computed in the precision below the configuration's
(``control`` in the traffic file, float8 with one scale per tensor for
these bfloat16 configurations) must fail at least one of the cell's
limits.  Here at a size a CPU test holds; on the chip at the cell's own
size (``calibrate.py``)."""
from __future__ import annotations

import gc

from chipbench import bench
from chipbench.tests import tiny


def _fails(cmp, limits):
    return {k: cmp[k] for k in limits if cmp[k] > limits[k]}


def test_train_control_fails_a_limit_the_program_keeps():
    """The cell's limits are set for its own size; at this size the
    control has to fail one that the program's sound run passes."""
    bench.use_program_sources()
    cell = tiny.tiny_cell("stablelm3b-train-fused")
    drv = bench.driver_for(cell)
    seed = 2**36 + 1
    r = {kind: fn() for kind, _, fn in drv.calibration(cell, [seed], [seed])}
    limits = cell.traffic["limits"]
    assert set(_fails(r["control"], limits)) - set(_fails(r["sound"], limits)), r


def test_sweep_control_fails_a_limit():
    bench.use_program_sources()
    cell = tiny.tiny_cell("ffn-population-sweep")
    drv = bench.driver_for(cell)
    sess = drv.Session(cell)
    seed = 2**36 + 2
    data = sess.data(seed)
    got = sess.claims(seed, sess.checked_sweep(seed, data))
    gc.collect()
    want = sess.reference(seed, data, got["pruned"])
    low = sess.reference(seed, data, got["pruned"],
                         lowp=cell.traffic["control"])
    cmp = drv.compare(low, want)
    assert _fails(cmp, cell.traffic["limits"]), cmp


def test_serve_control_fails_a_limit():
    bench.use_program_sources()
    cell = tiny.tiny_cell("stablelm3b-serve-batch")
    drv = bench.driver_for(cell)
    readings = {kind: fn() for kind, _, fn in
                drv.calibration(cell, [2**36 + 3], [2**36 + 3])}
    limit = cell.traffic["limits"]["logit_gap"]
    assert readings["sound"]["logit_gap"] <= limit, readings
    assert readings["control"]["logit_gap"] > limit, readings
