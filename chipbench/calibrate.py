#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from (not part of
a benchmark run).

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 101,102,103 [--out <file>.json]

For every seed of ``--seeds``: the numbers the cell's check compares,
between the program (as a run drives it through set-up) and the plain
reference: the lower readings.  For every seed of ``--control-seeds``:
the same numbers between the reference in the control's precision
(``control`` in the traffic file) and the reference, and between each
planted fault of the cell's kind and the reference: the upper readings.
All in one process on one chip; the program's compiled step is built
once.  Prints one JSON line per reading and writes them all to
``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import bench  # noqa: E402


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None, cell=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if cell is None:
        cell = bench.find_cell(args.workload)
        bench.prepare_jax()
        try:
            bench.chip_devices(cell.chips)
        except bench.HarnessError as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 3
    driver = bench.driver_for(cell)
    rows = []

    def emit(kind, seed, reading, t0):
        row = {"kind": kind, "seed": seed, "s": time.time() - t0,
               **{k: v for k, v in reading.items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for kind, seed, reading_fn in driver.calibration(cell, args.seeds,
                                                     args.control_seeds):
        t0 = time.time()
        emit(kind, seed, reading_fn(), t0)
        gc.collect()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
