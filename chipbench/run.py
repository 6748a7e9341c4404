#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: load the cell's files by name (``bench.find_cell``),
check for the chip, build the system under test with weights and inputs
made from ``--seed``, warm up every shape the window uses (set-up), run
the measured window for ``--seconds``, then check what the timed path
produced against the configuration's plain reference.

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` (with ``busy_s`` and ``window_s`` from the profiler's trace
when traced), ``breakdown`` when traced, and last ``checks``: each
number compared with its limit.  The same numbers end standard error.

With no TPU, too few chips, or a chip missing from ``peaks.py`` it
prints no result and exits 3.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import bench  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell: bench.Cell, seed: int, seconds: float, trace: bool,
             devices, process_start: float = PROCESS_START) -> dict:
    """Everything after the chip check.  Returns the result object."""
    from chipbench import peaks as peaks_mod
    kind = devices[0].device_kind
    peaks = peaks_mod.peaks_for(kind) if devices[0].platform == "tpu" else None
    driver = bench.driver_for(cell)
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        ctx = bench.Context(cell=cell, seed=seed, seconds=seconds,
                             trace=trace, trace_dir=trace_dir,
                             devices=devices, peaks=peaks,
                             meter=bench.CompileMeter(),
                             process_start=process_start)
        out = driver.run(ctx)
        summary = None
        if trace:
            from chipbench import trace as trace_mod
            summary = trace_mod.load(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    if trace:
        lctx = bench.LayerContext(summary=summary, counters=out.counters,
                                  peaks=peaks, cell=cell)
        for m in cell.per_layer:
            v = bench.reader_for(m["name"]).read(lctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(out.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": int(out.memory_peak_bytes)}
    result = {"correct": all(c.ok for c in out.checks),
              "attempted": int(out.attempted), "failed": int(out.failed),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})"
              f"{'' if _ok(c) else '  FAILED'}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result, allow_nan=True), flush=True)


def _ok(c):
    return math.isfinite(c["value"]) and c["value"] <= c["limit"]


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = bench.find_cell(args.workload)
        bench.prepare_jax()
        devices = bench.chip_devices(cell.chips)
    except bench.HarnessError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
