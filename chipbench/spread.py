#!/usr/bin/env python3
"""Repeat a cell's runs and report how widely they spread (not part of a
benchmark run; for setting bounds).

    python chipbench/spread.py --workload <cell> --seeds 11,12,13,14,15,16 \\
        --sets 2 --seconds 30 [--trace 0] --out <file>.jsonl

Runs ``chipbench/run.py`` once per seed, in turn, in child processes (this
parent never touches JAX, so each child has the chip), ``--sets`` times
over the same seeds.  Keeps every result line, and prints for each set
and metric the median and the spread: the distance between the first and
third quartiles (``statistics.quantiles(values, n=4)``) as a share of the
median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q3 - q1) / abs(med) if med else float("inf")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", default="30")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [s for s in args.seeds.split(",") if s]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    sets = []
    with out.open("a") as f:
        for k in range(args.sets):
            rows = []
            for s in seeds:
                p = subprocess.run(
                    [sys.executable, str(RUN), "--workload", args.workload,
                     "--seed", s, "--seconds", args.seconds, "--trace",
                     args.trace], capture_output=True, text=True)
                lines = p.stdout.strip().splitlines()
                try:
                    res = json.loads(lines[-1])
                except (IndexError, json.JSONDecodeError):
                    res = {"error": p.returncode, "stderr": p.stderr[-2000:]}
                res.update(set=k, seed=s, rc=p.returncode)
                f.write(json.dumps(res) + "\n")
                f.flush()
                rows.append(res)
                tail = " ".join(p.stderr.strip().splitlines()[-6:])
                print(f"set {k} seed {s} rc {p.returncode}: "
                      f"{json.dumps(res.get('metrics'))} correct="
                      f"{res.get('correct')} | {tail[-600:]}", flush=True)
            sets.append(rows)
    for k, rows in enumerate(sets):
        ok = [r for r in rows if "metrics" in r]
        names = sorted({m for r in ok for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(f"set {k} {m}: median {med!r} spread {sp!r} "
                      f"(n={len(vals)})")
        print(f"set {k} correct: {[r.get('correct') for r in rows]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
