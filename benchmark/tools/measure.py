#!/usr/bin/env python3
"""Run one cell several times, one new process a run, and report the spread.

    python3 benchmark/tools/measure.py --cell telemetry-1m.saturate \
        --seeds 11,2024,99991 --sets 2 --seconds 20 --trace 0 --out chiprun_out/sat.jsonl

Each set runs every seed once, in order; the sets use the same seeds. The
spread of a metric is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, per
set; the bound rule of the builder's contract takes the wider of the
two. Never touches JAX itself: each run holds the chip alone.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", default="20")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", required=True)
    ap.add_argument("--extra", default="", help="further arguments to run.py")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a", encoding="utf-8") as log:
        for n_set in range(args.sets):
            for seed in seeds:
                t = time.monotonic()
                proc = subprocess.run(
                    [sys.executable, "benchmark/run.py", "--workload", args.cell,
                     "--seed", str(seed), "--seconds", args.seconds,
                     "--trace", args.trace, *args.extra.split()],
                    cwd=ROOT, capture_output=True, text=True,
                )
                wall = time.monotonic() - t
                lines = proc.stdout.strip().splitlines()
                try:
                    line = json.loads(lines[-1])
                except (IndexError, ValueError):
                    line = {"correct": None, "metrics": {}}
                row = {"set": n_set, "seed": seed, "rc": proc.returncode,
                       "wall_s": round(wall, 1), **line}
                if not line.get("correct"):
                    row["stderr_tail"] = proc.stderr[-3000:]
                rows.append(row)
                log.write(json.dumps(row) + "\n")
                log.flush()
                short = {k: round(v["value"], 4) for k, v in line.get("metrics", {}).items()}
                print(f"set {n_set} seed {seed} rc {proc.returncode} correct "
                      f"{line.get('correct')} wall {wall:.0f}s {short} "
                      f"{line.get('compared')}", flush=True)
    names = sorted({k for r in rows for k in r.get("metrics", {})})
    for name in names:
        per_set = []
        for n_set in range(args.sets):
            vals = [r["metrics"][name]["value"] for r in rows
                    if r["set"] == n_set and name in r.get("metrics", {})]
            if vals:
                per_set.append((statistics.median(vals), spread(vals), min(vals), max(vals)))
        print(name, " | ".join(
            f"median {m:.6g} spread {s if s is None else round(100 * s, 3)}% "
            f"min {lo:.6g} max {hi:.6g}" for m, s, lo, hi in per_set
        ), flush=True)
    bad = [r for r in rows if not r.get("correct")]
    print(f"{len(rows)} runs, {len(bad)} not correct")


if __name__ == "__main__":
    main()
