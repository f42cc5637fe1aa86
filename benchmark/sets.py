#!/usr/bin/env python3
"""Run one cell several times in one call and print the spread.

    python benchmark/sets.py --workload <name> --seeds 11,12,13,14,15,16 [--seconds S] [--trace 0|1]

Each run is the benchmark's own command (``command`` of BENCHMARK.json) in a
process of its own, one after another: this parent never touches JAX, so it
never holds the chip.  For every metric it prints the values, the median and
the spread the builder's instructions define: the distance between the first
and third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median.  A bound is about five times the widest spread over the cells.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated, one run each")
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--show", type=int, default=0,
                    help="also print the last N earlier lines of each run")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    seconds = args.seconds if args.seconds else bench["run_seconds"]
    rows, bad = [], 0
    for seed in (int(s) for s in args.seeds.split(",")):
        cmd = list(bench["command"]) + [
            "--workload", args.workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(args.trace)]
        out = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        lines = out.stdout.strip().splitlines()
        for line in lines[-1 - args.show:-1] if args.show else ():
            print(f"  [{seed}] {line}")
        try:
            result = json.loads(lines[-1])
        except (IndexError, ValueError):
            bad += 1
            print(f"seed {seed}: exit {out.returncode}, no result line\n"
                  f"{out.stderr[-2000:]}", flush=True)
            continue
        if not result["correct"] or result["failed"]:
            bad += 1
        rows.append(result)
        print(f"seed {seed}: correct={result['correct']} "
              f"attempted={result['attempted']} failed={result['failed']} "
              + " ".join(f"{k}={v['value']:.6g}"
                         for k, v in result["metrics"].items())
              + f" mem={result['device']['memory_peak_bytes']}", flush=True)
    names = sorted({k for r in rows for k in r["metrics"]})
    for name in names:
        values = [r["metrics"][name]["value"] for r in rows
                  if name in r["metrics"]]
        print(f"{name}: n={len(values)} median={statistics.median(values):.6g}"
              f" min={min(values):.6g} max={max(values):.6g} "
              f"spread(IQR/median)={spread(values):.4%}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
