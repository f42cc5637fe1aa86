#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the cell's chips.  It has no CPU mode: where JAX
finds no TPU, another number of chips than the cell asks for, or a
``device_kind`` that ``benchmark/peaks.json`` does not list, it exits
non-zero and prints no result.  The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
with ``--trace 1``, ``breakdown``.
"""

import time

_T_START = time.perf_counter()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def main(argv=None, with_control: bool = False) -> int:
    """``with_control`` is ``readings.py``'s: one argument more, which
    ``run.py`` itself does not take."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    if with_control:
        ap.add_argument("--control", default="bf16")
    args = ap.parse_args(argv)

    import harness

    cell = harness.load_cell(args.workload)
    try:
        _, result = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), _T_START,
            control=args.control if with_control else None)
    except harness.NoDevice as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    # The verdict is the line's ``correct``; a run that printed its result
    # ran to its end.
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
