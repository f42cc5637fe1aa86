#!/usr/bin/env python3
"""Read the numbers a limit is set from, on the chip: one run of a cell
that also computes the lower-precision control's numbers.

    python benchmark/readings.py --workload <name> --seed <n> --seconds <s> [--trace 0|1] [--control bf16]

It is ``run.py`` with one thing more: after the comparison with the plain
reference it puts the control (``reference.py``: the reference with every
stored value rounded to the named precision) in the program's place and
prints what it reads beside each limit, on earlier lines.  The control has
to fail at least one number of every cell.  The benchmark's own runs do not
run the control; limits change only by steps 4 and 5 of "How correct is
decided", from these readings (PERF.md lists them).
"""

import sys

import run

if __name__ == "__main__":
    sys.exit(run.main(with_control=True))
