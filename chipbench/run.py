#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A fresh process on the first chip: set-up (patterns, analysis through the
plan cache, inputs from the seed, warm-up of the cell's own shapes), then a
measured window of ``--seconds``, then the check against the host float64
reference.  The last line of standard output is one JSON object; the
numbers compared are its last key and the last lines of standard error.
Exits nonzero, printing no result, without an accelerator or the solver.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
