#!/usr/bin/env python3
"""Chip benchmark of tuned serving and of the time to a tuned table.

    python3 benchmarks/chip/run.py --workload NAME --seed N --seconds S --trace 0|1

Run from the root of a checkout on a machine with a TPU.  ``NAME`` is a
workload of ``BENCHMARK.json``.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``compared``:
each number that decided ``correct`` with its limit); the compared numbers
are also the last lines of standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import os
import sys
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the TPU runtime logs to a fixed directory unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
