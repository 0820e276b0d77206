#!/usr/bin/env python
"""One run of one cell of ``BENCHMARK.json``::

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with the chips the cell asks for: anything else exits non-zero
and prints no result (there is no CPU fallback).  The last line of stdout is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``.  Every other number is
on an earlier line.  ``benchmarks/harness.py`` says where a cell's files are.
"""

import time

T_START = time.perf_counter()      # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
