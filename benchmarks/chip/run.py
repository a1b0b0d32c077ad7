#!/usr/bin/env python3
"""On-chip benchmark: run one cell once, print one JSON result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.  See ``chipbench/harness.py``.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
