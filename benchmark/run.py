#!/usr/bin/env python3
"""The benchmark's entry point: one cell, one process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything it does is in ``benchmark/harness/main.py``; everything that belongs
to one configuration, traffic mix, driver or metric is a file of its own that
the harness finds by the name in ``BENCHMARK.json`` (``benchmark/README.md``).
"""
import os
import sys
import time

T_START = time.perf_counter()  # set-up is counted from here

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                   # harness, flops
sys.path.insert(1, os.path.dirname(HERE))  # the program under test

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main.main(sys.argv[1:], t_start=T_START))
