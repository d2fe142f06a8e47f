#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 tnn_bench/run.py --workload proto-train --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic and metrics are read from
``BENCHMARK.json`` at the checkout's root (see ``tnn_bench/tnnbench/harness.py``).
The last line of standard output is the result as one JSON object.
"""
import sys
import time

T_PROCESS = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from tnnbench import harness

    sys.exit(harness.main(sys.argv[1:], T_PROCESS))
