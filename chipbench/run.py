"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its deployment, its traffic, its
limits and its per-layer metrics are found by name from BENCHMARK.json
(see harness.py). The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (platform,
device kind, chip count, peak device memory), with ``--trace 1`` also
``breakdown``, and last ``compared``: each number compared with the plain
reference beside its limit. Without the TPU chips the cell asks for it
exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
