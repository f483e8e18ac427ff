#!/usr/bin/env python3
"""Run one cell with a fault of faults.py planted, on the chip at the cell's
own size; the result line must read `correct: false`.

    python3 benchmark/tests/control.py <fault> --workload <cell> --seed <n> --seconds <s> --trace 0

Not one of the benchmark's runs: BENCHMARK.json's command never calls it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402
from benchmark.tests import faults  # noqa: E402

if __name__ == "__main__":
    with faults.FAULTS[sys.argv[1]]():
        sys.exit(run.main(sys.argv[2:]))
