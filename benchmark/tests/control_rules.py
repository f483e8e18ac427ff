#!/usr/bin/env python3
"""control.py for the two-step crush cell, on the chip at the cell's own
size.

    python3 benchmark/tests/control_rules.py <fault> --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With a fault of faults_rules.py planted, the result line must read
`correct: false` (`chip_runs.py`'s `:fault` form only knows faults.py).

Not one of the benchmark's runs: BENCHMARK.json's command never calls it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402
from benchmark.tests import faults_rules  # noqa: E402

if __name__ == "__main__":
    with faults_rules.FAULTS[sys.argv[1]]():
        sys.exit(run.main(sys.argv[2:]))
