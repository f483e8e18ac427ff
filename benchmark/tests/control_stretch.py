#!/usr/bin/env python3
"""control.py for the stretch cluster's crush cell, on the chip at the
cell's own size, and the faults it plants (test_reference_stretch.py
plants them on the CPU at a tiny size).

    python3 benchmark/tests/control_stretch.py <fault> --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With a fault planted, the result line must read `correct: false`:

  stale_mapping   faults.py's control for the crush cells: every remap
                  hands out the previous epoch's table (a stale answer
                  where it was exact); mismatched_pgs.
  one_site        the rule's second block takes the first block's
                  datacenter: every row puts its four OSDs in site1,
                  twice the same two; site_violations and mismatched_pgs.

Not one of the benchmark's runs: BENCHMARK.json's command never calls it.
"""

import os
import sys
from contextlib import ExitStack

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tests.faults import _patched, stale_mapping  # noqa: E402
from benchmark.tests.faults_rules import _fresh_mappers  # noqa: E402


def one_site():
    from ceph_tpu.ops.crush import device
    real_plan = device.DeviceMapper._plan

    def plan(self, ruleno, result_max):
        """Every block after the first planned from the first's TAKE."""
        p = real_plan(self, ruleno, result_max)
        return p._replace(more=tuple(b._replace(take_id=p.take_id)
                                     for b in p.more))

    stack = ExitStack()
    stack.enter_context(_fresh_mappers())
    stack.enter_context(_patched(device.DeviceMapper, "_plan", plan))
    return stack


FAULTS = {f.__name__: f for f in (stale_mapping, one_site)}

if __name__ == "__main__":
    from benchmark import run
    with FAULTS[sys.argv[1]]():
        sys.exit(run.main(sys.argv[2:]))
