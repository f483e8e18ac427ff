"""A kernel family's share of its roofline, in per cent: the least time
the chip could take for the work the window did, over the device-busy time
of that window.

The work is counted from the cell's shapes by a function of
benchmark/harness/counts.py named in the metric's file, with arguments
given as paths into the run; the bound is a peak of
benchmark/harness/peaks.py.  It divides by the window's whole device-busy
time, not one kernel's: in these cells nothing else runs on the device,
and a PR that replaces the kernel stays bounded by the same count.
Nothing where no device time or no work was read; never 0."""

from ..harness import counts
from ..harness.paths import lookup


def read(params: dict, run: dict):
    tr = run.get("trace")
    args = [lookup(run, a) for a in params["args"]]
    if not tr or not tr["busy_s"] or any(a is None for a in args):
        return None
    work = getattr(counts, params["count"])(*args)
    if not work:
        return None
    least_s = work / run["peaks"][params["peak"]]
    return 100.0 * least_s / tr["busy_s"]
