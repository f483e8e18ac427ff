"""Order statistics of the window's samples.  ``pctl`` is the arithmetic
of ceph_tpu/testing/traffic.py ``pctl_ms`` (nearest rank, upper), copied
so that a later PR cannot change the yardstick."""

import statistics


def pctl(samples: list, p: float) -> float | None:
    """p-quantile of ``samples`` by nearest rank; None when empty."""
    if not samples:
        return None
    s = sorted(samples)
    return s[min(len(s) - 1, int(p * len(s)))]


def spread(values: list) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the contract's rule for a bound takes it."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
