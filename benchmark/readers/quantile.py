"""A quantile of the samples a driver kept over the window, scaled: the
95th percentile of the event loop's lateness, in milliseconds."""

from ..harness.paths import lookup
from ..harness.stats import pctl


def read(params: dict, run: dict):
    samples = lookup(run, params["samples"])
    if not samples:
        return None
    return params.get("scale", 1) * pctl(samples, params["quantile"])
