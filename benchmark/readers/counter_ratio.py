"""One count of the window over another, scaled: dispatches per op, CPU
milliseconds per op.  Nothing where either is missing or the divisor 0."""

from ..harness.paths import lookup


def read(params: dict, run: dict):
    num = lookup(run, params["numerator"])
    den = lookup(run, params["denominator"])
    if num is None or not den:
        return None
    return params.get("scale", 1) * num / den
