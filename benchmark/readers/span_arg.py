"""An integer argument of the program's spans of one name, over the
window: a quantile of it (p95 of an op's queue wait), its sum over the
spans' own duration (bytes per nanosecond of blocking dispatch: GB/s), or
its sum over the sum of another argument (lanes that ran in Pallas over
lanes launched).  Scaled.  Nothing without a trace, a span of that name
carrying the argument, or a divisor."""

from ..harness import program_spans
from ..harness.stats import pctl


def read(params: dict, run: dict):
    spans = program_spans.spans_of(run)
    if spans is None:
        return None
    evs = [ev for ev in program_spans.named(spans, params["name"])
           if all(a in ev[3] for a in params["args"])]
    if not evs:
        return None
    values = [sum(ev[3][a] for a in params["args"]) for ev in evs]
    scale = params.get("scale", 1)
    if "quantile" in params:
        return scale * pctl(values, params["quantile"])
    if "over" in params:
        den = sum(ev[3].get(params["over"], 0) for ev in evs)
    else:
        den = sum(hi - lo for _name, lo, hi, _args in evs)
    return scale * sum(values) / den if den else None
