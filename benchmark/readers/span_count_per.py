"""Marks of one name inside the window per unit of work: resends per op,
sub-op timeouts per op.  A mark is a span entered and left at once where
the counted thing happens.  0 where the program carries spans and the
mark never fired; nothing without a trace, without spans in it, or
without the unit."""

from ..harness import program_spans
from ..harness.paths import lookup


def read(params: dict, run: dict):
    spans = program_spans.spans_of(run)
    per = lookup(run, params["per"])
    if spans is None or not per:
        return None
    return len(program_spans.named(spans, params["name"])) / per
