"""Self time of the program's spans of some name prefixes inside the
window, per unit of work and scaled: milliseconds of the event loop's
thread per op in the messenger, host milliseconds of readback per remap.
Self time is a span's duration less the spans nested in it, so a handler
that encodes and sends is charged for neither.  0 where the program
carries spans and none of these ran; nothing without a trace, without
spans in it, or without the unit."""

from ..harness import program_spans
from ..harness.paths import lookup


def read(params: dict, run: dict):
    spans = program_spans.spans_of(run)
    per = lookup(run, params["per"])
    if spans is None or not per:
        return None
    return (params.get("scale", 1)
            * program_spans.self_ns(spans, params["prefixes"]) / per)
