"""How much of a time base the program's spans cover, in per cent.

``base: window``: the union of the spans on the thread that held the
window (the event loop's) over the window: what is left is time of that
thread no span names.  ``base: device_idle``: the union of the spans, on
any thread, over the part of the window in which no operation ran on the
device: the share of the device's idle time that a named piece of host
work explains.  ``exclude`` drops spans in which the host only waits for
the device.  Nothing without a trace or without spans in it, or where
the base is empty."""

from ..harness import program_spans


def read(params: dict, run: dict):
    spans = program_spans.spans_of(run)
    if spans is None:
        return None
    skip = tuple(params.get("exclude", ()))
    if params["base"] == "window":
        evs = spans["lines"].get(spans["window_line"], [])
        base = [list(spans["window"])]
    else:
        evs = [ev for line in spans["lines"].values() for ev in line]
        base = program_spans.idle(spans)
    took = [(lo, hi) for name, lo, hi, _args in evs
            if name.startswith(tuple(params["prefixes"]))
            and name not in skip]
    whole = sum(hi - lo for lo, hi in base)
    if not whole:
        return None
    return 100.0 * program_spans.covered_ns(took, base) / whole
