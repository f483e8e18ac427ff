"""The sum of an integer argument over the program's spans of one name
inside the window, per unit of work and scaled: MiB of shard fetched per
read.  0 where the program carries spans and none of that name ran;
nothing without a trace, without spans in it, or without the unit."""

from ..harness import program_spans
from ..harness.paths import lookup


def read(params: dict, run: dict):
    spans = program_spans.spans_of(run)
    per = lookup(run, params["per"])
    if spans is None or not per:
        return None
    total = sum(ev[3].get(params["arg"], 0)
                for ev in program_spans.named(spans, params["name"]))
    return params.get("scale", 1) * total / per
