#!/usr/bin/env python3
"""What the program's spans leave uncovered: the self time of every span
name in the window, and the time on the window's thread between spans,
grouped by the pair of top-level spans it lies between and split at 0.2 ms
(shorter: the loop is busy between two callbacks; longer: it may be
asleep).  Reads the newest trace under .bench_trace, or the given cell's.

    python3 benchmark/tests/span_gaps.py [cell] [rows]

Holds no chip: it only reads the profiler's file.
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import program_spans  # noqa: E402
from benchmark.harness.trace import newest, union  # noqa: E402

SHORT_NS = 200_000


def main(cell: str | None = None, rows: int = 12) -> None:
    path = (newest(os.path.join(program_spans.ROOT, ".bench_trace", cell))
            if cell else program_spans.newest())
    spans = program_spans.parse(path) if path else None
    if spans is None:
        raise SystemExit("span_gaps: no trace with a window under "
                         ".bench_trace")
    t0, t1 = spans["window"]
    loop = sorted(spans["lines"].get(spans["window_line"], []),
                  key=lambda e: (e[1], -e[2]))
    program_spans.self_ns(spans, [program_spans.PREFIX])
    tops, end = [], t0
    for name, lo, hi, _args in loop:
        if lo >= end:
            tops.append((name, lo, hi))
            end = hi
    gaps = {True: collections.Counter(), False: collections.Counter()}
    count = collections.Counter()
    for (a, _lo, hi), (b, lo, _hi) in zip(tops, tops[1:]):
        short = lo - hi < SHORT_NS
        gaps[short][a, b] += lo - hi
        count[short, a, b] += 1
    covered = sum(hi - lo for lo, hi in union(
        [(lo, hi) for _n, lo, hi, _a in loop]))
    idle = program_spans.idle(spans)
    args: dict = {}
    for evs in spans["lines"].values():
        for name, _lo, _hi, given in evs:
            at = args.setdefault(name, collections.Counter(events=0))
            at["events"] += 1
            at.update(given)
    out = {
        "trace": path, "window_s": (t1 - t0) / 1e9,
        "host_events": sum(len(v) for v in spans["lines"].values()),
        "threads": {ln: len(v) for ln, v in spans["lines"].items()},
        "covered_s": covered / 1e9,
        "short_gaps_s": sum(gaps[True].values()) / 1e9,
        "long_gaps_s": sum(gaps[False].values()) / 1e9,
        "device_idle_s": sum(hi - lo for lo, hi in idle) / 1e9,
        "device_idle_gaps": len(idle),
        "args_sum": args,
        "self_ms": {n: ns / 1e6 for n, ns in sorted(
            spans["self"].items(), key=lambda kv: -kv[1])},
        "short_gaps_ms": [[a, b, ns / 1e6, count[True, a, b]]
                          for (a, b), ns in gaps[True].most_common(rows)],
        "long_gaps_ms": [[a, b, ns / 1e6, count[False, a, b]]
                         for (a, b), ns in gaps[False].most_common(rows)],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main(*sys.argv[1:2], *map(int, sys.argv[2:3]))
