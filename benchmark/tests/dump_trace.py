#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, event counts and names; and
cut a small recording for benchmark/tests/data.

    python3 benchmark/tests/dump_trace.py .bench_trace/<cell> out.json [seconds]

The recording keeps the harness's spans and the device events of the first
`seconds` of the measured window, in the shape harness/trace.py `load`
returns.  Holds no chip: it only reads the profiler's file.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import trace  # noqa: E402


def main(log_dir: str, out: str, seconds: float = 2.0) -> None:
    from jax.profiler import ProfileData
    path = trace.newest(log_dir)
    print("trace", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("plane", repr(plane.name))
        for ln in plane.lines:
            events = list(ln.events)
            names = {}
            for ev in events:
                names[ev.name] = names.get(ev.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:6]
            span = ((min(e.start_ns for e in events),
                     max(e.start_ns + e.duration_ns for e in events))
                    if events else None)
            print("  line %r: %d events, %s, %s"
                  % (ln.name, len(events), span, top))
    tr = trace.load(log_dir)
    t0, t1 = trace.window_of(tr)
    cut = t0 + int(seconds * 1e9)
    print("window", t0, t1, (t1 - t0) / 1e9)
    print("reduced", json.dumps(trace.reduce(tr)))
    rec = {"device": {p: [e for e in evs if t0 <= e[1] < cut]
                      for p, evs in tr["device"].items()},
           "spans": [(n, s, min(d, cut - s)) for n, s, d in tr["spans"]
                     if s < cut and s + d > t0]}
    with open(out, "w") as f:
        json.dump(rec, f)
    print("recorded", out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *map(float, sys.argv[3:4]))
