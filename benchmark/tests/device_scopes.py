#!/usr/bin/env python3
"""The device's time of one traced run under the program's own names:
self time inside the window by scope path and operation kind, in ms per
unit of work (a remap, where the harness spanned them; else the whole
window), largest first, then the sums by outermost and by innermost
scope.  Reads the newest trace under .bench_trace, or the given cell's.

    python3 benchmark/tests/device_scopes.py [cell] [rows]

Holds no chip: it only reads the profiler's file.
"""

import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import device_scopes, program_spans  # noqa: E402
from benchmark.harness.trace import newest, short  # noqa: E402

UNIT_SPAN = "bench.remap.mapping"


def kind(instruction: str) -> str:
    """`%crush_straw2_descend.72 custom-call` -> `crush_straw2_descend`,
    `%fusion.6255 fusion` -> `fusion`: XLA's numbering changes with every
    edit of the program, the kind does not."""
    name, _, opcode = short(instruction).partition(" ")
    name = re.sub(r"[.\d]+$", "", name.lstrip("%"))
    return name if opcode == "custom-call" else (opcode or name)


def main(cell: str | None = None, rows: int = 40) -> None:
    path = (newest(os.path.join(program_spans.ROOT, ".bench_trace", cell))
            if cell else program_spans.newest())
    parsed = device_scopes.parse(path) if path else None
    if parsed is None:
        raise SystemExit("device_scopes: no trace with a window under "
                         ".bench_trace, or no xplane_pb2 to read it")
    reduced = device_scopes.reduce(parsed)
    t0, t1 = parsed["window"]
    per = sum(1 for name, lo, _hi in parsed["spans"]
              if name == UNIT_SPAN and t0 <= lo < t1) or 1
    table, outer, inner = (collections.Counter() for _ in range(3))
    for (p, name), ns in reduced["by_op"].items():
        ms = ns / 1e6 / per
        table["/".join(p) or "-", kind(name)] += ms
        outer[p[0] if p else "-"] += ms
        inner[p[-1] if p else "-"] += ms
    out = {
        "trace": path, "bytes": os.path.getsize(path),
        "window_s": (t1 - t0) / 1e9, "units": per,
        "operations": sum(len(evs) for evs in parsed["planes"]),
        "instructions": len(parsed["op_names"]),
        "busy_ms": reduced["busy_ns"] / 1e6 / per,
        "scoped_ms": device_scopes.scoped_ns(reduced) / 1e6 / per,
        "by_outermost_ms": outer.most_common(),
        "by_innermost_ms": inner.most_common(),
        "by_path_and_kind_ms": [[p, k, ms] for (p, k), ms
                                in table.most_common(rows)],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main(*sys.argv[1:2], *map(int, sys.argv[2:3]))
