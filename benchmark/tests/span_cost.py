#!/usr/bin/env python3
"""What a span of the program costs with no profiler running, on this
host's CPU: microseconds for a call that does one addition, bare, inside
`with span(name)` and inside `with span(name, bytes=n)`, and for a mark.
Best of five loops.  Imports JAX and touches no device computation.

    python3 benchmark/tests/span_cost.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import ceph_tpu  # noqa: E402,F401
from ceph_tpu.trace.span import mark, span  # noqa: E402

N = 300_000


def bare(x):
    return x + 1


def in_span(x):
    with span("msgr.dispatch"):
        return x + 1


def in_span_with_arg(x):
    with span("msgr.write", bytes=x):
        return x + 1


def a_mark(x):
    mark("client.resend", age_us=x)


def best_us(fn) -> float:
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        for i in range(N):
            fn(i)
        best = min(best, (time.perf_counter() - t) / N * 1e6)
    return best


if __name__ == "__main__":
    print(json.dumps({fn.__name__ + "_us": best_us(fn) for fn in (
        bare, in_span, in_span_with_arg, a_mark)}))
