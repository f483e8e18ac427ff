"""The program's own spans, read from the profiler's trace file.

The program writes a host span ``rados.<name>`` at each layer boundary
(ceph_tpu/trace/span.py) whenever a profiler session runs; the harness
runs one around the window of a ``--trace 1`` run.  A reader is handed no
path, so the run's file is found as ``trace.newest`` finds it: the newest
``*.xplane.pb`` under ``.bench_trace/*/``.  It is parsed once per process
(the crush cell's file is 57 MB) into plain lists, and everything below
``parse`` works on those lists, so the readers are checked on a hand-made
list without a profiler.

    {"window": (t0_ns, t1_ns),
     "window_line": line,                    # the thread that held bench.window
     "lines": {line: [(name, start_ns, end_ns, {arg: int})]},   # rados.* only
     "busy": [[lo_ns, hi_ns], ...]}          # union of device ops, clipped

Events are clipped to the window; one that starts outside it is not
counted as a mark.  A trace of a program without spans (the parent of the
PR that brought them) parses to no lines, and every reader then returns
None.
"""

import glob
import os

from . import trace as window_trace
from .trace import self_times, union

PREFIX = "rados."
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_parsed: dict = {}      # path -> spans(); one run per process


def newest(root: str = ROOT) -> str | None:
    paths = glob.glob(os.path.join(root, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def parse(path: str) -> dict | None:
    """The file's rados.* host events by thread, its window and the
    device's busy intervals; None where it holds no window span."""
    from jax.profiler import ProfileData
    lines, window, window_line, device = {}, None, None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            kept = [ln for ln in plane.lines
                    if ln.name not in window_trace.SUMMARY_LINES]
            ops = [ln for ln in kept if ln.name == "XLA Ops"] or kept
            device += [(int(ev.start_ns),
                        int(ev.start_ns + ev.duration_ns))
                       for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                line = "%s#%d" % (ln.name, i)
                for ev in ln.events:
                    if ev.name == window_trace.WINDOW_SPAN:
                        window = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                        window_line = line
                    elif ev.name.startswith(PREFIX):
                        lines.setdefault(line, []).append(
                            (ev.name, int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns),
                             {k: v for k, v in ev.stats
                              if isinstance(v, int)}))
    if window is None:
        return None
    return clip({"window": window, "window_line": window_line,
                 "lines": lines, "busy": device})


def clip(spans: dict) -> dict:
    """Keep what lies in the window, cut to it."""
    t0, t1 = spans["window"]
    lines = {}
    for line, events in spans["lines"].items():
        kept = [(name, max(lo, t0), min(hi, t1), args)
                for name, lo, hi, args in events if t0 <= lo < t1]
        if kept:
            lines[line] = kept
    busy = union([(max(lo, t0), min(hi, t1)) for lo, hi in spans["busy"]
                  if min(hi, t1) > max(lo, t0)])
    return {"window": (t0, t1), "window_line": spans["window_line"],
            "lines": lines, "busy": busy}


def spans_of(run: dict) -> dict | None:
    """The spans of this run's trace, or None: an untraced run, no trace
    file, no window in it, or a program that wrote no span."""
    if not run.get("trace"):
        return None
    if "program_spans" in run:          # a test's hand-made list
        spans = run["program_spans"]
    else:
        path = newest()
        if path is None:
            return None
        if path not in _parsed:
            _parsed[path] = parse(path)
        spans = _parsed[path]
    return spans if spans and spans["lines"] else None


def named(spans: dict, name: str) -> list:
    """(name, start, end, args) of every event of that name, all
    threads."""
    return [ev for evs in spans["lines"].values() for ev in evs
            if ev[0] == name]


def self_ns(spans: dict, prefixes: list) -> int:
    """Nanoseconds in which a span of ``prefixes`` ran and no span nested
    in it did, summed over threads."""
    if "self" not in spans:     # name -> ns, once for all the readers
        own: dict = {}
        for evs in spans["lines"].values():
            for name, ns in self_times(
                    [(name, lo, hi) for name, lo, hi, _a in evs]).items():
                own[name] = own.get(name, 0) + ns
        spans["self"] = own
    return sum(ns for name, ns in spans["self"].items()
               if name.startswith(tuple(prefixes)))


def covered_ns(intervals: list, base: list) -> int:
    """Nanoseconds of ``base`` (merged intervals) that ``intervals``
    cover."""
    total, merged, i = 0, union(intervals), 0
    for b0, b1 in base:         # both sorted and disjoint: one sweep
        while i < len(merged) and merged[i][1] <= b0:
            i += 1
        j = i
        while j < len(merged) and merged[j][0] < b1:
            total += min(merged[j][1], b1) - max(merged[j][0], b0)
            j += 1
    return total


def idle(spans: dict) -> list:
    """The window less the device's busy intervals."""
    t0, t1 = spans["window"]
    edges = [t0] + [e for iv in spans["busy"] for e in iv] + [t1]
    return [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
