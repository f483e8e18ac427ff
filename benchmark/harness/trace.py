"""The profiler window, and the reduction from its trace to numbers.

The harness opens the JAX profiler itself around the measured window of a
``--trace 1`` run.  ``load`` turns the ``.xplane.pb`` file into plain
lists of (name, start_ns, duration_ns); everything below it works on
those lists, so the reduction is checked on a small recorded trace
(benchmark/tests/data) without a chip.

Busy is the union of the intervals in which an operation ran on the
device; idle share is 1 - busy / window.  Idle gaps are named by the
innermost span of the harness's own ``bench.*`` annotations that covers
the gap's middle: coarse by design, until the program carries spans of
its own.
"""

import glob
import os
import re

# an op's name in the trace is its whole HLO line: "%run.1 = u32[3,131072]
# {...} custom-call(...), custom_call_target=..."; keep "%run.1 custom-call"
_OP_KIND = re.compile(r" ([a-z][a-z0-9-]*)\(")

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
# lines of a device plane that restate the ops line at another grain
SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code")


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # the host's Python frames are not read
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def span(name: str):
    """A host span of the harness in the profiler's own trace."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def newest(log_dir: str) -> str:
    """The newest trace file the profiler wrote under ``log_dir``."""
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise SystemExit("benchmark: the profiler wrote no trace under %s"
                         % log_dir)
    return paths[-1]


def load(log_dir: str) -> dict:
    """{"device": {plane: [(name, start_ns, dur_ns)]}, "spans": [...]}
    from the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(newest(log_dir))
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines
                     if ln.name not in SUMMARY_LINES]
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            device[plane.name] = [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            spans += [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ln in plane.lines for ev in ln.events
                      if ev.name.startswith(SPAN_PREFIX)]
    return {"device": device, "spans": spans}


def short(name: str) -> str:
    lhs, sep, rhs = name.partition(" = ")
    kind = _OP_KIND.search(" " + rhs) if sep else None
    return (lhs + " " + kind.group(1) if kind else name)[:80]


def union(intervals: list) -> list:
    """Merge (start, end) intervals that touch or overlap."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def self_times(events: list) -> dict:
    """name -> nanoseconds in which that operation ran and no operation
    nested in it did: a `while` spans its body's operations on the same
    line, and its children must not be counted twice.  `events` are
    (name, start, end), properly nested or disjoint."""
    out, stack = {}, []

    def close(until):
        while stack and stack[-1][2] <= until:
            name, lo, hi, inner = stack.pop()
            out[name] = out.get(name, 0) + (hi - lo) - inner
            if stack:
                stack[-1][3] += hi - lo

    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        close(lo)
        if stack:
            hi = min(hi, stack[-1][2])      # clock skew: keep it nested
        stack.append([name, lo, hi, 0])
    close(float("inf"))
    return out


def window_of(trace: dict) -> tuple:
    """(start_ns, end_ns) of the measured window: the harness's own span."""
    found = [(s, s + d) for name, s, d in trace["spans"]
             if name == WINDOW_SPAN]
    if not found:
        raise SystemExit("benchmark: no %s span in the trace" % WINDOW_SPAN)
    return found[-1]


def _label(spans: list, at: float) -> str:
    cover = [(d, name) for name, s, d in spans if s <= at <= s + d]
    return min(cover)[1] if cover else "untraced"


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s (averaged over the device planes), window_s, the operations
    that took most device time of their own and the longest idle gaps of
    the window."""
    t0, t1 = window_of(trace)
    busy, per_op, gaps = [], {}, []
    for events in trace["device"].values():
        clipped = [(name, max(s, t0), min(s + d, t1))
                   for name, s, d in events if min(s + d, t1) > max(s, t0)]
        for name, ns in self_times(clipped).items():
            per_op[short(name)] = per_op.get(short(name), 0) + ns
        merged = union([(lo, hi) for _name, lo, hi in clipped])
        busy.append(sum(hi - lo for lo, hi in merged))
        edges = [t0] + [e for iv in merged for e in iv] + [t1]
        gaps += [(edges[i + 1] - edges[i], (edges[i] + edges[i + 1]) / 2)
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = max(len(busy), 1)
    return {
        "planes": len(busy),
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "device_ops": [[name, ns / n / 1e9] for name, ns in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(trace["spans"], mid), ns / 1e9]
                      for ns, mid in sorted(gaps, reverse=True)[:top]],
    }
