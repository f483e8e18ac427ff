"""The device's time under the program's own names: every operation of
the traced window with the name scopes it was traced under.

The program opens a scope ``rados.<name>`` around each stage of its
jitted programs (ceph_tpu/trace/span.py: ``scope``, the table
``SCOPES``); XLA keeps the scopes in an instruction's ``op_name``, and
the profiler writes that string into the trace file as the ``tf_op``
stat of the operation's *event metadata*.  ``jax.profiler.ProfileData``
shows an event's own stats only (on this chip: ``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``), and the device plane
has no ``Framework Name Scope`` line (its lines are ``XLA Modules``,
``XLA Ops``, ``Async XLA Ops``, ``TC Overlay``), so the file is read
with the profiler's own protobuf, ``xplane_pb2`` (it comes with the
installed tensorflow; nothing else of tensorflow is used).

A reader is handed no path, so the run's file is found as
``program_spans.newest`` finds it.  It is parsed once per process into
plain lists, and everything below ``parse`` works on those lists, so the
readers are checked on a hand-made list without a profiler.

    {"window": (t0_ns, t1_ns),
     "spans": [(name, start_ns, end_ns)],             # the harness's bench.*
     "planes": [[(instruction, start_ns, end_ns)]],   # one list a device
     "op_names": {instruction: op_name}}              # where the file has one

An instruction's **path** is the list of the program's scopes in its
``op_name``, outermost first, prefix dropped: ``jit(run)/rados.crush.
resolve.a/rados.crush.settle.draw/while/body/closed_call/rados.crush.
descend/...`` is ``("crush.resolve.a", "crush.settle.draw",
"crush.descend")``.  Time is self time (``trace.self_times``: a `while`
spans its body's operations on the same line and must not count them
twice), inside the window, averaged over the device planes.  A `while`
itself carries no ``op_name``: what it spends between its body's
operations has no path.  A trace of a program without scopes (the parent
of the PR that brought them) reduces to no scoped time, and every reader
then returns None.
"""

from . import program_spans
from . import trace as window_trace
from .trace import self_times, union

PREFIX = "rados."

_reduced: dict = {}     # path of the file -> scopes(); one run per process


def path_of(op_name: str) -> tuple:
    return tuple(part[len(PREFIX):] for part in op_name.split("/")
                 if part.startswith(PREFIX))


def parse(path: str) -> dict | None:
    """The file's device operations, their op_names and the window; None
    where the protobuf module is missing or the file holds no window."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    spans, planes, op_names = [], [], {}

    def times(line, ev):
        lo = line.timestamp_ns * 1000 + ev.offset_ps
        return lo // 1000, (lo + ev.duration_ps) // 1000

    for plane in space.planes:
        names = {i: md.name for i, md in plane.event_metadata.items()}
        if plane.name.startswith("/host:"):
            spans += [(names[ev.metadata_id], *times(line, ev))
                      for line in plane.lines for ev in line.events
                      if names.get(ev.metadata_id, "").startswith(
                          window_trace.SPAN_PREFIX)]
        elif plane.name.startswith("/device:TPU:"):
            tf_op = [i for i, sm in plane.stat_metadata.items()
                     if sm.name == "tf_op"]
            for md in plane.event_metadata.values():
                for stat in md.stats:
                    if stat.metadata_id in tf_op:
                        op_names[md.name] = (
                            stat.str_value
                            or plane.stat_metadata[stat.ref_value].name)
            kept = [ln for ln in plane.lines
                    if ln.name not in window_trace.SUMMARY_LINES]
            ops = [ln for ln in kept if ln.name == "XLA Ops"] or kept
            planes.append([(names[ev.metadata_id], *times(ln, ev))
                           for ln in ops for ev in ln.events])
    window = [(lo, hi) for name, lo, hi in spans
              if name == window_trace.WINDOW_SPAN]
    if not window:
        return None
    return {"window": window[-1], "spans": spans, "planes": planes,
            "op_names": op_names}


def reduce(parsed: dict) -> dict:
    """{"by_op": {(path, instruction): ns}, "busy_ns": ns}: self time
    inside the window by path and instruction, and the union of the
    window's device operations, both averaged over the planes."""
    t0, t1 = parsed["window"]
    by_op, busy = {}, 0
    n = max(len(parsed["planes"]), 1)
    for events in parsed["planes"]:
        clipped = [(name, max(lo, t0), min(hi, t1))
                   for name, lo, hi in events if min(hi, t1) > max(lo, t0)]
        busy += sum(hi - lo for lo, hi in union(
            [(lo, hi) for _name, lo, hi in clipped]))
        for name, ns in self_times(clipped).items():
            key = (path_of(parsed["op_names"].get(name, "")), name)
            by_op[key] = by_op.get(key, 0) + ns / n
    return {"by_op": by_op, "busy_ns": busy / n}


def scopes_of(run: dict) -> dict | None:
    """The reduced scopes of this run's trace, or None: an untraced run,
    no trace file, no window in it, no protobuf module, or a program
    whose operations carry no scope."""
    if not run.get("trace"):
        return None
    if "device_scopes" in run:          # a test's hand-made list
        scopes = reduce(run["device_scopes"])
    else:
        path = program_spans.newest()
        if path is None:
            return None
        if path not in _reduced:
            parsed = parse(path)
            _reduced[path] = reduce(parsed) if parsed else None
        scopes = _reduced[path]
    if not scopes or not any(p for p, _name in scopes["by_op"]):
        return None
    return scopes


def under_ns(scopes: dict, under: list, not_under: list = ()) -> float:
    """Self time of the operations whose path holds any scope of `under`
    and none of `not_under`; each operation once."""
    return sum(ns for (path, _name), ns in scopes["by_op"].items()
               if any(s in path for s in under)
               and not any(s in path for s in not_under))


def scoped_ns(scopes: dict) -> float:
    """Self time of the operations under any scope at all."""
    return sum(ns for (path, _name), ns in scopes["by_op"].items() if path)
