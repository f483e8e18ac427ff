#!/usr/bin/env python3
"""Where the window's thread is when no span of the program covers it: run
one cell untraced with a side thread that reads the main thread's stack
every few milliseconds while the window is open, and count the samples by
(innermost frame, innermost frame of the program).  A diagnosis for the
share of the loop's time that `loop_span_cover_pct` leaves uncovered; its
numbers are sample counts, never a metric.

    python3 benchmark/tests/loop_sampler.py out.json --workload <cell> --seed <n> --seconds <s>
"""

import collections
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness.session import Session  # noqa: E402

EVERY_S = 0.003


def where(frame) -> tuple:
    """(innermost "file:function", innermost one of the program)."""
    def tag(f):
        path = f.f_code.co_filename
        return "%s:%s" % (os.path.relpath(path, ROOT)
                          if path.startswith(ROOT)
                          else "/".join(path.split("/")[-2:]),
                          f.f_code.co_name)
    inner, f = tag(frame), frame
    while f is not None and not f.f_code.co_filename.startswith(
            os.path.join(ROOT, "ceph_tpu")):
        f = f.f_back
    return inner, tag(f) if f is not None else "-"


def main(out: str, argv: list) -> int:
    counts, open_ = collections.Counter(), threading.Event()
    main_id, stop = threading.main_thread().ident, threading.Event()

    def sample():
        while not stop.is_set():
            time.sleep(EVERY_S)
            if open_.is_set():
                frame = sys._current_frames().get(main_id)
                if frame is not None:
                    counts[where(frame)] += 1

    opened, closed = Session.open_window, Session.close_window

    def open_window(self):
        t0 = opened(self)
        open_.set()
        return t0

    def close_window(self):
        open_.clear()
        return closed(self)

    Session.open_window, Session.close_window = open_window, close_window
    t = threading.Thread(target=sample, daemon=True)
    t.start()
    try:
        rc = run.main(argv + ["--trace", "0"])
    finally:
        stop.set()
        t.join(5)
    total = sum(counts.values())
    with open(out, "w") as f:
        json.dump({"samples": total, "every_s": EVERY_S, "argv": argv,
                   "top": [[inner, prog, n] for (inner, prog), n
                           in counts.most_common(60)]}, f)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
