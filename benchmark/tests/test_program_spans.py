"""The readers of the program's spans: on a hand-made event list (no
profiler), and in the two rehearsal cells traced on the CPU backend, where
every new metric a cell is listed for must be printed.  Cells and metrics
come from rehearsal_spans.json: rehearsal.json with the span metrics
appended.  Not tier-1:

    python3 -m pytest benchmark/tests/test_program_spans.py -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import run
from benchmark.harness import program_spans
from benchmark.readers import (span_arg, span_count_per, span_cover,
                               span_self_per)
from benchmark.tests.test_rehearsal import CRUSH, RADOS, no_chip  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def made_up() -> dict:
    """A 100 ms window on thread "loop#0": a dispatch of 30 ms holding a
    handler of 20 ms holding an encode of 5 ms; a store span on another
    thread; two marks inside the window and one before it; the device busy
    from 40 to 70 ms."""
    loop = [
        ("rados.msgr.dispatch", 10 * MS, 40 * MS, {}),
        ("rados.osd.handle_op", 15 * MS, 35 * MS, {}),
        ("rados.msgr.encode", 20 * MS, 25 * MS, {}),
        ("rados.client.resend", -5 * MS, -5 * MS + 100, {"age_us": 9}),
        ("rados.client.resend", 50 * MS, 50 * MS + 100, {"age_us": 700}),
        ("rados.client.resend", 60 * MS, 60 * MS + 100, {"age_us": 900}),
        ("rados.ec.dispatch", 70 * MS, 80 * MS,
         {"bytes_in": 8 * MS, "bytes_out": 2 * MS}),
        ("rados.crush.launch", 80 * MS, 81 * MS,
         {"lanes": 4096, "pallas_lanes": 1024}),
        ("rados.crush.launch", 81 * MS, 82 * MS, {}),
        ("rados.op.retired", 90 * MS, 90 * MS + 50,
         {"queue_us": 1500, "total_us": 9000, "client": 0}),
        ("rados.op.retired", 91 * MS, 91 * MS + 50,
         {"total_us": 9500, "client": 1}),
        # straddles the window's end: cut to it
        ("rados.crush.wait", 95 * MS, 130 * MS, {}),
    ]
    other = [("rados.store.apply", 30 * MS, 34 * MS, {"txns": 1})]
    return program_spans.clip({
        "window": (0, 100 * MS), "window_line": "loop#0",
        "lines": {"loop#0": loop, "worker#1": other},
        "busy": [(40 * MS, 60 * MS), (55 * MS, 70 * MS),
                 (-10 * MS, -1 * MS)]})


def the_run(**facts) -> dict:
    return {"trace": {"busy_s": 0.03}, "facts": facts,
            "program_spans": made_up()}


def test_self_time_leaves_children_out():
    r = the_run(ops=10)
    per = {"per": "facts.ops", "scale": 1e-6}
    # dispatch 30 - handler 20; handler 20 - encode 5; encode 5
    assert span_self_per.read(
        {"prefixes": ["rados.msgr."], **per}, r) == pytest.approx(1.5)
    assert span_self_per.read(
        {"prefixes": ["rados.osd."], **per}, r) == pytest.approx(1.5)
    # another thread's span is no child of the loop's dispatch
    assert span_self_per.read(
        {"prefixes": ["rados.store."], **per}, r) == pytest.approx(0.4)
    assert span_self_per.read({"prefixes": ["rados.gc"], **per}, r) == 0
    assert span_self_per.read(
        {"prefixes": ["rados.crush.wait"], "per": "facts.ops",
         "scale": 1e-6}, r) == pytest.approx(0.5)


def test_marks_outside_the_window_are_not_counted():
    r = the_run(ops=4)
    assert span_count_per.read(
        {"name": "rados.client.resend", "per": "facts.ops"}, r) == 0.5
    assert span_count_per.read(
        {"name": "rados.osd.ec.subop_timeout", "per": "facts.ops"}, r) == 0
    assert span_count_per.read(
        {"name": "rados.client.resend", "per": "facts.none"}, r) is None


def test_args_quantile_rate_and_ratio():
    r = the_run()
    assert span_arg.read({"name": "rados.op.retired", "args": ["queue_us"],
                          "quantile": 0.95, "scale": 1e-3}, r) == 1.5
    assert span_arg.read({"name": "rados.op.retired", "args": ["subop_us"],
                          "quantile": 0.95}, r) is None
    # 10e6 bytes in 10e6 ns
    assert span_arg.read({"name": "rados.ec.dispatch",
                          "args": ["bytes_in", "bytes_out"]}, r) == 1.0
    # the launch without lane counts is left out of the ratio
    assert span_arg.read({"name": "rados.crush.launch",
                          "args": ["pallas_lanes"], "over": "lanes",
                          "scale": 100}, r) == 25.0


def test_cover_of_the_window_and_of_the_devices_idle_time():
    r = the_run()
    # loop thread: 10-40, 70-82, 95-100, and four marks of 100/50 ns
    cover = span_cover.read({"prefixes": ["rados."], "base": "window"}, r)
    assert cover == pytest.approx(47.0, abs=0.001)
    # idle is 0-40 and 70-100; named work other than the wait covers
    # 10-40 and 70-82 of it
    idle = span_cover.read(
        {"prefixes": ["rados.msgr.", "rados.ec.", "rados.crush."],
         "exclude": ["rados.crush.wait"], "base": "device_idle"}, r)
    assert idle == pytest.approx(100.0 * 42 / 70)


@pytest.mark.parametrize("run_", [
    {"facts": {"ops": 3}},                                  # untraced
    {"trace": {"busy_s": 0.1}, "facts": {"ops": 3},         # no spans: the
     "program_spans": {"window": (0, 9), "window_line": None,   # parent
                       "lines": {}, "busy": []}},
    {"trace": {"busy_s": 0.1}, "facts": {"ops": 3}, "program_spans": None},
])
def test_nothing_without_a_trace_or_spans(run_):
    assert span_self_per.read({"prefixes": ["rados."], "per": "facts.ops"},
                              run_) is None
    assert span_count_per.read({"name": "rados.client.resend",
                                "per": "facts.ops"}, run_) is None
    assert span_arg.read({"name": "rados.op.retired", "args": ["queue_us"],
                          "quantile": 0.5}, run_) is None
    assert span_cover.read({"prefixes": ["rados."], "base": "window"},
                           run_) is None


def test_no_trace_file_reads_nothing(tmp_path):
    assert program_spans.newest(str(tmp_path)) is None


def listed(cell: str) -> set:
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        old = {m["name"] for m in json.load(f)["per_layer"]}
    with open(os.path.join(HERE, "rehearsal_spans.json")) as f:
        return {m["name"] for m in json.load(f)["per_layer"]
                if m["name"] not in old and cell in m["workloads"]}


@pytest.mark.parametrize("cell,seed", [(RADOS, 2 ** 31 + 13),
                                       (CRUSH, 2 ** 31 + 9)])
def test_traced_rehearsal_prints_every_span_metric(no_chip, capsys,  # noqa: F811
                                                   cell, seed):
    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", "2", "--trace", "1"],
                  bench_file=os.path.join(HERE, "rehearsal_spans.json"),
                  mixes=os.path.join(HERE, "workloads"))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["compared"]["programs_new_in_window"]["value"] == 0
    want = listed(cell)
    assert len(want) >= 4 and want <= set(line["metrics"]), \
        sorted(want - set(line["metrics"]))
    print(json.dumps({k: line["metrics"][k] for k in sorted(want)}))
    cover = "loop_span_cover_pct"
    if cover in want:
        assert 0 < line["metrics"][cover]["value"] <= 100
