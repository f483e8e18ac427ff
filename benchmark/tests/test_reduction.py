"""The reduction from a trace to busy time, idle share, top operations and
longest gaps, checked on a small recorded trace and on made-up intervals.

data/rados-4m-2s.json: the first 2 s of the measured window of a traced run
of k8m3.write-4m-t16 on one TPU v5e (PR 26), cut by dump_trace.py.
data/crush-10m-50ms.json: the first 50 ms of crush-1000osd-10m.reweight-churn,
each name cut to "%op = kind(", as trace.short reads it.
"""

import json
import os

import pytest

from benchmark.harness import counts, trace
from benchmark.harness.stats import pctl, spread
from benchmark.readers import (counter_ratio, host_share, quantile,
                               roofline, trace_idle)

DATA = os.path.join(os.path.dirname(__file__), "data")


def recorded(name):
    with open(os.path.join(DATA, name)) as f:
        rec = json.load(f)
    return {"device": {p: [tuple(e) for e in evs]
                       for p, evs in rec["device"].items()},
            "spans": [tuple(s) for s in rec["spans"]]}


def test_union_merges_overlap_and_touch():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [[0, 4], [5, 7], [9, 9]]


def test_made_up_trace():
    tr = {"device": {"/device:TPU:0": [("a", 10, 10), ("b", 12, 5),
                                       ("a", 60, 20), ("c", 95, 20)]},
          "spans": [("bench.window", 0, 100), ("bench.remap.mapping", 20, 45)]}
    red = trace.reduce(tr)
    # busy: [10,20) with b nested + [60,80) + [95,100) clipped = 10 + 20 + 5
    assert red["busy_s"] == pytest.approx(35e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["device_ops"][0] == ["a", pytest.approx(25e-9)]
    assert sorted(o[1] for o in red["device_ops"][1:]) == \
        [pytest.approx(5e-9)] * 2
    # gaps: [0,10) [20,60) [80,95); the longest lies inside the mapping span
    assert red["idle_gaps"][0] == ["bench.remap.mapping", pytest.approx(40e-9)]
    assert [g[1] for g in red["idle_gaps"]] == sorted(
        (g[1] for g in red["idle_gaps"]), reverse=True)
    assert sum(g[1] for g in red["idle_gaps"]) + red["busy_s"] == \
        pytest.approx(red["window_s"])
    assert trace_idle.read({}, {"trace": red}) == pytest.approx(65.0)


def test_two_planes_average():
    tr = {"device": {"/device:TPU:0": [("a", 0, 50)],
                     "/device:TPU:1": [("a", 0, 100)]},
          "spans": [("bench.window", 0, 100)]}
    red = trace.reduce(tr)
    assert red["planes"] == 2
    assert red["busy_s"] == pytest.approx(75e-9)


def test_self_times_do_not_count_children_twice():
    got = trace.self_times([("while", 0, 100), ("a", 10, 30), ("b", 12, 20),
                            ("a", 40, 50), ("c", 120, 130)])
    assert got == {"while": 70, "a": 22, "b": 8, "c": 10}
    assert sum(got.values()) == 110     # the union of the five


def test_short_names():
    assert trace.short(
        '%run.1 = u32[3,131072]{1,0:T(4,128)} custom-call(u32[8,131072]'
        '{1,0:T(8,128)} %data32.1), custom_call_target="tpu_custom_call"'
    ) == "%run.1 custom-call"
    assert trace.short(
        "%while.7 = (u32[]{:T(128)}, s32[10,1048576,3]{1,2,0:T(4,128)}) "
        "while((u32[]{:T(128)}) %tuple.1), condition=%c, body=%b"
    ) == "%while.7 while"
    assert trace.short("jit_run(123)") == "jit_run(123)"


def test_recorded_rados_trace():
    tr = recorded("rados-4m-2s.json")
    red = trace.reduce(tr)
    events = [e for evs in tr["device"].values() for e in evs]
    assert len(events) == 22
    assert red["window_s"] == pytest.approx(2.0)
    # no two encodes of this recording overlap: busy is their plain sum
    assert red["busy_s"] == pytest.approx(sum(e[2] for e in events) / 1e9)
    assert red["busy_s"] * 1e3 == pytest.approx(0.3436, rel=1e-3)
    assert red["device_ops"] == [["%run.1 custom-call",
                                  pytest.approx(red["busy_s"])]]
    assert len(red["idle_gaps"]) == 10
    assert all(g[0] == "bench.window" for g in red["idle_gaps"])
    idle = trace_idle.read({}, {"trace": red})
    assert 99.9 < idle < 100.0


def test_recorded_crush_trace():
    """A `while` that outlasts the cut holds 720 nested operations: busy
    is the union, the top operations are the Pallas draws by their own
    time, and the times of their own add up to the union."""
    tr = recorded("crush-10m-50ms.json")
    red = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(0.05)
    assert red["busy_s"] == pytest.approx(0.049066975)
    assert red["device_ops"][0] == ["%run.160 custom-call",
                                    pytest.approx(0.005012156)]
    t0, t1 = trace.window_of(tr)
    own = trace.self_times([(n, max(s, t0), min(s + d, t1))
                            for n, s, d in tr["device"]["/device:TPU:0"]])
    assert sum(own.values()) / 1e9 == pytest.approx(red["busy_s"])
    assert own["%while.7 = while("] < \
        0.2 * red["busy_s"] * 1e9
    assert red["idle_gaps"][0] == ["bench.remap.mapping",
                                   pytest.approx(0.000933019)]
    assert trace_idle.read({}, {"trace": red}) == pytest.approx(
        100 * (1 - 0.049066975 / 0.05))


def test_no_window_span_is_an_error():
    with pytest.raises(SystemExit):
        trace.reduce({"device": {}, "spans": []})


def test_counts():
    assert counts.ec_encode_bytes(4 << 20, 8, 3) == (4 << 20) * 11 // 8
    assert counts.crush_map_bytes(10_000_000, 3, 2) == 480_000_000


def test_readers_return_nothing_when_nothing_to_read():
    params = {"count": "ec_encode_bytes", "peak": "hbm_bytes_per_s",
              "args": ["facts.payload_bytes", "config.k", "config.m"]}
    run = {"facts": {"payload_bytes": 0}, "config": {"k": 8, "m": 3},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"planes": 1, "busy_s": 1.0, "window_s": 2.0}}
    assert roofline.read(params, run) is None           # no work: never 0
    assert roofline.read(params, dict(run, trace=None)) is None
    run["facts"]["payload_bytes"] = 819_000_000 * 8 // 11
    assert roofline.read(params, run) == pytest.approx(0.1, rel=1e-6)
    assert trace_idle.read({}, {"trace": None}) is None
    assert trace_idle.read({}, {"trace": dict(run["trace"], planes=0)}) is None
    assert host_share.read({"per": "facts.remaps"}, run) is None
    ratio = {"numerator": "facts.a", "denominator": "facts.b", "scale": 10}
    assert counter_ratio.read(ratio, {"facts": {"a": 1, "b": 0}}) is None
    assert counter_ratio.read(ratio, {"facts": {"a": 1, "b": 4}}) == 2.5
    q = {"samples": "facts.s", "quantile": 0.95, "scale": 1000}
    assert quantile.read(q, {"facts": {"s": []}}) is None
    assert quantile.read(q, {"facts": {"s": [0.001] * 19 + [0.5]}}) == 500.0


def test_stats():
    assert pctl([], 0.5) is None
    assert pctl([3, 1, 2], 0.5) == 2
    assert pctl(list(range(100)), 0.95) == 95
    assert spread([10, 10, 11, 9, 10, 10]) == pytest.approx(0.05)
