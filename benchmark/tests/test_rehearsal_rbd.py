"""The rbd_bench driver walked end to end at a tiny size on the CPU
backend: k2m1 on 3 OSDs, a 1 MiB image of 16 objects of 64 KiB on an EC
data pool with overwrites, four 4 KiB writers; named in no BENCHMARK.json.
A sound run must read `correct: true`; each fault of faults_rbd.py must
read `correct: false` by the number it is meant for; a program without the
flag or the counters must end the run with no result.  Not tier-1:

    python3 -m pytest benchmark/tests/test_rehearsal_rbd.py -q -p no:cacheprovider
"""

import contextlib
import json
import os

import pytest

from benchmark import run
from benchmark.tests import faults_rbd
from benchmark.tests.faults import _patched
from benchmark.tests.test_rehearsal import no_chip  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
WRITE, MIXED = "k2m1.randwrite-4k-t4", "k2m1.randrw-4k-t4"
END_TO_END = {"ops_per_s", "lat_p50_ms", "lat_p90_ms", "setup_s"}
# what a traced run reads on the CPU, where no device plane exists: the
# trace-fed shares (idle, roofline) are left out, never 0
TRACED = {"dispatches_per_op", "host_cpu_ms_per_op", "loop_lag_p95_ms.4k",
          "client_lat_p95_ms.4k", "loop_ms_per_op.client",
          "loop_ms_per_op.msgr", "loop_ms_per_op.osd", "loop_ms_per_op.ec",
          "loop_ms_per_op.store", "loop_ms_per_op.gc", "loop_span_cover_pct",
          "ec_dispatch_xfer_gbps", "client_resends_per_op",
          "subop_timeouts_per_op", "client_target_hits_per_op",
          "msgr_rx_direct_mib_per_op", "sub_read_mib_per_op",
          "delta_writes_per_op", "rmw_fallbacks_per_op",
          "op_stage_p95_ms.delta_read.4k", "op_stage_p95_ms.delta_lock.4k",
          "loop_ms_per_op.rbd"}


def rehearse(capsys, cell, seed, traced=0, fault=contextlib.nullcontext):
    with fault():
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "2", "--trace", str(traced)],
                      bench_file=os.path.join(HERE, "rehearsal_rbd.json"),
                      mixes=os.path.join(HERE, "workloads"))
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    return line


@pytest.mark.parametrize("cell,seed,traced,metrics", [
    (WRITE, 3, 0, END_TO_END),
    (WRITE, 2 ** 31 + 13, 1, TRACED),
    (MIXED, 5, 0, END_TO_END),
])
def test_sound_run(no_chip, capsys, cell, seed, traced, metrics):  # noqa: F811
    line = rehearse(capsys, cell, seed, traced)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    c, f = line["compared"], line["facts"]
    for name in ("image_mismatched_blocks", "parity_mismatched_shards",
                 "hinfo_mismatched_shards", "degraded_mismatches",
                 "rmw_fallbacks", "host_fallbacks",
                 "osds_marked_down_in_window", "programs_new_in_window"):
        assert c[name]["value"] == 0, name
    assert c["blocks_overwritten"]["value"] >= 1
    assert c["shards_compared"]["value"] >= 3
    assert c["delta_writes"]["value"] >= c["delta_writes"]["limit"] > 0
    assert c["degraded_ec_dispatches"]["value"] >= 1
    assert f["payload_bytes"] == f["writes_completed"] * 4096
    if cell == MIXED:
        assert c["read_mismatches"]["value"] == 0
        assert 0 < f["writes_completed"] < f["ops_completed"]
    else:
        assert f["writes_completed"] == f["ops_completed"]
        # k2m1: an overwrite reads 4 KiB of the one parity shard and, one
        # time in two, of a data shard the primary does not hold; the
        # counters are read at the window's edges, four writers in flight
        assert 4096 * (f["ops_completed"] - 4) <= f["sub_read_bytes"] \
            <= 8192 * (f["ops_completed"] + 4)
    if traced:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["delta_writes_per_op"] == pytest.approx(1.0, abs=0.1)
        assert m["rmw_fallbacks_per_op"] == 0
        assert 0 < m["dispatches_per_op"] <= 1.0
        assert m["op_stage_p95_ms.delta_read.4k"] > 0
        assert m["op_stage_p95_ms.delta_lock.4k"] >= 0
        assert m["loop_ms_per_op.rbd"] > 0
        assert 1 / 256 * 0.75 <= m["sub_read_mib_per_op"] <= 2 / 256 * 1.25


@pytest.mark.parametrize("fault,caught_by,deaf", [
    ("parity_delta_dropped", ("parity_mismatched_shards",
                              "degraded_mismatches"),
     ("image_mismatched_blocks", "hinfo_mismatched_shards")),
    ("delta_misplaced", ("parity_mismatched_shards",
                         "hinfo_mismatched_shards"),
     ("image_mismatched_blocks",)),
    ("fallback_forced", ("rmw_fallbacks", "delta_writes"),
     ("image_mismatched_blocks", "parity_mismatched_shards",
      "hinfo_mismatched_shards", "degraded_mismatches")),
])
def test_fault_reads_not_correct(no_chip, capsys, fault, caught_by,  # noqa: F811
                                 deaf):
    line = rehearse(capsys, WRITE, 17, fault=faults_rbd.FAULTS[fault])
    assert line["correct"] is False

    def sound(c):
        return (c["value"] <= c["limit"] if c["relation"] == "<="
                else c["value"] >= c["limit"])

    for name in caught_by:
        assert not sound(line["compared"][name]), (name, line["compared"])
    # what no read-back could see stays unseen by the read-back
    for name in deaf:
        assert sound(line["compared"][name]), (name, line["compared"])


@pytest.mark.parametrize("lacks", ["flag", "counter"])
def test_a_program_without_the_mechanism_ends_at_once(no_chip, capsys,  # noqa: F811
                                                      lacks):
    """What the parent commit does: its monitor cannot set the flag, its
    EC backend counts no delta writes.  Non-zero, and no result."""
    from ceph_tpu.mon.monitor import Monitor
    from ceph_tpu.osd.ecbackend import ECPGBackend
    real_set, real_init = Monitor._cmd_pool_set, ECPGBackend.__init__

    def no_flag(self, cmd):
        if cmd["var"] == "allow_ec_overwrites":
            raise ValueError("cannot set %r" % cmd["var"])
        return real_set(self, cmd)

    def no_counter(self, osd):
        real_init(self, osd)
        del self.delta_writes

    patch = (_patched(Monitor, "_cmd_pool_set", no_flag) if lacks == "flag"
             else _patched(ECPGBackend, "__init__", no_counter))
    with patch, pytest.raises(SystemExit) as e:
        run.main(["--workload", WRITE, "--seed", "1", "--seconds", "1"],
                 bench_file=os.path.join(HERE, "rehearsal_rbd.json"),
                 mixes=os.path.join(HERE, "workloads"))
    assert e.value.code == 3
    assert capsys.readouterr().out == ""


def test_the_benchmark_names_the_cell_with_every_metric():
    top = run.load(run.ROOT, "BENCHMARK.json")
    cell = "rbd-ec-k8m3.randwrite-4k-t16"
    entry, = [w for w in top["workloads"] if w["name"] == cell]
    assert entry["chips"] == 1
    mix = run.load(run.HERE, "workloads", cell + ".json")
    assert mix["driver"] == "rbd_bench" and mix["config"] == entry["config"]
    listed = {m["name"] for m in top["end_to_end"] + top["per_layer"]
              if cell in m.get("workloads", [cell])}
    assert listed == END_TO_END | TRACED | {"device_idle_pct.rados",
                                            "ec_delta_roofline"}
    config, = [c for c in top["configs"] if c["name"] == entry["config"]]
    stated = run.load(run.ROOT, config["file"])
    assert stated["source"] == config["source"]
    assert len(config["source"]) <= 200
    sibling = run.load(run.ROOT, "benchmark/configs/rados-bench-k8m3.json")
    for key in ("profile", "osds", "timers", "objectstore", "layout"):
        assert stated[key] == sibling[key], key
    assert sorted(stated["reduced"]) == sorted(config["reduced"])
