"""The read driver walked end to end at a tiny size on the CPU backend:
k2m1 on 3 OSDs, 24 objects of 64 KiB, both kinds of cell (healthy, and
one OSD stopped under noout); named in no BENCHMARK.json.  Sound runs
must read `correct: true`; the faults of faults_read.py must read
`correct: false`; a program that refuses `noout` must end the run with no
result.  Not tier-1:

    python3 -m pytest benchmark/tests/test_rehearsal_read.py -q -p no:cacheprovider
"""

import contextlib
import json
import os

import pytest

from benchmark import run
from benchmark.tests import faults_read
from benchmark.tests.faults import _patched
from benchmark.tests.test_rehearsal import no_chip  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
DEGRADED, HEALTHY = "k2m1.degraded-read-64k-t4", "k2m1.seqread-64k-t4"
END_TO_END = {"ops_per_s", "lat_mean_ms", "setup_s"}
# what a traced run reads on the CPU, where no device plane exists: the
# trace-fed shares (idle, roofline) are left out, never 0
TRACED = {"dispatches_per_op", "host_cpu_ms_per_op", "loop_lag_p95_ms.4m",
          "client_lat_p50_ms", "client_lat_p95_ms.4m",
          "loop_ms_per_op.client", "loop_ms_per_op.msgr",
          "loop_ms_per_op.osd", "loop_ms_per_op.ec", "loop_ms_per_op.store",
          "loop_ms_per_op.gc", "loop_span_cover_pct",
          "client_resends_per_op", "reconstructs_per_op",
          "sub_read_mib_per_op", "op_stage_p95_ms.sub_read.4m",
          "op_stage_p95_ms.queue.4m", "loop_ms_per_op.harness"}
TRACED_DEGRADED = TRACED | {"ec_dispatch_xfer_gbps",
                            "op_stage_p95_ms.decode.4m"}


def rehearse(capsys, cell, seed, traced=0, fault=contextlib.nullcontext):
    with fault():
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "2", "--trace", str(traced)],
                      bench_file=os.path.join(HERE, "rehearsal_read.json"),
                      mixes=os.path.join(HERE, "workloads"))
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    return line


@pytest.mark.parametrize("cell,seed,traced,metrics", [
    (HEALTHY, 3, 0, END_TO_END),
    (DEGRADED, 2 ** 31 + 13, 0, END_TO_END),
    (HEALTHY, 2 ** 31 + 5, 1, TRACED),
    (DEGRADED, 7, 1, TRACED_DEGRADED),
])
def test_sound_run(no_chip, capsys, cell, seed, traced, metrics):  # noqa: F811
    line = rehearse(capsys, cell, seed, traced)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    c, f = line["compared"], line["facts"]
    assert c["read_mismatches"]["value"] == 0
    assert c["reads_compared"]["value"] >= line["attempted"]
    if cell == DEGRADED:
        assert c["victim_in_and_down"]["value"] == 1
        assert c["osds_marked_down"]["value"] == 1
        assert f["reconstructed_reads"] * 2 >= f["ops_completed"]
        # k2m1's one parity row is all ones: both lost positions are
        # rebuilt by the same matrix, so they share queue and programs
        assert f["reconstruct_positions"] == 2
        assert f["reconstruct_matrices"] == 1
        assert f["reconstruct_programs"] == len(
            f["reconstruct_buckets"].split(","))
        # k2m1: every read fetches the one other shard it needs; the
        # counters are read at the window's edges, with 4 reads in flight
        assert abs(f["sub_read_bytes"] // 32768 - f["ops_completed"]) <= 4
    else:
        assert f["reconstructed_reads"] == 0 and f["ec_dispatches"] == 0
        assert c["osds_marked_down"]["value"] == 0
    if traced:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        if cell == DEGRADED:
            assert m["reconstructs_per_op"] >= 0.5
            assert m["op_stage_p95_ms.decode.4m"] > 0
        else:
            assert m["reconstructs_per_op"] == 0
            assert m["dispatches_per_op"] == 0
        # 64 KiB objects, k=2: one 32 KiB shard fetched per read
        assert m["sub_read_mib_per_op"] == pytest.approx(1 / 32, rel=0.25)
        assert m["op_stage_p95_ms.sub_read.4m"] > 0
        # a read is queued and reaches its PG like any op; the compare of
        # every read runs under the harness's own span
        assert m["op_stage_p95_ms.queue.4m"] >= 0
        assert m["loop_ms_per_op.harness"] > 0


@pytest.mark.parametrize("cell,fault,caught_by", [
    (DEGRADED, "reconstruct_zeroed", "read_mismatches"),
    (DEGRADED, "victim_spared", "reconstructed_reads"),
])
def test_fault_reads_not_correct(no_chip, capsys, cell, fault,  # noqa: F811
                                 caught_by):
    line = rehearse(capsys, cell, 17, fault=faults_read.FAULTS[fault])
    assert line["correct"] is False
    c = line["compared"][caught_by]
    sound = (c["value"] <= c["limit"] if c["relation"] == "<="
             else c["value"] >= c["limit"])
    assert not sound, c


def test_a_healthy_pool_is_deaf_to_reconstruct_zeroed(no_chip, capsys):  # noqa: F811
    """The control breaks the degraded cell's guarantee only: a healthy
    read rebuilds nothing."""
    line = rehearse(capsys, HEALTHY, 19,
                    fault=faults_read.FAULTS["reconstruct_zeroed"])
    assert line["correct"] is True


@pytest.mark.parametrize("cell,lacks", [
    (DEGRADED, "noout"), (DEGRADED, "counter"), (HEALTHY, "counter")])
def test_a_program_without_the_mechanism_ends_at_once(no_chip, capsys,  # noqa: F811
                                                      cell, lacks):
    """What the parent commit does: no flag in the monitor, no count of
    reconstructed reads in the EC backend.  Non-zero, and no result."""
    from ceph_tpu.mon.monitor import Monitor
    from ceph_tpu.osd.ecbackend import ECPGBackend
    real_cmd, real_init = Monitor._run_command, ECPGBackend.__init__

    def no_flag(self, prefix, cmd):
        if prefix in ("osd set", "osd unset"):
            raise ValueError("unknown command %r" % prefix)
        return real_cmd(self, prefix, cmd)

    def no_counter(self, osd):
        real_init(self, osd)
        del self.reconstructed_reads

    patch = (_patched(Monitor, "_run_command", no_flag) if lacks == "noout"
             else _patched(ECPGBackend, "__init__", no_counter))
    with patch, pytest.raises(SystemExit) as e:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1"],
                 bench_file=os.path.join(HERE, "rehearsal_read.json"),
                 mixes=os.path.join(HERE, "workloads"))
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_the_benchmark_names_the_degraded_cell_only():
    """The degraded kind is a cell of BENCHMARK.json and carries every
    read-path metric.  The healthy kind dispatches nothing, and the
    benchmark's check refuses a cell whose traced window holds no device
    operation (PERF.md section 7), so no metric may list it: what a
    healthy read costs at the cell's size is `control_read.py
    victim_spared`."""
    top = run.load(run.ROOT, "BENCHMARK.json")
    degraded = "k8m3.degraded-read-4m-t16"
    read_cells = [w for w in top["workloads"]
                  if w["traffic"] == "seqread-4m-t16"]
    assert [w["name"] for w in read_cells] == [degraded]
    mix = run.load(run.HERE, "workloads", degraded + ".json")
    assert mix["driver"] == "rados_bench_read"
    assert mix["config"] == read_cells[0]["config"]
    listed = {m["name"] for m in top["end_to_end"] + top["per_layer"]
              if degraded in m.get("workloads", [degraded])}
    assert listed >= END_TO_END | TRACED_DEGRADED | {
        "device_idle_pct.rados", "ec_decode_roofline"}
