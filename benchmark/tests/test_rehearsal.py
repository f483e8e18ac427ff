"""The drivers walked end to end at a tiny size on the CPU backend: k2m1 on
3 OSDs with 64 KiB objects, and 8192 PGs on 40 OSDs; named in no
BENCHMARK.json.  The test steers the look for a chip itself, so a device
number is never printed under a TPU's name: the result line names the CPU.

Sound runs must read `correct: true`; every fault of faults.py planted under
the timed path must read `correct: false`.  Not tier-1 (tests/ is untouched):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import contextlib
import json
import os

import pytest

from benchmark import run
from benchmark.harness import device, peaks
from benchmark.tests import faults

HERE = os.path.dirname(os.path.abspath(__file__))
RADOS, CRUSH = "k2m1.write-64k-t4", "crush-40osd-8k.reweight-churn"


@pytest.fixture
def no_chip(monkeypatch):
    def as_jax_reports(_chips):
        import jax
        d = jax.devices()
        return {"platform": d[0].platform, "kind": d[0].device_kind,
                "count": len(d)}
    monkeypatch.setattr(device, "require_chips", as_jax_reports)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def rehearse(capsys, cell, seed, traced=0, fault=contextlib.nullcontext):
    with fault():
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "2", "--trace", str(traced)],
                      bench_file=os.path.join(HERE, "rehearsal.json"),
                      mixes=os.path.join(HERE, "workloads"))
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    last = out.err.strip().splitlines()[-len(line["compared"]):]
    assert all(ln.startswith("compared ") for ln in last)
    assert line["device"]["platform"] == "cpu"
    return line


def test_off_the_chip_it_fails_and_prints_nothing(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", RADOS, "--seed", "1", "--seconds", "1"],
                 bench_file=os.path.join(HERE, "rehearsal.json"),
                 mixes=os.path.join(HERE, "workloads"))
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell,seed,traced,metrics", [
    (RADOS, 3, 0, {"ops_per_s", "lat_mean_ms", "lat_p50_ms", "lat_p90_ms",
                   "setup_s"}),
    (RADOS, 2 ** 31 + 11, 1, {"dispatches_per_op", "host_cpu_ms_per_op",
                              "loop_lag_p95_ms.4k", "client_lat_p95_ms.4k"}),
    (CRUSH, 5, 0, {"remap_s", "setup_s"}),
    (CRUSH, 2 ** 31 + 7, 1, {"remap_host_ms"}),
])
def test_sound_run(no_chip, capsys, cell, seed, traced, metrics):
    line = rehearse(capsys, cell, seed, traced)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # no device plane on the CPU: the trace-fed metrics are left out
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert ("breakdown" in line) == bool(traced)


@pytest.mark.parametrize("cell,fault,caught_by", [
    (RADOS, "parity_zeroed", "degraded_mismatches"),
    (RADOS, "altered_read", "readback_mismatches"),
    (RADOS, "half_dropped", "readback_mismatches"),
    (RADOS, "host_ec", "ec_dispatches_in_window"),
    (CRUSH, "stale_mapping", "mismatched_pgs"),
    (CRUSH, "altered_rows", "mismatched_pgs"),
])
def test_fault_reads_not_correct(no_chip, capsys, cell, fault, caught_by):
    line = rehearse(capsys, cell, 17, fault=faults.FAULTS[fault])
    assert line["correct"] is False
    c = line["compared"][caught_by]
    sound = (c["value"] <= c["limit"] if c["relation"] == "<="
             else c["value"] >= c["limit"])
    assert not sound, c
