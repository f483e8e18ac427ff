"""The two-step crush driver walked end to end at a tiny size on the CPU
backend: an LRC pool of 8192 PGs on 3 racks x 5 hosts x 3 OSDs, named in no
BENCHMARK.json.  A sound run must read `correct: true`; every fault of
faults_rules.py must read `correct: false` by the number it was planted
for; a program whose codec has no create_rule, or whose device mapper does
not take the rule, must end with no result before any pool is mapped.
Not tier-1:

    python3 -m pytest benchmark/tests/test_rehearsal_rules.py -q -p no:cacheprovider
"""

import contextlib
import json
import os

import pytest

from benchmark import run
from benchmark.tests import faults_rules
from benchmark.tests.test_rehearsal import no_chip  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "crush-3rack-lrc-8k.reweight-churn"


def rehearse(capsys, seed, traced=0, fault=contextlib.nullcontext):
    with fault():
        rc = run.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "2", "--trace", str(traced)],
                      bench_file=os.path.join(HERE, "rehearsal_rules.json"),
                      mixes=os.path.join(HERE, "workloads"))
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    return line


def sound(c: dict) -> bool:
    return (c["value"] <= c["limit"] if c["relation"] == "<="
            else c["value"] >= c["limit"])


@pytest.mark.parametrize("seed,traced,metrics", [
    (5, 0, {"remap_s", "setup_s"}),
    (2 ** 31 + 7, 1, {"remap_host_ms", "remap_readback_ms",
                      "crush_pallas_lane_pct", "crush_resolve_lane_pct",
                      "crush_rule_steps", "crush_indep_retry_lane_pct"}),
])
def test_sound_run(no_chip, capsys, seed, traced, metrics):  # noqa: F811
    line = rehearse(capsys, seed, traced)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    c = line["compared"]
    assert c["pgs_compared"]["value"] == 128
    assert c["rule_steps_on_device"]["value"] == 2
    assert c["locality_violations"]["value"] == 0
    assert line["facts"]["none_slots_sampled"] == 0
    if traced:
        assert line["metrics"]["crush_rule_steps"]["value"] == 2


@pytest.mark.parametrize("fault,caught_by,may", [
    ("stale_mapping", ("mismatched_pgs",), ()),
    ("second_step_from_root", ("locality_violations", "mismatched_pgs"),
     ()),
    # a row's OSD that moves across the middle also breaks locality
    ("holes_shifted", ("mismatched_pgs",), ("locality_violations",)),
])
def test_fault_reads_not_correct(no_chip, capsys, fault,  # noqa: F811
                                 caught_by, may):
    line = rehearse(capsys, 17, fault=faults_rules.FAULTS[fault])
    assert line["correct"] is False
    for name in caught_by:
        assert not sound(line["compared"][name]), line["compared"][name]
    for name, c in line["compared"].items():
        if name not in caught_by + may:
            assert sound(c), (name, c)
    if fault == "holes_shifted":
        assert line["facts"]["none_slots_sampled"] > 0


def test_no_create_rule_ends_with_no_result(no_chip, capsys,  # noqa: F811
                                            monkeypatch):
    from ceph_tpu.ec.base import ErasureCode
    from ceph_tpu.ec.interface import ErasureCodeInterface
    monkeypatch.delattr(ErasureCode, "create_rule")
    monkeypatch.delattr(ErasureCodeInterface, "create_rule")
    monkeypatch.setattr(ErasureCodeInterface, "__abstractmethods__",
                        ErasureCodeInterface.__abstractmethods__
                        - {"create_rule"})
    with pytest.raises(SystemExit) as e:
        rehearse(capsys, 3)
    assert e.value.code not in (0, None)
    said = capsys.readouterr()
    assert said.out == "" and "no create_rule" in said.err


def test_rule_outside_the_device_ends_with_no_result(
        no_chip, capsys, monkeypatch):  # noqa: F811
    from ceph_tpu.ops.crush.device import DeviceMapper

    def single_step_only(self, ruleno, result_max):
        raise ValueError("device mapper supports a single choose step")

    monkeypatch.setattr(DeviceMapper, "_plan", single_step_only)
    from ceph_tpu.parallel import mapping

    def never(*_a, **_kw):
        raise AssertionError("OSDMapMapping built on a rule the device "
                             "mapper refused")

    monkeypatch.setattr(mapping, "OSDMapMapping", never)
    with faults_rules._fresh_mappers(), pytest.raises(SystemExit) as e:
        rehearse(capsys, 3)
    assert e.value.code not in (0, None)
    said = capsys.readouterr()
    assert said.out == "" and "does not take the pool's rule" in said.err


def test_another_rule_than_the_configuration_ends_with_no_result(
        no_chip, capsys, monkeypatch):  # noqa: F811
    from ceph_tpu.ec.lrc import ErasureCodeLrc
    monkeypatch.setattr(ErasureCodeLrc, "_rule_prologue", lambda self: [])
    with pytest.raises(SystemExit) as e:
        rehearse(capsys, 3)
    assert e.value.code not in (0, None)
    said = capsys.readouterr()
    assert said.out == "" and "the configuration says" in said.err
