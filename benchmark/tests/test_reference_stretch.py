"""The stretch cell's plain reference, its witnesses, and its driver walked
end to end at a tiny size on the CPU backend.

crush_stretch_ref runs a rule of several TAKE/EMIT blocks through
crush_rules_ref.Map.do_rule.  No upstream answer is on record for a rule
of two blocks (tests/golden/crush_mappings.json has none), so the
witnesses tie it to what has one: a rule of two blocks answers what its
two one-block rules answer, put together and cut to result_max, on the
maps whose one-block answers upstream's C gave
(data/upstream_crush_rules_golden.json); and a replicated pool's rows of
a one-block rule equal crush_ref.py's, the sibling cell's reference.

The rehearsal: a pool of 8192 PGs on 2 datacenters x 16 hosts x 3 OSDs,
named in no BENCHMARK.json.  A sound run must read `correct: true`; the
control stale_mapping and the fault one_site (control_stretch.py) must
read `correct: false` by the numbers they were planted for; a program
whose device mapper does not take the rule must end with no result
before any pool is mapped.  Not tier-1:

    python3 -m pytest benchmark/tests/test_reference_stretch.py -q -p no:cacheprovider
"""

import contextlib
import itertools
import json
import os

import pytest

from benchmark import run
from benchmark.reference import crush_ref
from benchmark.reference import crush_rules_ref as rules_ref
from benchmark.reference import crush_stretch_ref as ref
from benchmark.tests import control_stretch
from benchmark.tests.test_rehearsal import no_chip  # noqa: F401
from benchmark.tests.test_reference_rules import GOLDEN, crush_of

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "crush-2dc-stretch-8k.reweight-churn"
HIER = sorted(name for name in GOLDEN if name.startswith("hier_straw2_"))


def blocks_of(crush: rules_ref.Map, x: int, result_max: int, weights,
              *rules) -> tuple:
    """(the answer of the rules' blocks run as one rule, the one-block
    rules' answers put together and cut to result_max)."""
    whole = crush.do_rule([s for r in rules for s in r], x, result_max,
                          weights)
    apart = [o for r in rules
             for o in crush.do_rule(r, x, result_max, weights)]
    return whole, apart[:result_max]


@pytest.mark.parametrize("name", HIER)
def test_two_blocks_are_their_one_block_rules_put_together(name):
    """Every pair of the map's witnessed rules (firstn, indep, two
    chained firstn steps), and two blocks under two of the root's
    hosts, at every result_max the golden queries ask and one under the
    blocks' sum (EMIT's cap), reweights included."""
    case = GOLDEN[name]
    crush = crush_of(case)
    rules = [[tuple(s) for s in case["rules"][r]]
             for r in sorted(case["rules"])]
    _t, hosts, _w = crush.buckets[-1]
    rules.append([(rules_ref.TAKE, hosts[0], 0),
                  (rules_ref.CHOOSE_FIRSTN, 2, 0), (rules_ref.EMIT, 0, 0)])
    rules.append([(rules_ref.TAKE, hosts[1], 0),
                  (rules_ref.CHOOSE_INDEP, 2, 0), (rules_ref.EMIT, 0, 0)])
    asked = sorted({(x, rm) for _r, x, rm in case["queries"]})[:40]
    cut = 0
    for first, second in itertools.product(rules, repeat=2):
        for x, rm in asked + [(x, 2) for x, _rm in asked[:10]]:
            whole, apart = blocks_of(crush, x, rm, case["reweights"],
                                     first, second)
            assert whole == apart, (name, first, second, x, rm)
            cut += len(crush.do_rule(first, x, rm, case["reweights"])) \
                + len(crush.do_rule(second, x, rm, case["reweights"])) > rm
    assert cut > 0      # EMIT's cap was reached


def uniform(hosts: int, per_host: int) -> rules_ref.Map:
    """crush_ref.Map.uniform's map as crush_rules_ref takes it."""
    buckets = {-(h + 2): (1, list(range(h * per_host, (h + 1) * per_host)),
                          [0x10000] * per_host) for h in range(hosts)}
    buckets[-1] = (2, [-(h + 2) for h in range(hosts)],
                   [0x10000 * per_host] * hosts)
    return rules_ref.Map(buckets)


def test_a_replicated_pool_equals_the_sibling_reference():
    """`take root; chooseleaf firstn 0 type host; emit` at size 3: up,
    acting and the primaries equal crush_ref.py's, with OSDs out,
    reweighted and down."""
    hosts, per_host, n = 10, 4, 40
    old, new = crush_ref.Map.uniform(hosts, per_host), uniform(hosts,
                                                               per_host)
    steps = [(rules_ref.TAKE, -1, 0), (rules_ref.CHOOSELEAF_FIRSTN, 0, 1),
             (rules_ref.EMIT, 0, 0)]
    weight = [0x10000] * n
    for o in (1, 2, 3, 17):
        weight[o] = 0
    weight[9] = 0x8000
    up = [o not in (5, 30) for o in range(n)]
    downs = 0
    for ps in range(400):
        want = crush_ref.pg_to_up_acting(old, 1, 1000, 3, ps, weight, up)
        got = ref.pg_to_up_acting(new, steps, 1, 1000, 3, ps, weight, up)
        assert ([o for o in got[0] if o != ref.NONE], got[1],
                [o for o in got[2] if o != ref.NONE], got[3]) == want, ps
        downs += len(want[0]) < 3
    assert downs > 0    # a down OSD left its row short


def rehearse(capsys, seed, traced=0, fault=contextlib.nullcontext):
    with fault():
        rc = run.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "2", "--trace", str(traced)],
                      bench_file=os.path.join(HERE, "rehearsal_stretch.json"),
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
    (2 ** 31 + 9, 1, {"remap_host_ms", "crush_tail_lane_pct",
                      "crush_resolve_lane_pct", "crush_rule_steps",
                      "crush_rule_blocks"}),
])
def test_sound_run(no_chip, capsys, seed, traced, metrics):  # noqa: F811
    line = rehearse(capsys, seed, traced)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    c = line["compared"]
    assert c["pgs_compared"]["value"] == 128
    assert c["rule_blocks_on_device"]["value"] == 2
    assert c["site_violations"]["value"] == 0
    if traced:
        assert line["metrics"]["crush_rule_blocks"]["value"] == 2
        assert line["metrics"]["crush_rule_steps"]["value"] == 2
        # both blocks' lanes seated in one tail: the share sums them
        assert line["metrics"]["crush_tail_lane_pct"]["value"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    ("stale_mapping", ("mismatched_pgs",)),
    ("one_site", ("site_violations", "mismatched_pgs")),
])
def test_fault_reads_not_correct(no_chip, capsys, fault,  # noqa: F811
                                 caught_by):
    line = rehearse(capsys, 17, fault=control_stretch.FAULTS[fault])
    assert line["correct"] is False
    for name in caught_by:
        assert not sound(line["compared"][name]), line["compared"][name]
    for name, c in line["compared"].items():
        if name not in caught_by:
            assert sound(c), (name, c)


def test_rule_outside_the_device_ends_with_no_result(
        no_chip, capsys, monkeypatch):  # noqa: F811
    """A device mapper that takes one TAKE/EMIT pair alone, as it did
    before rules of several blocks."""
    from benchmark.tests.faults_rules import _fresh_mappers
    from ceph_tpu.ops.crush.device import DeviceMapper
    from ceph_tpu.parallel import mapping

    def one_block_only(self, ruleno, result_max):
        raise ValueError("device mapper supports one TAKE/EMIT pair")

    def never(*_a, **_kw):
        raise AssertionError("OSDMapMapping built on a rule the device "
                             "mapper refused")

    monkeypatch.setattr(DeviceMapper, "_plan", one_block_only)
    monkeypatch.setattr(mapping, "OSDMapMapping", never)
    with _fresh_mappers(), pytest.raises(SystemExit) as e:
        rehearse(capsys, 3)
    assert e.value.code == 3
    said = capsys.readouterr()
    assert said.out == "" and "does not take the pool's rule" in said.err


def test_another_rule_than_the_configuration_ends_with_no_result(
        no_chip, capsys, monkeypatch):  # noqa: F811
    from benchmark.drivers import crush_churn_stretch as driver
    real = driver.crush_text

    def one_block(cfg):
        return real(dict(cfg, rule=cfg["rule"][:3]))

    monkeypatch.setattr(driver, "crush_text", one_block)
    with pytest.raises(SystemExit) as e:
        rehearse(capsys, 3)
    assert e.value.code == 3
    said = capsys.readouterr()
    assert said.out == "" and "the configuration says" in said.err
