"""The plain reference for rules of several steps, held to upstream's C:
data/upstream_crush_rules_golden.json is a copy of the oracle's answers
(tests/golden/gen_crush_golden.py builds it from src/crush/*.c) for every
query of tests/golden/crush_mappings.json on a straw2 map with the optimal
tunables whose rule is one indep step, one chooseleaf step or two chained
firstn steps, reweights included.  No upstream answer is on record for two
chained indep steps; test_chained_indep_* hold that composition to what
its pieces, each with a witness here, must give."""

import json
import os

import pytest

from benchmark.reference import crush_rules_ref as ref

with open(os.path.join(os.path.dirname(__file__), "data",
                       "upstream_crush_rules_golden.json")) as _f:
    GOLDEN = json.load(_f)["maps"]


def crush_of(case: dict) -> ref.Map:
    return ref.Map({int(k): tuple(v) for k, v in case["buckets"].items()})


def shape(steps: list) -> tuple:
    return tuple(op for op, _a, _b in steps if op.startswith("choose"))


CASES = [(name, rule) for name, case in sorted(GOLDEN.items())
         for rule in sorted(case["rules"])]


@pytest.mark.parametrize("name,rule", CASES)
def test_do_rule_agrees_with_upstream_c(name, rule):
    case = GOLDEN[name]
    crush, steps = crush_of(case), [tuple(s) for s in case["rules"][rule]]
    asked = [(q, want) for q, want in zip(case["queries"], case["results"])
             if str(q[0]) == rule]
    assert len(asked) >= 60
    for (_rule, x, result_max), want in asked:
        assert crush.do_rule(steps, x, result_max,
                             case["reweights"]) == want, (name, rule, x)


def test_the_witnessed_shapes():
    """What the data covers, so that a thinner copy cannot pass unseen:
    the indep loop alone, indep under chooseleaf, chained firstn steps,
    holes, and OSDs weighted down or out."""
    shapes = {shape(c["rules"][r]) for c in GOLDEN.values()
              for r in c["rules"]}
    assert {("choose_indep",), ("chooseleaf_indep",),
            ("choose_firstn", "choose_firstn"),
            ("chooseleaf_firstn",)} <= shapes
    assert ("choose_indep", "chooseleaf_indep") not in shapes
    holes = sum(r.count(ref.NONE) for c in GOLDEN.values()
                for r in c["results"])
    down = sum(w < 0x10000 for c in GOLDEN.values() for w in c["reweights"])
    assert holes > 0 and down >= 3
    assert sum(len(c["queries"]) for c in GOLDEN.values()) == 871


def three_level(racks: int, hosts: int, osds: int) -> tuple:
    """(map, steps of the LRC rule): the root is -1, rack r is
    -(2 + r), its host h is -(2 + racks + r * hosts + h)."""
    w, buckets = 0x10000, {}
    for r in range(racks):
        for h in range(hosts):
            first = (r * hosts + h) * osds
            buckets[-(2 + racks + r * hosts + h)] = (
                1, list(range(first, first + osds)), [w] * osds)
        buckets[-(2 + r)] = (
            2, [-(2 + racks + r * hosts + h) for h in range(hosts)],
            [w * osds] * hosts)
    buckets[-1] = (3, [-(2 + r) for r in range(racks)],
                   [w * osds * hosts] * racks)
    return ref.Map(buckets), [
        (ref.SET_CHOOSELEAF_TRIES, 5, 0), (ref.SET_CHOOSE_TRIES, 100, 0),
        (ref.TAKE, -1, 0), (ref.CHOOSE_INDEP, 2, 2),
        (ref.CHOOSELEAF_INDEP, 4, 1), (ref.EMIT, 0, 0)]


def test_chained_indep_is_its_two_pieces_put_together():
    """Each rack the first step chose is a take of its own for the second,
    at position 0 with parent_r 0: so the rule's answer is the one-step
    rule `take <that rack>; chooseleaf indep 4 type host` run once per
    rack, side by side.  One-step indep rules have upstream's witness."""
    crush, steps = three_level(4, 5, 3)
    weight = [0x10000] * 60
    for o in (0, 1, 2, 17, 31):
        weight[o] = 0
    weight[40] = 0x6000
    for x in range(0, 40000, 97):
        racks = crush.do_rule(
            [(ref.SET_CHOOSE_TRIES, 100, 0), (ref.TAKE, -1, 0),
             (ref.CHOOSE_INDEP, 2, 2), (ref.EMIT, 0, 0)], x, 8, weight)
        want = []
        for rack in racks:
            want += crush.do_rule(
                [(ref.SET_CHOOSELEAF_TRIES, 5, 0),
                 (ref.SET_CHOOSE_TRIES, 100, 0), (ref.TAKE, rack, 0),
                 (ref.CHOOSELEAF_INDEP, 4, 1), (ref.EMIT, 0, 0)],
                x, 8, weight)
        assert crush.do_rule(steps, x, 8, weight) == want


def test_chained_indep_keeps_holes_in_place_and_skips_a_none_take():
    """A rack with three hosts leaves its fourth position NONE, where it
    is; a first step that finds no second rack emits four positions."""
    crush, steps = three_level(2, 3, 2)
    weight = [0x10000] * 12
    rows = [crush.do_rule(steps, x, 8, weight) for x in range(200)]
    assert all(len(r) == 8 and r.count(ref.NONE) == 2 for r in rows)
    assert all(r.index(ref.NONE) < 4 <= 7 - r[::-1].index(ref.NONE)
               for r in rows)
    one, steps1 = three_level(1, 4, 2)
    for x in range(50):
        got = one.do_rule(steps1, x, 8, [0x10000] * 8)
        assert len(got) == 4 and ref.NONE not in got
        up, prim, acting, _p = ref.pg_to_up_acting(
            one, steps1, 1, 64, 8, x, [0x10000] * 8, [True] * 8)
        assert len(up) == 8 and up[4:] == [ref.NONE] * 4 and prim == up[0]
        assert acting == up


def test_up_keeps_a_down_osd_as_a_hole():
    crush, steps = three_level(3, 4, 3)
    weight, osd_up = [0x10000] * 36, [True] * 36
    base = ref.pg_to_up_acting(crush, steps, 1, 64, 8, 5, weight, osd_up)
    victim = base[0][0]
    osd_up[victim] = False
    up, prim, _a, _p = ref.pg_to_up_acting(crush, steps, 1, 64, 8, 5,
                                           weight, osd_up)
    assert up == [ref.NONE] + base[0][1:] and prim == base[0][1]
