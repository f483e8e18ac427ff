"""Rules of more than one choose step on the device mapper: root -> rack
-> host, firstn steps or indep steps, held to the host engine and to the
benchmark's plain reference (benchmark/reference/crush_rules_ref.py,
which has upstream's witnesses) on small maps, through do_rule_batch and
through the whole-pool pass."""

import numpy as np
import pytest

from benchmark.drivers.crush_churn_rules import reference_of
from ceph_tpu.models.crushmap import (CHOOSE_FIRSTN, CHOOSE_INDEP,
                                      CHOOSELEAF_FIRSTN, CHOOSELEAF_INDEP,
                                      EMIT, ITEM_NONE, SET_CHOOSE_TRIES,
                                      SET_CHOOSELEAF_TRIES, STRAW2, TAKE,
                                      CrushMap)
from ceph_tpu.ops.crush.device import DeviceMapper
from ceph_tpu.ops.crush.hashes import pps_seed_v
from ceph_tpu.ops.crush.host import Mapper

LRC = [(SET_CHOOSELEAF_TRIES, 5, 0), (SET_CHOOSE_TRIES, 100, 0),
       (TAKE, -1, 0), (CHOOSE_INDEP, 2, 2), (CHOOSELEAF_INDEP, 4, 1),
       (EMIT, 0, 0)]
RULES = {
    "lrc": LRC,
    "firstn2x2": [(TAKE, -1, 0), (CHOOSE_FIRSTN, 2, 2),
                  (CHOOSELEAF_FIRSTN, 2, 1), (EMIT, 0, 0)],
    # numrep over what exists: four racks asked of three
    "indep4x2": [(TAKE, -1, 0), (CHOOSE_INDEP, 4, 2),
                 (CHOOSELEAF_INDEP, 2, 1), (EMIT, 0, 0)],
    "firstn0x1": [(TAKE, -1, 0), (CHOOSE_FIRSTN, 0, 2),
                  (CHOOSE_FIRSTN, 1, 1), (CHOOSE_FIRSTN, 1, 0),
                  (EMIT, 0, 0)],
    "ec1": [(TAKE, -1, 0), (CHOOSELEAF_INDEP, 0, 1), (EMIT, 0, 0)],
    "rep1": [(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1), (EMIT, 0, 0)],
}
RULE_IDS = {name: i for i, name in enumerate(RULES)}

def build(uneven: bool, racks: int = 3, hosts: int = 4) -> tuple:
    """3 racks x 4 hosts x 3 OSDs; uneven: rack 0 has two hosts (so an
    LRC group there has two holes), one host has five OSDs, weights
    differ."""
    m = CrushMap()
    m.types = {0: "osd", 1: "host", 2: "rack", 3: "root"}
    osd, bid, rack_ids = 0, -2, []
    for r in range(racks):
        host_ids = []
        for h in range(2 if uneven and r == 0 else hosts):
            n = 5 if uneven and (r, h) == (1, 1) else 3
            ws = [0x10000 + (0x3000 * ((osd + i) % 3) if uneven else 0)
                  for i in range(n)]
            b = m.add_bucket(STRAW2, 1, list(range(osd, osd + n)), ws,
                             id=bid)
            host_ids.append(b.id)
            osd, bid = osd + n, bid - 1
        b = m.add_bucket(STRAW2, 2, host_ids,
                         [m.buckets[h].weight for h in host_ids], id=bid)
        rack_ids.append(b.id)
        bid -= 1
    m.add_bucket(STRAW2, 3, rack_ids,
                 [m.buckets[r].weight for r in rack_ids], id=-1,
                 name="default")
    for name, steps in RULES.items():
        m.add_rule(steps, id=RULE_IDS[name], name=name)
    return m, osd


def weights_of(n: int) -> list:
    """A whole host out (OSDs 3-5, the second host of rack 0 in either
    map), two single OSDs out, one reweighted to a half."""
    w = [0x10000] * n
    for o in (3, 4, 5, 10, 20):
        w[o] = 0
    w[7] = 0x8000
    return w


class Fixture:
    def __init__(self, uneven: bool, **shape):
        self.map, self.n = build(uneven, **shape)
        self.dm = DeviceMapper(self.map)
        self.host = Mapper(self.map)
        # the map and each rule as the plain reference takes them
        self.ref = reference_of(self.map, 0)[0]
        self.ref_rules = {name: reference_of(self.map, RULE_IDS[name])[1]
                          for name in RULES}
        self.w = weights_of(self.n)


@pytest.fixture(scope="module")
def even():
    return Fixture(False)


@pytest.fixture(scope="module")
def uneven():
    return Fixture(True)


@pytest.fixture(scope="module")
def wide():
    """8 racks of 16 hosts, rack 0 of two: few enough takes collide (an
    eighth of the first step's, two fifths of the second's) for both
    steps of the lrc rule to run their later rounds on a tail."""
    return Fixture(True, racks=8, hosts=16)


def padded(row: list, width: int) -> list:
    return list(row) + [ITEM_NONE] * (width - len(row))


CASES = [
    # (map, rule, result_max)
    ("even", "lrc", 8),
    ("uneven", "lrc", 8),           # a rack of two hosts: holes in place
    ("uneven", "lrc", 6),           # result_max under numrep x groups
    ("even", "firstn2x2", 4),
    ("uneven", "firstn2x2", 3),     # the second window cut to one
    ("uneven", "indep4x2", 8),      # a NONE take is skipped: rows shift
    ("even", "indep4x2", 5),
    ("uneven", "firstn0x1", 3),     # three steps
    ("uneven", "ec1", 6),
    ("even", "rep1", 3),
]


@pytest.mark.parametrize("which,rule,result_max", CASES)
def test_do_rule_batch_equals_host_and_reference(request, which, rule,
                                                 result_max):
    fx = request.getfixturevalue(which)
    xs = np.arange(700, dtype=np.int64) * 2654435761 % (1 << 32)
    got = fx.dm.do_rule_batch(RULE_IDS[rule], xs, result_max,
                              np.asarray(fx.w, np.int32))
    holes = 0
    for i, x in enumerate(xs):
        host = fx.host.do_rule(RULE_IDS[rule], int(x), result_max, fx.w)
        plain = fx.ref.do_rule(fx.ref_rules[rule], int(x), result_max, fx.w)
        assert host == plain, (rule, int(x))
        assert list(got[i]) == padded(host, got.shape[1]), (rule, int(x))
        holes += host.count(ITEM_NONE)
    if (which, rule) in (("uneven", "lrc"), ("uneven", "indep4x2")):
        assert holes > 0        # the case has the holes it is there for


def test_lrc_rows_keep_their_groups_in_place(uneven):
    """Positions 0-3 are one rack's, 4-7 another's; the rack of two hosts,
    one of them out, leaves three holes inside its own half, nothing
    shifts."""
    fx = uneven
    parent = {i: b.id for b in fx.map.buckets.values() for i in b.items}
    got = fx.dm.do_rule_batch(RULE_IDS["lrc"], np.arange(400), 8,
                              np.asarray(fx.w, np.int32))
    small = 0
    for row in got:
        halves = [[int(o) for o in row[lo:lo + 4] if o != ITEM_NONE]
                  for lo in (0, 4)]
        racks = [{parent[parent[o]] for o in half} for half in halves]
        assert all(len(r) == 1 for r in racks) and racks[0] != racks[1]
        hosts = [parent[o] for half in halves for o in half]
        assert len(set(hosts)) == len(hosts)
        small += any(len(half) == 1 for half in halves)
    assert small > 100      # rack 0 is chosen for two rows of three


@pytest.mark.parametrize("which,rule,size,can_shift,pg_num", [
    ("uneven", "lrc", 8, False, 600),
    ("even", "firstn2x2", 4, True, 600),
    # the attempt structure (three full-width rounds, flags, resolve chain)
    ("uneven", "lrc", 8, False, 16384),
])
def test_whole_pool_pass_equals_host(request, which, rule, size, can_shift,
                                     pg_num):
    fx = request.getfixturevalue(which)
    mask = (1 << (pg_num - 1).bit_length()) - 1
    exists = np.ones(fx.n, bool)
    isup = np.ones(fx.n, bool)
    isup[11] = False
    st = fx.dm.map_pool_state(RULE_IDS[rule], size, pg_num, pg_num, mask, 1,
                              True, np.asarray(fx.w, np.int32), exists,
                              isup, None, can_shift)
    assert st.steps == 2 and st.lanes >= pg_num
    raw, up = np.array(st.raw)[:pg_num], np.array(st.up)
    pps = pps_seed_v(np.arange(pg_num), pg_num, mask, 1, True)
    for ps in range(0, pg_num, 1 if pg_num < 1000 else 41):
        want = padded(fx.host.do_rule(RULE_IDS[rule], int(pps[ps]), size,
                                      fx.w), size)
        assert list(raw[ps]) == want, ps
        if not can_shift:
            assert list(up[ps]) == [ITEM_NONE if o == 11 else o
                                    for o in want], ps
    assert st.none_slots == int((up == ITEM_NONE).sum())
    if rule == "lrc":
        assert st.none_slots >= 2 * (pg_num // 2)
    if pg_num >= 16384:
        # rack 0 cannot fill four positions: the first round leaves those
        # lanes with an undefined slot, and so do rejections elsewhere
        assert 0 < st.retry_lanes <= st.lanes
        assert st.resolve_lanes > 0
    else:
        # firstn has no such round, and a small batch runs the full loops
        assert st.retry_lanes == 0
    # no tail without the Pallas lane kernels, whatever the lanes
    assert st.indep_tail_lanes == 0


_TAIL_PASSES: dict = {}


def tail_pass(fx, rule: str, size: int, pg_num: int = 16384) -> dict:
    """One whole-pool pass of `rule` at 16,384 lanes with the Pallas
    lane kernels (interpret mode: a minute of compile a program), once
    for the module: its MapState, and the jitted pool and resolve
    programs it ran last, each with the shapes it was given and the
    arguments that built it, for the tests that read their text."""
    if rule in _TAIL_PASSES:
        return _TAIL_PASSES[rule]
    import jax
    n, ran = fx.n, {}
    exists, isup = np.ones(n, bool), np.ones(n, bool)
    isup[11] = False

    def spied(name):
        real = getattr(DeviceMapper, name)

        def fetch(self, *key, **kw):
            fn = real(self, *key, **kw)

            def call(*args):
                ran[name] = (fn, [jax.ShapeDtypeStruct(a.shape, a.dtype)
                                  for a in args], key)
                return fn(*args)
            return call
        return fetch

    mp = pytest.MonkeyPatch()
    mp.setenv("CEPH_TPU_PALLAS_INTERPRET", "1")
    for name in ("_compiled_pool", "_compiled_device_resolve"):
        mp.setattr(DeviceMapper, name, spied(name))
    try:
        st = fx.dm.map_pool_state(
            RULE_IDS[rule], size, pg_num, pg_num, pg_num - 1, 1, True,
            np.asarray(fx.w, np.int32), exists, isup, None, False)
    finally:
        mp.undo()
    _TAIL_PASSES[rule] = {"state": st, "pool": ran["_compiled_pool"],
                          "resolve": ran["_compiled_device_resolve"]}
    return _TAIL_PASSES[rule]


def compiled_text(program) -> str:
    """The compiled module of a jitted program that has run with these
    shapes: the executable the call made, no second compile."""
    fn, shapes, _key = program
    return fn.lower(*shapes).compile().as_text()


@pytest.mark.parametrize("rule,size", [("lrc", 8), ("ec1", 6)])
def test_whole_pool_pass_with_the_indep_tail_equals_host(
        monkeypatch, wide, rule, size):
    """At 16,384 lanes and with the Pallas lane kernels (interpret mode
    here) every indep step runs its first round over all its takes and
    its later rounds on the compacted takes that still had an undefined
    slot; rows equal the host engine's position by position, holes
    included (rack 0 cannot fill four positions; an OSD is down)."""
    from ceph_tpu.ops.crush import pallas_draw
    monkeypatch.setenv("CEPH_TPU_PALLAS_INTERPRET", "1")
    fx, pg_num = wide, 16384
    ruleno = RULE_IDS[rule]
    plan = fx.dm._plan(ruleno, size)
    tails = [fx.dm._tail_slots(ruleno, size, pg_num,
                               fx.dm._tail_start(ruleno, size, i), i)
             for i in range(len(plan.steps))]
    assert all(tails), tails
    widths = [pg_num * f // fx.dm.RC_ROW * kt
              for f, kt in zip(plan.lane_factors, tails)]
    assert all(n % pallas_draw.TL == 0 for n in widths), widths
    st = tail_pass(fx, rule, size)["state"]
    assert all(fx.dm.fm.descent_in_pallas[n] for n in widths)
    raw, up = np.array(st.raw), np.array(st.up)
    pps = pps_seed_v(np.arange(pg_num), pg_num, pg_num - 1, 1, True)
    holes = 0
    for ps in range(0, pg_num, 29):
        want = padded(fx.host.do_rule(ruleno, int(pps[ps]), size, fx.w),
                      size)
        assert list(raw[ps]) == want, ps
        assert list(up[ps]) == [ITEM_NONE if o == 11 else o
                                for o in want], ps
        holes += want.count(ITEM_NONE)
    assert holes > 0 or rule != "lrc"
    # takes seated in a tail: at most every take that failed, and (two
    # takes a lane in the second step) more than the lanes that did
    assert st.tail_lanes == 0 and fx.dm.tail_overflows == 0
    assert 0 < st.retry_lanes < st.lanes
    if rule == "lrc":
        assert st.retry_lanes < st.indep_tail_lanes < 3 * st.lanes
    else:
        assert 0 < st.indep_tail_lanes <= st.retry_lanes
    assert 0 < st.resolve_lanes < st.retry_lanes


# the scopes of ceph_tpu/trace/span.py's table the resolve program of a
# two-step indep pool reaches
RESOLVE_SCOPES = ("crush.resolve.compact", "crush.resolve.a",
                  "crush.resolve.b", "crush.resolve.c",
                  "crush.settle.draw", "crush.settle.post",
                  "crush.settle.scatter", "crush.resolve.counts",
                  "crush.seeds", "crush.step", "crush.descend",
                  "crush.is_out")


def test_the_two_step_pool_program_carries_its_scopes(wide):
    """Every instruction the pool program traced stands under a
    registered scope but the chunk loop's own, each stage of the table
    is there, and a descent is found under a first round and under a
    tail's rounds: the paths the device trace is split by."""
    from tests.test_scopes import POOL_SCOPES, scope_paths, scoped_share
    paths = scope_paths(compiled_text(tail_pass(wide, "lrc", 8)["pool"]))
    seen = {name for p in paths for name in p}
    assert seen == POOL_SCOPES, seen ^ POOL_SCOPES
    assert scoped_share(paths) >= 95.0, scoped_share(paths)
    assert ("crush.first", "crush.descend") in paths
    assert ("crush.tail.rounds", "crush.descend") in paths
    assert ("crush.tail.rounds", "crush.is_out") in paths
    assert ("crush.tail.move", "crush.seeds") in paths
    # no tail inside a tail, no round outside first or a tail
    assert not any("crush.tail.move" in p and "crush.tail.rounds" in p
                   for p in paths)
    assert all(p[0] in ("crush.first", "crush.tail.rounds")
               for p in paths if "crush.descend" in p), paths


def test_the_resolve_program_carries_its_stages(wide):
    """Each of the three stages holds a draw, a post-process and a
    scatter; compaction and the counts stand beside them."""
    from tests.test_scopes import scope_paths, scoped_share
    paths = scope_paths(compiled_text(tail_pass(wide, "lrc", 8)["resolve"]))
    seen = {name for p in paths for name in p}
    # stage A's lanes (16,384 slots) run the attempt structure: its
    # rounds are "first" rounds of that stage
    assert seen == set(RESOLVE_SCOPES) | {"crush.first"}, seen
    assert scoped_share(paths) >= 95.0, scoped_share(paths)
    for stage in ("crush.resolve.a", "crush.resolve.b", "crush.resolve.c"):
        for part in ("crush.settle.draw", "crush.settle.post",
                     "crush.settle.scatter"):
            assert any(p[:2] == (stage, part) for p in paths), (stage, part)
        assert any(p[:2] == (stage, "crush.settle.draw")
                   and p[-1] == "crush.descend" for p in paths), stage
    assert ("crush.resolve.compact",) in paths
    assert ("crush.resolve.counts",) in paths
    # a stage never nests in another
    assert not any(sum(n.startswith("crush.resolve.") for n in p) > 1
                   for p in paths)


def test_a_scope_adds_no_operation_to_the_resolve_program(
        monkeypatch, wide):
    """The resolve program lowered again with `scope` a null context:
    the same StableHLO, so a scope is metadata of the instructions and
    nothing else (tests/test_crush_device.py::TestDenseTail holds the
    pool program with a tail to the same)."""
    from tests.test_scopes import assert_same_without_scopes
    fn, shapes, key = tail_pass(wide, "lrc", 8)["resolve"]
    monkeypatch.setenv("CEPH_TPU_PALLAS_INTERPRET", "1")
    assert_same_without_scopes(
        monkeypatch, fn.lower(*shapes), shapes,
        lambda: DeviceMapper(wide.map)._compiled_device_resolve(*key))


def test_one_step_pass_counts_one_step(even):
    st = even.dm.map_pool_state(
        RULE_IDS["rep1"], 3, 256, 256, 255, 1, True,
        np.asarray(even.w, np.int32), np.ones(even.n, bool),
        np.ones(even.n, bool), None, True)
    assert (st.steps, st.retry_lanes, st.tail_lanes,
            st.indep_tail_lanes) == (1, 0, 0, 0)


OUTSIDE = {
    "two takes": [(TAKE, -1, 0), (CHOOSE_FIRSTN, 1, 2), (EMIT, 0, 0),
                  (TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 1, 1), (EMIT, 0, 0)],
    "firstn then indep": [(TAKE, -1, 0), (CHOOSE_FIRSTN, 2, 2),
                          (CHOOSELEAF_INDEP, 2, 1), (EMIT, 0, 0)],
    "indep then firstn": [(TAKE, -1, 0), (CHOOSE_INDEP, 2, 2),
                          (CHOOSELEAF_FIRSTN, 2, 1), (EMIT, 0, 0)],
    "a step below a chooseleaf": [(TAKE, -1, 0), (CHOOSELEAF_INDEP, 2, 2),
                                  (CHOOSE_INDEP, 2, 0), (EMIT, 0, 0)],
    "take after a choose": [(TAKE, -1, 0), (CHOOSE_INDEP, 2, 2),
                            (TAKE, -2, 0), (CHOOSE_INDEP, 2, 0),
                            (EMIT, 0, 0)],
}


@pytest.mark.parametrize("what", sorted(OUTSIDE))
def test_a_rule_outside_the_scope_still_raises(what):
    m, n = build(False)
    m.add_rule(OUTSIDE[what], id=50)
    dm = DeviceMapper(m)
    with pytest.raises(ValueError):
        dm.do_rule_batch(50, np.arange(16), 4, np.full(n, 0x10000, np.int32))
    with pytest.raises(ValueError):
        dm.map_pool_state(50, 4, 64, 64, 63, 1, True,
                          np.full(n, 0x10000, np.int32), np.ones(n, bool),
                          np.ones(n, bool))


def test_a_pool_outside_the_scope_is_counted_in_scalar_pools():
    """OSDMapMapping maps it on the host, says so, and the rows are the
    host engine's; the pool beside it still takes the device."""
    from ceph_tpu.osd.osdmap import (OSD_EXISTS, OSD_UP, POOL_TYPE_ERASURE,
                                     Incremental, OSDMap, PGPool, pg_t)
    from ceph_tpu.parallel.mapping import OSDMapMapping
    crush, n = build(False)
    crush.add_rule(OUTSIDE["two takes"], id=50)
    m = OSDMap()
    inc = Incremental(epoch=1)
    inc.new_max_osd = n
    inc.new_crush = crush
    inc.new_pools[1] = PGPool(id=1, name="two", pg_num=16, size=2,
                              crush_rule=50)
    inc.new_pools[2] = PGPool(id=2, name="lrc", type=POOL_TYPE_ERASURE,
                              pg_num=16, size=8, min_size=5,
                              crush_rule=RULE_IDS["lrc"])
    m.apply_incremental(inc)
    inc = m.new_incremental()
    for o in range(n):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = 0x10000
    m.apply_incremental(inc)
    mp = OSDMapMapping(m)
    assert (mp.device_pools, mp.scalar_pools) == (1, 1)
    assert mp.rule_steps == {2: 2}
    for pool in (1, 2):
        for ps in range(16):
            assert mp.get(pg_t(pool, ps)) == m.pg_to_up_acting_osds(
                pg_t(pool, ps)), (pool, ps)
