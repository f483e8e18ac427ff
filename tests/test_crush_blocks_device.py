"""Rules of several TAKE/EMIT blocks on the device mapper: a stretch
cluster's `take site1; chooseleaf firstn 2 type host; emit; take site2;
...` (doc/rados/operations/stretch-mode.rst), an indep pair of blocks and
blocks of two shapes, held to the host engine and to the benchmark's
plain reference (benchmark/reference/crush_rules_ref.py) on small
two-datacenter maps, through do_rule_batch and through the whole-pool
pass; and a rule of two blocks held to its two one-block rules, whose
shape upstream's C answered (tests/golden/crush_mappings.json has no
rule of two blocks)."""

import numpy as np
import pytest

from benchmark.drivers.crush_churn_rules import reference_of
from ceph_tpu.models.crushmap import (CHOOSELEAF_FIRSTN, CHOOSELEAF_INDEP,
                                      EMIT, ITEM_NONE, STRAW2, TAKE,
                                      CrushMap)
from ceph_tpu.ops.crush.device import DeviceMapper
from ceph_tpu.ops.crush.hashes import pps_seed_v
from ceph_tpu.ops.crush.host import Mapper

SITE1, SITE2, ROOT = -2, -3, -1


def block(take: int, op: int, n: int, want: int = 1) -> list:
    return [(TAKE, take, 0), (op, n, want), (EMIT, 0, 0)]


RULES = {
    "stretch": (block(SITE1, CHOOSELEAF_FIRSTN, 2)
                + block(SITE2, CHOOSELEAF_FIRSTN, 2)),
    "indep pair": (block(SITE1, CHOOSELEAF_INDEP, 2)
                   + block(SITE2, CHOOSELEAF_INDEP, 2)),
    # two shapes, run in turn: a firstn block under a site, an indep
    # block under the root
    "two shapes": (block(SITE1, CHOOSELEAF_FIRSTN, 2)
                   + block(ROOT, CHOOSELEAF_INDEP, 3)),
    # the blocks alone, for the rule put together from them
    "site1 firstn": block(SITE1, CHOOSELEAF_FIRSTN, 2),
    "site2 firstn": block(SITE2, CHOOSELEAF_FIRSTN, 2),
    "site1 indep": block(SITE1, CHOOSELEAF_INDEP, 2),
    "site2 indep": block(SITE2, CHOOSELEAF_INDEP, 2),
    "root indep": block(ROOT, CHOOSELEAF_INDEP, 3),
}
RULE_IDS = {name: i for i, name in enumerate(RULES)}


def build(hosts: int, osds: int = 3) -> tuple:
    """Two datacenters of `hosts` hosts of `osds` OSDs under one root,
    weights seeded unevenly; datacenter s is bucket -(2 + s)."""
    m = CrushMap()
    m.types = {0: "osd", 1: "host", 8: "datacenter", 11: "root"}
    rng = np.random.default_rng(40)
    osd, bid, sites = 0, -4, []
    for s in range(2):
        host_ids = []
        for _h in range(hosts):
            ws = [int(v) for v in rng.integers(0x8000, 0x20000, osds)]
            b = m.add_bucket(STRAW2, 1, list(range(osd, osd + osds)), ws,
                             id=bid)
            host_ids.append(b.id)
            osd, bid = osd + osds, bid - 1
        b = m.add_bucket(STRAW2, 8, host_ids,
                         [m.buckets[h].weight for h in host_ids],
                         id=-(2 + s), name="site%d" % (s + 1))
        sites.append(b.id)
    m.add_bucket(STRAW2, 11, sites, [m.buckets[s].weight for s in sites],
                 id=ROOT, name="default")
    for name, steps in RULES.items():
        m.add_rule(steps, id=RULE_IDS[name], name=name)
    return m, osd


def weights_of(n: int, hosts: int, thin: bool = False) -> list:
    """Seeded: four OSDs out and two reweighted, or (thin) every host of
    site1 but its last out besides, so that block 1 places one OSD."""
    rng = np.random.default_rng(41)
    w = [0x10000] * n
    for o in rng.choice(n, 6, replace=False)[:4]:
        w[int(o)] = 0
    for o in rng.choice(n, 2, replace=False):
        w[int(o)] = 0x9000
    if thin:
        for o in range(3 * (hosts - 1)):
            w[o] = 0
    return w


class Fixture:
    def __init__(self, hosts: int):
        self.hosts = hosts
        self.map, self.n = build(hosts)
        self.dm = DeviceMapper(self.map)
        self.host = Mapper(self.map)
        self.ref = reference_of(self.map, 0)[0]
        self.ref_rules = {name: reference_of(self.map, RULE_IDS[name])[1]
                          for name in RULES}

    def weights(self, thin: bool = False) -> list:
        return weights_of(self.n, self.hosts, thin)


@pytest.fixture(scope="module")
def small():
    """2 datacenters x 4 hosts x 3 OSDs."""
    return Fixture(4)


@pytest.fixture(scope="module")
def wide():
    """2 datacenters x 16 hosts x 3 OSDs: few enough draws collide for a
    whole pool's pass to seat its unplaced lanes in the tail."""
    return Fixture(16)


def padded(row: list, width: int) -> list:
    return list(row) + [ITEM_NONE] * (width - len(row))


CASES = [
    # (rule, result_max, thin)
    ("stretch", 4, False),
    ("stretch", 3, False),          # EMIT's cap inside the second block
    ("stretch", 4, True),           # one OSD in site1: site2's shift left
    ("indep pair", 4, False),
    ("indep pair", 3, False),
    ("indep pair", 4, True),        # site1's hole stays in place
    ("two shapes", 5, False),
    ("two shapes", 4, True),
]


@pytest.mark.parametrize("rule,result_max,thin", CASES)
def test_do_rule_batch_equals_host_and_reference(small, rule, result_max,
                                                 thin):
    fx = small
    w = fx.weights(thin)
    xs = np.arange(600, dtype=np.int64) * 2654435761 % (1 << 32)
    got = fx.dm.do_rule_batch(RULE_IDS[rule], xs, result_max,
                              np.asarray(w, np.int32))
    assert got.shape == (600, result_max)
    first = RULE_IDS["site1 indep" if rule == "indep pair"
                     else "site1 firstn"]
    short = 0
    for i, x in enumerate(xs):
        host = fx.host.do_rule(RULE_IDS[rule], int(x), result_max, w)
        plain = fx.ref.do_rule(fx.ref_rules[rule], int(x), result_max, w)
        assert host == plain, (rule, int(x))
        assert list(got[i]) == padded(host, result_max), (rule, int(x))
        short += [o != ITEM_NONE for o in fx.host.do_rule(
            first, int(x), result_max, w)].count(True) < 2
    # the case has what it is there for: site1's one OSD
    assert short == (600 if thin else 0)


@pytest.mark.parametrize("first,second,result_max", [
    ("site1 firstn", "site2 firstn", 4), ("site1 firstn", "site2 firstn", 3),
    ("site1 indep", "site2 indep", 4), ("site1 indep", "site2 indep", 3),
    ("site1 firstn", "root indep", 5), ("site1 firstn", "root indep", 4),
])
@pytest.mark.parametrize("thin", [False, True])
def test_two_blocks_are_their_one_block_rules_put_together(
        small, first, second, result_max, thin):
    """The device's rows of a rule of two blocks are the host engine's
    answers of its two one-block rules, put together and cut to
    result_max."""
    fx = small
    w = fx.weights(thin)
    both = {("site1 firstn", "site2 firstn"): "stretch",
            ("site1 indep", "site2 indep"): "indep pair",
            ("site1 firstn", "root indep"): "two shapes"}[first, second]
    xs = np.arange(300, dtype=np.int64) * 40503 % (1 << 32)
    got = fx.dm.do_rule_batch(RULE_IDS[both], xs, result_max,
                              np.asarray(w, np.int32))
    for i, x in enumerate(xs):
        apart = [o for r in (first, second)
                 for o in fx.host.do_rule(RULE_IDS[r], int(x), result_max,
                                          w)]
        assert list(got[i]) == padded(apart[:result_max], result_max), x


def whole_pool(fx, rule: str, size: int, pg_num: int, can_shift: bool,
               w: list):
    mask = (1 << (pg_num - 1).bit_length()) - 1
    exists, isup = np.ones(fx.n, bool), np.ones(fx.n, bool)
    isup[13] = False
    st = fx.dm.map_pool_state(RULE_IDS[rule], size, pg_num, pg_num, mask,
                              1, True, np.asarray(w, np.int32), exists,
                              isup, None, can_shift)
    raw, up = np.array(st.raw)[:pg_num], np.array(st.up)
    pps = pps_seed_v(np.arange(pg_num), pg_num, mask, 1, True)
    for ps in range(0, pg_num, 1 if pg_num < 1000 else 37):
        want = padded(fx.host.do_rule(RULE_IDS[rule], int(pps[ps]), size,
                                      w), size)
        assert list(raw[ps]) == want, ps
        kept = [ITEM_NONE if o == 13 else o for o in want]
        if can_shift:
            kept = padded([o for o in kept if o != ITEM_NONE], size)
        assert list(up[ps]) == kept, ps
    assert (st.blocks, st.steps) == (2, 2)
    return st


@pytest.mark.parametrize("rule,size,thin", [
    (rule, size, thin) for rule, size, thin in CASES])
def test_whole_pool_pass_equals_host(small, rule, size, thin):
    """600 PGs: every round over every lane, up from raw on the device
    (a replicated pool's rows shift past the down OSD, an erasure
    pool's keep their holes)."""
    st = whole_pool(small, rule, size, 600, rule == "stretch",
                    small.weights(thin))
    assert st.tail_lanes == st.indep_tail_lanes == st.retry_lanes == 0


@pytest.mark.parametrize("rule,size,pg_num", [
    ("stretch", 4, 8192), ("indep pair", 4, 8192), ("two shapes", 5, 16384)])
def test_whole_pool_pass_with_the_tail_equals_host(monkeypatch, wide, rule,
                                                   size, pg_num):
    """With the Pallas lane kernels (interpret mode here) and 16,384
    lanes a choose: the two blocks of one shape stacked into one choose
    (8192 PGs), or blocks of two shapes run in turn (16,384 PGs).  The
    first round runs over all the lanes, those it leaves unplaced are
    replayed on the compacted tail (firstn: the block's; indep: the
    step's), and the rows equal the host engine's; a tail's count holds
    both blocks' lanes."""
    from ceph_tpu.ops.crush import pallas_draw
    monkeypatch.setenv("CEPH_TPU_PALLAS_INTERPRET", "1")
    fx, ruleno = wide, RULE_IDS[rule]
    plan = fx.dm._plan(ruleno, size)
    stacked = len(plan.groups) == 1
    assert plan.groups == (((0, 1),) if stacked else ((0,), (1,)))
    for block, _step in fx.dm._tail_keys(ruleno, size):
        slots = fx.dm._tail_slots(ruleno, size, pg_num, fx.dm._tail_start(
            ruleno, size, 0, block), 0, block)
        lanes = (2 if stacked else 1) * pg_num
        assert slots and lanes // fx.dm.RC_ROW * slots % pallas_draw.TL == 0
    st = whole_pool(fx, rule, size, pg_num, rule == "stretch",
                    fx.weights())
    assert fx.dm.tail_overflows == 0
    assert fx.dm.fm.descent_in_pallas[lanes]
    # firstn's tail and indep's, each where its block has one
    assert (st.tail_lanes > 0) == (rule != "indep pair")
    assert (st.indep_tail_lanes > 0) == (rule != "stretch")
    assert 0 < st.tail_lanes + st.indep_tail_lanes < 2 * lanes
    assert st.resolve_lanes < st.tail_lanes + st.indep_tail_lanes


@pytest.mark.parametrize("rule,size,incremental", [
    ("stretch", 4, True), ("stretch", 3, False), ("indep pair", 3, False)])
def test_a_remap_after_a_reweight_equals_host(small, rule, size,
                                              incremental):
    """A reweight down remaps only the lanes whose raw row holds the OSD
    where no entry a block places can be cut off by EMIT (an indep
    block's slots collide with the entry cut off); otherwise the whole
    pool again.  Rows equal the host engine's either way."""
    fx, pg_num = small, 600
    w = fx.weights()
    st = whole_pool(fx, rule, size, pg_num, rule == "stretch", w)
    w2 = list(w)
    w2[next(o for o in range(12, 24) if w[o])] = 0
    exists, isup = np.ones(fx.n, bool), np.ones(fx.n, bool)
    isup[13] = False
    st2 = st.remap(np.asarray(w2, np.int32), exists, isup, None)
    assert (st2.lanes < st.lanes) == incremental
    raw = np.array(st2.raw)[:pg_num]
    mask = (1 << (pg_num - 1).bit_length()) - 1
    pps = pps_seed_v(np.arange(pg_num), pg_num, mask, 1, True)
    for ps in range(pg_num):
        want = fx.host.do_rule(RULE_IDS[rule], int(pps[ps]), size, w2)
        assert list(raw[ps]) == padded(want, size), ps


def test_a_pool_of_the_stretch_rule_maps_on_the_device(small):
    """OSDMapMapping maps it on the device through the pool pass, says
    how many blocks ran, and its rows are OSDMap's own."""
    from ceph_tpu.osd.osdmap import (OSD_EXISTS, OSD_UP, Incremental,
                                     OSDMap, PGPool, pg_t)
    from ceph_tpu.parallel.mapping import OSDMapMapping
    m = OSDMap()
    inc = Incremental(epoch=1)
    inc.new_max_osd = small.n
    inc.new_crush = small.map
    inc.new_pools[1] = PGPool(id=1, name="stretch", pg_num=64, size=4,
                              min_size=2, crush_rule=RULE_IDS["stretch"])
    m.apply_incremental(inc)
    inc = m.new_incremental()
    for o, w in enumerate(small.weights()):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = w
    m.apply_incremental(inc)
    mp = OSDMapMapping(m)
    assert (mp.device_pools, mp.scalar_pools) == (1, 0)
    assert mp.rule_blocks == {1: 2} and mp.rule_steps == {1: 2}
    for ps in range(64):
        assert mp.get(pg_t(1, ps)) == m.pg_to_up_acting_osds(pg_t(1, ps))


def test_the_stretch_pool_program_emits_under_its_scope(small):
    """The pool program of a rule of two blocks puts its rows together
    under `crush.emit`, and nothing of a descent stands there."""
    import jax
    import jax.numpy as jnp
    from tests.test_scopes import scope_paths
    dm = small.dm
    fn = dm._compiled_pool(RULE_IDS["stretch"], 4, True, False, 600, 1023,
                           1, True, 600, 1)
    shapes = [jax.ShapeDtypeStruct((small.n,), d)
              for d in (jnp.int32, bool, bool, jnp.int32)]
    paths = scope_paths(fn.lower(*shapes).compile().as_text())
    emitted = [p for p in paths if "crush.emit" in p]
    assert emitted and all(p == ("crush.emit",) for p in emitted)


def test_a_one_block_pool_program_gains_no_operation(monkeypatch, small):
    """The pool and resolve programs of a rule of one block are lowered
    with the block code made to raise wherever it is reached: they lower
    all the same, and no instruction stands under `crush.emit`, so a
    one-block rule's programs are the ones it had before blocks."""
    import jax
    import jax.numpy as jnp
    from ceph_tpu.ops.crush import device as D

    def unreachable(*_a, **_kw):
        raise AssertionError("block code reached by a one-block rule")

    monkeypatch.setenv("CEPH_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(D, "_emit", unreachable)
    monkeypatch.setattr(D.DeviceMapper, "_emit_groups",
                        staticmethod(unreachable))
    dm, ruleno, n = DeviceMapper(small.map), RULE_IDS["site1 firstn"], 16384
    slots = dm._tail_slots(ruleno, 2, n, dm.TAIL_KT)
    assert slots
    shapes = [jax.ShapeDtypeStruct((small.n,), d)
              for d in (jnp.int32, bool, bool, jnp.int32)]
    pool = dm._compiled_pool(ruleno, 2, True, False, n, n - 1, 1, True, n,
                             1, (slots,)).lower(*shapes)
    S = jax.ShapeDtypeStruct
    resolve = dm._compiled_device_resolve(
        ruleno, 2, True, False, n, n - 1, 1, True, 8192, 2048, 512, n, n,
        128, 4096, 8192).lower(
            S((n, 2), jnp.int32), S((n, 2), jnp.int32), S((n,), jnp.int32),
            S((n,), jnp.int8), S((6,), jnp.int32), *shapes)
    for lowered in (pool, resolve):
        text = lowered.as_text(debug_info=True)
        assert "rados.crush.descend" in text and "crush.emit" not in text
