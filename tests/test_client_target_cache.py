"""The client looks a pg's acting set up once per map epoch.

RadosClient keeps a table pg -> (acting primary, acting) that belongs to
one map state: the host CRUSH descent runs the first time a pg is asked
for in an epoch, the table answers after, and every change of the map
(an incremental applied in place, a full map, a caller assigning
``osdmap``) drops it.  Keyed by the epoch the answer is exact: every
test below holds it to a fresh ``pg_to_up_acting_osds`` on the map the
client holds.
"""

import asyncio

import pytest

from ceph_tpu.client.rados import RadosClient
from ceph_tpu.models.crushmap import (CHOOSELEAF_FIRSTN, EMIT, STRAW2, TAKE,
                                      CrushMap)
from ceph_tpu.msg.messages import MOSDMapMsg
from ceph_tpu.osd.osdmap import (OSD_EXISTS, OSD_UP, Incremental, OSDMap,
                                 PGPool, pg_t)
from ceph_tpu.testing import LocalCluster

POOL, PG_NUM, N_OSDS = 1, 8, 6


def run(coro, timeout=300):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


def tiny_map() -> OSDMap:
    """Six OSDs on six hosts, one replicated pool of 8 PGs, size 3."""
    m = OSDMap()
    crush = CrushMap()
    hosts = [crush.add_bucket(STRAW2, 1, [o], [0x10000], id=-(o + 2)).id
             for o in range(N_OSDS)]
    crush.add_bucket(STRAW2, 2, hosts, [0x10000] * N_OSDS, id=-1)
    crush.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1), (EMIT, 0, 0)],
                   id=0)
    inc = Incremental(epoch=1)
    inc.new_max_osd = N_OSDS
    inc.new_crush = crush
    inc.new_pools[POOL] = PGPool(id=POOL, name="p", pg_num=PG_NUM, size=3,
                                 crush_rule=0)
    m.apply_incremental(inc)
    inc = m.new_incremental()
    for o in range(N_OSDS):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = 0x10000
        inc.new_up_client[o] = "127.0.0.1:%d" % (6800 + o)
    m.apply_incremental(inc)
    return m


class Counted:
    """A client on `m` whose map's CRUSH descents are counted."""

    def __init__(self, m: OSDMap):
        self.client = RadosClient("127.0.0.1:1", seed=5)
        self.calls = 0
        self.adopt(m)

    def adopt(self, m: OSDMap) -> None:
        """Assign the map directly, as a test or a caller may."""
        real = m.pg_to_up_acting_osds

        def counting(pg):
            self.calls += 1
            return real(pg)

        m.pg_to_up_acting_osds = counting
        self.client.osdmap = m


def fresh(m: OSDMap, pg: pg_t) -> tuple:
    """What the client must answer for `pg` on `m` (by the class's own
    method: the instance's may be Counted's)."""
    _up, _upp, acting, primary = OSDMap.pg_to_up_acting_osds(m, pg)
    return primary, tuple(acting)


def all_pgs():
    return [pg_t(POOL, ps) for ps in range(PG_NUM)]


def oid_in(m: OSDMap, pg: pg_t, tag: str = "o") -> str:
    pool = m.pools[pg.pool]
    return next(n for n in ("%s%d" % (tag, i) for i in range(10000))
                if pool.raw_pg_to_pg(m.object_locator_to_pg(n, pg.pool))
                == pg)


def test_second_lookup_of_a_pg_runs_no_crush():
    m = tiny_map()
    t = Counted(m)
    c = t.client
    a, b = oid_in(m, pg_t(POOL, 3), "a"), oid_in(m, pg_t(POOL, 3), "b")
    first = c._calc_target(POOL, a)
    assert t.calls == 1 and (c.target_hits, c.target_misses) == (0, 1)
    assert first == (fresh(m, pg_t(POOL, 3))[0], pg_t(POOL, 3),
                     fresh(m, pg_t(POOL, 3))[1])
    assert c._calc_target(POOL, a) == first
    assert c._calc_target(POOL, b) == first     # another name, same pg
    assert t.calls == 1 and (c.target_hits, c.target_misses) == (2, 1)
    other = oid_in(m, pg_t(POOL, 4))
    assert c._calc_target(POOL, other)[1] == pg_t(POOL, 4)
    assert t.calls == 2 and c.target_misses == 2


def test_stored_answer_is_immutable():
    c = Counted(tiny_map()).client
    primary, _pgid, acting = c._calc_target(POOL, "x")
    assert isinstance(acting, tuple) and acting[0] == primary
    assert c._calc_target(POOL, "x")[2] is acting


def _primary_down(m: OSDMap):
    inc = m.new_incremental()
    inc.new_state[fresh(m, pg_t(POOL, 0))[0]] = OSD_UP      # xor: down
    return inc


def _pg_temp(m: OSDMap):
    inc = m.new_incremental()
    _p, acting = fresh(m, pg_t(POOL, 1))
    inc.new_pg_temp[pg_t(POOL, 1)] = list(reversed(acting))
    return inc


def _upmap(m: OSDMap):
    inc = m.new_incremental()
    _p, acting = fresh(m, pg_t(POOL, 2))
    spare = next(o for o in range(N_OSDS) if o not in acting)
    inc.new_pg_upmap_items[pg_t(POOL, 2)] = [(acting[0], spare)]
    return inc


CHANGES = {"primary-down": _primary_down, "pg-temp": _pg_temp,
           "upmap": _upmap}


@pytest.mark.parametrize("how", ["incremental", "full-map"])
@pytest.mark.parametrize("change", sorted(CHANGES))
def test_map_change_drops_the_table(change, how):
    """Delivered as the mon delivers it; every pg of the pool then reads
    as a fresh descent on the new map does, and the change is one the
    old table would have got wrong."""
    t = Counted(tiny_map())
    c = t.client
    before = {pg: c._pg_target(pg) for pg in all_pgs()}
    assert t.calls == PG_NUM and len(c._targets) == PG_NUM
    inc = CHANGES[change](c.osdmap)
    want = tiny_map()
    want.apply_incremental(inc)
    held = c.osdmap
    if how == "incremental":
        c._handle_map(MOSDMapMsg(full=None, incrementals=[inc.encode()]))
        assert c.osdmap is held             # mutated in place
    else:
        c._handle_map(MOSDMapMsg(full=want.encode(), incrementals=[]))
        assert c.osdmap is not held         # replaced
    assert c.osdmap.epoch == want.epoch and not c._targets
    after = {pg: c._pg_target(pg) for pg in all_pgs()}
    assert after == {pg: fresh(want, pg) for pg in all_pgs()}
    assert after != before
    assert c.target_misses == 2 * PG_NUM and c.target_hits == 0


def test_a_map_that_changed_nothing_keeps_the_table():
    """An incremental that does not follow the client's epoch is not
    applied (`changed` false): the table stands."""
    t = Counted(tiny_map())
    c = t.client
    for pg in all_pgs():
        c._pg_target(pg)
    stale = Incremental(epoch=c.osdmap.epoch)       # already have it
    c._handle_map(MOSDMapMsg(full=None, incrementals=[stale.encode()]))
    assert len(c._targets) == PG_NUM
    c._pg_target(pg_t(POOL, 0))
    assert t.calls == PG_NUM and c.target_hits == 1


@pytest.mark.parametrize("how", ["another-map-same-epoch",
                                 "same-map-next-epoch"])
def test_assigning_or_mutating_the_map_directly_drops_the_table(how):
    t = Counted(tiny_map())
    c = t.client
    for pg in all_pgs():
        c._pg_target(pg)
    if how == "another-map-same-epoch":
        m2 = tiny_map()
        acting = fresh(m2, pg_t(POOL, 5))[1]
        m2.pg_temp[pg_t(POOL, 5)] = list(reversed(acting))
        assert m2.epoch == c.osdmap.epoch
        t.adopt(m2)
    else:
        c.osdmap.apply_incremental(_primary_down(c.osdmap))
    m = c.osdmap
    calls = t.calls
    assert {pg: c._pg_target(pg) for pg in all_pgs()} == \
        {pg: fresh(m, pg) for pg in all_pgs()}
    assert t.calls == calls + PG_NUM
    assert c._targets_of == (m, m.epoch)


def test_cap_drops_and_refills():
    t = Counted(tiny_map())
    c = t.client
    c.TARGET_TABLE_CAP = 3
    m = c.osdmap
    for n, pg in enumerate(all_pgs()):
        assert c._pg_target(pg) == fresh(m, pg)
        assert len(c._targets) == n % 3 + 1     # 1, 2, 3, 1, 2, 3, 1, 2
    assert t.calls == PG_NUM
    # the newest refill answers; what went with a drop is computed again
    assert c._pg_target(pg_t(POOL, 7)) == fresh(m, pg_t(POOL, 7))
    assert t.calls == PG_NUM and c.target_hits == 1
    assert c._pg_target(pg_t(POOL, 0)) == fresh(m, pg_t(POOL, 0))
    assert t.calls == PG_NUM + 1 and len(c._targets) == 3
    assert RadosClient.TARGET_TABLE_CAP == 1 << 16


@pytest.mark.parametrize("pg", [pg_t(9, 0), pg_t(POOL, PG_NUM)],
                         ids=["no-pool", "ps-out-of-range"])
def test_a_pg_the_map_cannot_place_is_kept_for_its_epoch_only(pg):
    t = Counted(tiny_map())
    c = t.client
    assert c._pg_target(pg) == (-1, ()) == c._pg_target(pg)
    assert t.calls == 1 and c.target_hits == 1
    inc = c.osdmap.new_incremental()
    inc.new_pools[pg.pool] = PGPool(id=pg.pool, name="later",
                                    pg_num=2 * PG_NUM, size=3, crush_rule=0)
    c._handle_map(MOSDMapMsg(full=None, incrementals=[inc.encode()]))
    primary, acting = c._pg_target(pg)
    assert primary >= 0 and len(acting) == 3
    assert (primary, acting) == fresh(c.osdmap, pg)


def test_backoff_pruning_reads_the_new_map():
    """_handle_map prunes a backoff whose primary lost the pg, and keeps
    one whose primary still has it: both through the same lookup, after
    the old epoch's table went."""
    t = Counted(tiny_map())
    c = t.client
    lost = fresh(c.osdmap, pg_t(POOL, 0))[0]
    kept_pg = next(pg for pg in all_pgs()
                   if lost not in fresh(c.osdmap, pg)[1])
    kept = fresh(c.osdmap, kept_pg)[0]
    for pg in all_pgs():
        c._pg_target(pg)
    c._backoffs[(POOL, 0, None)] = (lost, 1)
    c._backoffs[(POOL, kept_pg.ps, "obj")] = (kept, 2)
    c._backoffs[(7, 0, None)] = (kept, 3)           # its pool is gone
    inc = _primary_down(c.osdmap)
    c._handle_map(MOSDMapMsg(full=None, incrementals=[inc.encode()]))
    assert c._backoffs == {(POOL, kept_pg.ps, "obj"): (kept, 2)}


@pytest.mark.parametrize("victim", ["primary", "replica"])
def test_op_in_flight_across_an_osds_death_is_retargeted(victim):
    """The op is sent on the old epoch's table to a set that has just
    lost a member; the new map drops the table, _scan_requests sees the
    new acting set and the op is acknowledged.  (A primary that lost a
    replica may answer before the map says so; the table still follows
    the map.)"""

    async def main():
        # the shipped grace: a compile on the shared loop (the pool's
        # map program) must not be read as a death before the one
        # this test stages (ROADMAP A-first)
        c = await LocalCluster(n_osds=4,
                               conf={"heartbeat_grace": 6.0}).start()
        try:
            pid = await c.create_pool("t", pg_num=4, size=3)
            await c.wait_health(pid)
            cl, io = c.client, c.client.io_ctx("t")
            await io.write_full("obj", b"v1")
            hits = cl.target_hits
            primary, pgid, acting = cl._calc_target(pid, "obj")
            assert cl.target_hits == hits + 1       # same epoch: the table
            dead = primary if victim == "primary" else acting[-1]
            await c.kill_osd(dead)
            assert cl.osdmap.is_up(dead)            # the map does not know
            epoch = cl.osdmap.epoch
            w = asyncio.ensure_future(io.write_full("obj", b"v2"))
            await asyncio.sleep(0)
            op, = [o for o in cl._inflight.values() if o.oid == "obj"]
            assert (op.target, op.acting) == (primary, acting)
            await asyncio.wait_for(w, 60)
            if victim == "primary":
                # nobody could answer before the new map came
                assert cl.osdmap.epoch > epoch
                assert op.sends >= 2 and dead not in op.acting
                assert op.target == op.acting[0] != dead
            await c.wait_osd_down(dead)
            assert dead not in cl._calc_target(pid, "obj")[2]
            for ps in range(4):
                pg = pg_t(pid, ps)
                assert cl._pg_target(pg) == fresh(cl.osdmap, pg)
            assert await io.read("obj") == b"v2"
            # rados ls resolves its PGs through the same lookup (a
            # hit or, if the mon marked the victim out meanwhile, a
            # miss on the newer map: one lookup a pg either way)
            lookups = cl.target_hits + cl.target_misses
            assert await cl.list_objects(pid) == ["obj"]
            assert cl.target_hits + cl.target_misses == lookups + 4
        finally:
            await c.stop()

    run(main())
