"""OSDMap pipeline tests, modeled on src/test/osd/TestOSDMap.cc:
stable-mod/pps math, up/acting composition, temps, upmaps, primary
affinity, incrementals, and bulk-vs-scalar mapping parity."""

import random

import numpy as np
import pytest

from ceph_tpu.models.crushmap import (
    CHOOSELEAF_FIRSTN,
    CHOOSE_INDEP,
    EMIT,
    ITEM_NONE,
    STRAW2,
    TAKE,
    CrushMap,
)
from ceph_tpu.osd.osdmap import (
    FLAG_HASHPSPOOL,
    OSD_EXISTS,
    OSD_UP,
    POOL_TYPE_ERASURE,
    Incremental,
    OSDMap,
    PGPool,
    calc_bits_of,
    ceph_stable_mod,
    pg_t,
)
from ceph_tpu.parallel.mapping import OSDMapMapping, pps_for_pool


def make_cluster(n_hosts=5, per_host=4, pg_num=64):
    """A small cluster map: one straw2 root over hosts over osds, one
    replicated pool and one EC pool."""
    m = OSDMap()
    crush = CrushMap()
    host_ids = []
    dev = 0
    for h in range(n_hosts):
        items = list(range(dev, dev + per_host))
        dev += per_host
        b = crush.add_bucket(STRAW2, 1, items, [0x10000] * per_host,
                             id=-(h + 2))
        host_ids.append(b.id)
    crush.add_bucket(STRAW2, 2, host_ids,
                     [crush.buckets[h].weight for h in host_ids], id=-1)
    crush.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1), (EMIT, 0, 0)],
                   id=0)
    crush.add_rule([(TAKE, -1, 0), (CHOOSE_INDEP, 0, 0), (EMIT, 0, 0)],
                   id=1)

    n = n_hosts * per_host
    inc = Incremental(epoch=1)
    inc.new_max_osd = n
    inc.new_crush = crush
    inc.new_pools[1] = PGPool(id=1, name="rbd", pg_num=pg_num, size=3,
                              crush_rule=0)
    inc.new_pools[2] = PGPool(id=2, name="ecpool", pg_num=pg_num, size=5,
                              type=POOL_TYPE_ERASURE, crush_rule=1,
                              min_size=4)
    m.apply_incremental(inc)

    inc = m.new_incremental()
    for o in range(n):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = 0x10000
        inc.new_up_client[o] = "127.0.0.1:%d" % (6800 + o)
    m.apply_incremental(inc)
    return m


class TestBasics:
    def test_stable_mod(self):
        # pg_num 12: mask 15; inputs whose low bits exceed 11 fold back
        assert ceph_stable_mod(11, 12, 15) == 11
        assert ceph_stable_mod(13, 12, 15) == 13 & 7
        assert calc_bits_of(11) == 4

    def test_object_to_pg_deterministic(self):
        m = make_cluster()
        pg1 = m.object_locator_to_pg("foo", 1)
        pg2 = m.object_locator_to_pg("foo", 1)
        assert pg1 == pg2
        assert m.object_locator_to_pg("bar", 1) != pg1

    def test_pps_vector_matches_scalar(self):
        pool = PGPool(id=7, name="x", pg_num=48)
        ps = np.arange(48)
        vec = pps_for_pool(pool, ps)
        for i in range(48):
            assert vec[i] == pool.raw_pg_to_pps(pg_t(7, i))

    def test_mapping_complete_and_sized(self):
        m = make_cluster()
        for ps in range(64):
            up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(1, ps))
            assert len(up) == 3 and upp in up
            assert len(set(up)) == 3
            up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(2, ps))
            assert len(up) == 5
        # unknown pool / out-of-range ps
        assert m.pg_to_up_acting_osds(pg_t(9, 0)) == ([], -1, [], -1)
        assert m.pg_to_up_acting_osds(pg_t(1, 64)) == ([], -1, [], -1)

    def test_failure_domain_separation(self):
        m = make_cluster()
        for ps in range(64):
            up, _, _, _ = m.pg_to_up_acting_osds(pg_t(1, ps))
            hosts = {o // 4 for o in up}
            assert len(hosts) == 3, "two replicas share a host"


class TestStateChanges:
    def test_down_osd_removed_from_up(self):
        m = make_cluster()
        victim_pg = pg_t(1, 5)
        up0, _, _, _ = m.pg_to_up_acting_osds(victim_pg)
        victim = up0[0]
        inc = m.new_incremental()
        inc.new_state[victim] = OSD_UP  # xor: clears UP
        m.apply_incremental(inc)
        up, _, _, _ = m.pg_to_up_acting_osds(victim_pg)
        assert victim not in up
        assert len(up) == 2  # replicated shifts left

    def test_down_osd_leaves_hole_in_ec(self):
        m = make_cluster()
        victim_pg = pg_t(2, 9)
        up0, _, _, _ = m.pg_to_up_acting_osds(victim_pg)
        victim = up0[2]
        inc = m.new_incremental()
        inc.new_state[victim] = OSD_UP
        m.apply_incremental(inc)
        up, _, _, _ = m.pg_to_up_acting_osds(victim_pg)
        assert up[2] == ITEM_NONE
        assert len(up) == 5

    def test_out_osd_remapped(self):
        m = make_cluster()
        pgid = pg_t(1, 3)
        up0, _, _, _ = m.pg_to_up_acting_osds(pgid)
        victim = up0[1]
        inc = m.new_incremental()
        inc.new_weight[victim] = 0  # marked out -> crush reweight rejects
        m.apply_incremental(inc)
        up, _, _, _ = m.pg_to_up_acting_osds(pgid)
        assert victim not in up
        assert len(up) == 3  # remapped to a replacement

    def test_pg_temp_overrides_acting(self):
        m = make_cluster()
        pgid = pg_t(1, 7)
        up, upp, _, _ = m.pg_to_up_acting_osds(pgid)
        other = [o for o in range(20) if o not in up][:3]
        inc = m.new_incremental()
        inc.new_pg_temp[pgid] = other
        m.apply_incremental(inc)
        up2, _, acting, actp = m.pg_to_up_acting_osds(pgid)
        assert up2 == up            # up unchanged
        assert acting == other      # acting overridden
        assert actp == other[0]
        # clearing restores
        inc = m.new_incremental()
        inc.new_pg_temp[pgid] = []
        m.apply_incremental(inc)
        _, _, acting3, _ = m.pg_to_up_acting_osds(pgid)
        assert acting3 == up

    def test_primary_temp(self):
        m = make_cluster()
        pgid = pg_t(1, 11)
        up, _, _, _ = m.pg_to_up_acting_osds(pgid)
        inc = m.new_incremental()
        inc.new_primary_temp[pgid] = up[2]
        m.apply_incremental(inc)
        _, _, _, actp = m.pg_to_up_acting_osds(pgid)
        assert actp == up[2]

    def test_pg_upmap(self):
        m = make_cluster()
        pgid = pg_t(1, 13)
        target = [0, 4, 8]
        inc = m.new_incremental()
        inc.new_pg_upmap[pgid] = target
        m.apply_incremental(inc)
        up, _, _, _ = m.pg_to_up_acting_osds(pgid)
        assert up == target

    def test_pg_upmap_items(self):
        m = make_cluster()
        pgid = pg_t(1, 17)
        up0, _, _, _ = m.pg_to_up_acting_osds(pgid)
        src = up0[1]
        dst = next(o for o in range(20)
                   if o not in up0 and o // 4 not in {x // 4 for x in up0})
        inc = m.new_incremental()
        inc.new_pg_upmap_items[pgid] = [(src, dst)]
        m.apply_incremental(inc)
        up, _, _, _ = m.pg_to_up_acting_osds(pgid)
        assert dst in up and src not in up

    def test_primary_affinity_zero_moves_primary(self):
        m = make_cluster()
        pgid = pg_t(1, 19)
        up0, upp0, _, _ = m.pg_to_up_acting_osds(pgid)
        inc = m.new_incremental()
        inc.new_primary_affinity[upp0] = 0
        m.apply_incremental(inc)
        up, upp, _, _ = m.pg_to_up_acting_osds(pgid)
        assert upp != upp0
        assert upp in up

    def test_epoch_must_follow(self):
        m = make_cluster()
        with pytest.raises(ValueError):
            m.apply_incremental(Incremental(epoch=m.epoch + 2))


class TestBulkMapping:
    def _assert_parity(self, m):
        mapping = OSDMapMapping(m)
        for pool in m.pools.values():
            for ps in range(pool.pg_num):
                pg = pg_t(pool.id, ps)
                up, upp, acting, actp = m.pg_to_up_acting_osds(pg)
                bup, bupp, bact, bactp = mapping.get(pg)
                assert (bup, bupp, bact, bactp) == (up, upp, acting, actp), \
                    "bulk mismatch at %s" % (pg,)

    def test_bulk_matches_scalar_healthy(self):
        self._assert_parity(make_cluster())

    def test_bulk_matches_scalar_with_churn(self):
        m = make_cluster()
        rng = random.Random(0)
        inc = m.new_incremental()
        for o in rng.sample(range(20), 4):
            inc.new_state[o] = OSD_UP          # down
        for o in rng.sample(range(20), 3):
            inc.new_weight[o] = rng.choice([0, 0x8000])
        inc.new_pg_temp[pg_t(1, 3)] = [1, 5, 9]
        inc.new_pg_upmap_items[pg_t(1, 4)] = [(rng.randrange(20),
                                               rng.randrange(20))]
        inc.new_primary_affinity[2] = 0x4000
        m.apply_incremental(inc)
        self._assert_parity(m)


def test_device_mapper_shared_per_crush_content():
    """Every daemon of a process decodes its own OSDMap; maps with the
    same crush content share one DeviceMapper (and its compiled
    programs), a different crush map gets its own."""
    from ceph_tpu.cli.osdmaptool import create_simple
    blob = create_simple(6, 8, 3).encode()
    a, b = OSDMap.decode(blob), OSDMap.decode(blob)
    assert a.crush is not b.crush
    assert a.device_mapper() is b.device_mapper()
    assert a.device_mapper().map is not a.crush     # a private copy
    other = create_simple(7, 8, 3)
    assert other.device_mapper() is not a.device_mapper()
