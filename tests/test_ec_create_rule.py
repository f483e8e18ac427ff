"""create_rule: a codec turns its profile's crush-* keys into the rule
that places its chunks (ErasureCodeInterface::create_rule), and the mon
gives a new erasure pool that rule on a map that names the types."""

import asyncio

import pytest

from benchmark.drivers.crush_churn_rules import build_crush
from ceph_tpu.ec.plugin import ErasureCodePluginRegistry
from ceph_tpu.models.crushmap import (CHOOSE_INDEP, CHOOSELEAF_INDEP, EMIT,
                                      SET_CHOOSE_TRIES, SET_CHOOSELEAF_TRIES,
                                      TAKE, CrushMap)
from ceph_tpu.mon import Monitor
from ceph_tpu.store.kv import MemKV
from ceph_tpu.utils.context import Context

LRC = {"plugin": "lrc", "k": "4", "m": "2", "l": "3",
       "crush-locality": "rack", "crush-failure-domain": "host"}


def three_level(racks=3, hosts=4, osds=3, root="default") -> CrushMap:
    return build_crush({"crush": {
        "racks": racks, "hosts_per_rack": hosts, "osds_per_host": osds,
        "types": {"osd": 0, "host": 1, "rack": 2, "root": 3}, "root": root,
        "osd_weight": 0x10000}})


def codec(profile: dict):
    return ErasureCodePluginRegistry.instance().factory(
        profile["plugin"], dict(profile))


@pytest.mark.parametrize("profile,steps", [
    ({"plugin": "jerasure", "k": "2", "m": "1"},
     [(TAKE, -1, 0), (CHOOSELEAF_INDEP, 0, 1), (EMIT, 0, 0)]),
    ({"plugin": "isa", "k": "4", "m": "2", "crush-failure-domain": "rack"},
     [(TAKE, -1, 0), (CHOOSELEAF_INDEP, 0, 2), (EMIT, 0, 0)]),
    ({"plugin": "jerasure", "k": "2", "m": "1",
      "crush-failure-domain": "osd"},
     [(TAKE, -1, 0), (CHOOSELEAF_INDEP, 0, 0), (EMIT, 0, 0)]),
    (LRC,
     [(SET_CHOOSELEAF_TRIES, 5, 0), (SET_CHOOSE_TRIES, 100, 0),
      (TAKE, -1, 0), (CHOOSE_INDEP, 2, 2), (CHOOSELEAF_INDEP, 4, 1),
      (EMIT, 0, 0)]),
    ({"plugin": "lrc", "k": "4", "m": "2", "l": "3"},
     [(SET_CHOOSELEAF_TRIES, 5, 0), (SET_CHOOSE_TRIES, 100, 0),
      (TAKE, -1, 0), (CHOOSELEAF_INDEP, 0, 1), (EMIT, 0, 0)]),
    ({"plugin": "lrc", "k": "6", "m": "3", "l": "3",
      "crush-locality": "rack", "crush-failure-domain": "osd"},
     [(SET_CHOOSELEAF_TRIES, 5, 0), (SET_CHOOSE_TRIES, 100, 0),
      (TAKE, -1, 0), (CHOOSE_INDEP, 3, 2), (CHOOSELEAF_INDEP, 4, 0),
      (EMIT, 0, 0)]),
])
def test_the_exact_steps(profile, steps):
    crush = three_level()
    ruleno = codec(profile).create_rule("pool_rule", crush)
    assert crush.rules[ruleno].name == "pool_rule"
    assert crush.rules[ruleno].steps == steps


def test_a_rule_of_that_name_is_returned_as_it_is():
    crush = three_level()
    c = codec(LRC)
    first = c.create_rule("lrcpool", crush)
    assert c.create_rule("lrcpool", crush) == first
    assert len(crush.rules) == 1
    assert c.create_rule("other", crush) != first


@pytest.mark.parametrize("profile,crush,what", [
    (dict(LRC, **{"crush-locality": "room"}), three_level(), "room"),
    (dict(LRC, **{"crush-failure-domain": "chassis"}), three_level(),
     "chassis"),
    ({"plugin": "jerasure", "k": "2", "m": "1",
      "crush-failure-domain": "row"}, three_level(), "row"),
    (dict(LRC, **{"crush-root": "ssd"}), three_level(), "ssd"),
    (LRC, three_level(root="site1"), "default"),
])
def test_a_name_the_map_lacks_is_an_error(profile, crush, what):
    with pytest.raises(ValueError, match=what):
        codec(profile).create_rule("r", crush)
    assert not crush.rules


def _mon_run(body):
    async def main():
        mon = Monitor(Context("mon"), store=MemKV())
        await mon.start()
        try:
            inc = mon._pending()
            inc.new_max_osd = 36
            mon._propose_pending()
            return body(mon)
        finally:
            await mon.shutdown()
    return asyncio.run(main())


def test_the_mon_gives_an_lrc_pool_its_own_rule_on_a_three_level_map():
    def body(mon):
        mon._run_command("osd setcrushmap",
                         {"crush": three_level().to_dict()})
        assert mon.osdmap.crush.types[2] == "rack"
        mon._run_command("osd erasure-code-profile set",
                         {"name": "LRCprofile", "profile": LRC})
        pid = mon._run_command("osd pool create", {
            "pool": "lrcpool", "pool_type": "erasure", "pg_num": 8,
            "erasure_code_profile": "LRCprofile"})["pool_id"]
        pool = mon.osdmap.pools[pid]
        rule = mon.osdmap.crush.rules[pool.crush_rule]
        assert pool.size == 8 and rule.name == "lrcpool"
        assert rule.steps[3:5] == [(CHOOSE_INDEP, 2, 2),
                                   (CHOOSELEAF_INDEP, 4, 1)]
        # the pool maps through it: two racks, four hosts in each
        from ceph_tpu.osd.osdmap import pg_t
        raw, _pps = mon.osdmap._pg_to_raw_osds(pool, pg_t(pid, 3))
        assert len(raw) == 8
        # a plain profile on the same map takes the base's rule
        pid2 = mon._run_command("osd pool create", {
            "pool": "ecpool", "pool_type": "erasure", "pg_num": 8})["pool_id"]
        rule2 = mon.osdmap.crush.rules[mon.osdmap.pools[pid2].crush_rule]
        assert rule2.steps == [(TAKE, -1, 0), (CHOOSELEAF_INDEP, 0, 1),
                               (EMIT, 0, 0)]
        # a profile that names a type the map lacks fails the command
        mon._run_command("osd erasure-code-profile set", {
            "name": "rooms", "profile": dict(
                LRC, **{"crush-locality": "room"})})
        with pytest.raises(ValueError, match="room"):
            mon._run_command("osd pool create", {
                "pool": "roompool", "pool_type": "erasure", "pg_num": 8,
                "erasure_code_profile": "rooms"})
        assert all(p.name != "roompool" for p in mon.osdmap.pools.values())
    _mon_run(body)


@pytest.mark.parametrize("per_host", [0, 3])
def test_on_the_maps_the_mon_builds_an_erasure_pool_keeps_rule_1(per_host):
    def body(mon):
        mon.ctx.conf.set("mon_crush_osds_per_host", per_host)
        inc = mon._pending()
        inc.new_crush = mon._crush_with(8)
        mon._propose_pending()
        before = dict(mon.osdmap.crush.rules)
        mon._run_command("osd erasure-code-profile set",
                         {"name": "LRCprofile", "profile": LRC})
        pid = mon._run_command("osd pool create", {
            "pool": "lrcpool", "pool_type": "erasure", "pg_num": 8,
            "erasure_code_profile": "LRCprofile"})["pool_id"]
        assert mon.osdmap.pools[pid].crush_rule == 1
        assert dict(mon.osdmap.crush.rules) == before
    _mon_run(body)
