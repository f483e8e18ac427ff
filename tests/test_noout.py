"""The cluster-wide `noout` flag and the reads a pool serves under it.

Upstream's maintenance procedure (doc/rados/troubleshooting/
troubleshooting-osd.rst, "Stopping w/out Rebalancing"): `ceph osd set
noout`, stop the OSD, work, start it, `ceph osd unset noout`.  While the
flag stands the stopped OSD stays `in`, CRUSH keeps its position, and an
EC pool rebuilds every read of a data shard that lived there from the
survivors.
"""

import asyncio
import time

import pytest

from ceph_tpu.client.rados import RadosError
from ceph_tpu.mon import Monitor
from ceph_tpu.osd.osdmap import (CEPH_OSDMAP_NOOUT, CLUSTER_FLAGS,
                                 Incremental, OSDMap)
from ceph_tpu.store.kv import MemKV
from ceph_tpu.testing import LocalCluster
from ceph_tpu.testing.cluster import FAST_CONF
from ceph_tpu.utils.backoff import wait_for
from ceph_tpu.utils.context import Context

from benchmark.reference.rados_payload import Payloads

INTERVAL = FAST_CONF["mon_osd_down_out_interval"]


# -- the flags word on the map ---------------------------------------------


@pytest.mark.parametrize("before,new_flags,after", [
    (0, CEPH_OSDMAP_NOOUT, CEPH_OSDMAP_NOOUT),      # set
    (CEPH_OSDMAP_NOOUT, 0, 0),                      # cleared
    (CEPH_OSDMAP_NOOUT, -1, CEPH_OSDMAP_NOOUT),     # an epoch that leaves it
])
def test_flags_ride_an_incremental_through_encode_decode(before, new_flags,
                                                         after):
    m = OSDMap()
    m.flags = before
    inc = m.new_incremental()
    inc.new_flags = new_flags
    wire = Incremental.decode(inc.encode())
    assert wire.new_flags == new_flags
    m.apply_incremental(wire)
    assert m.flags == after
    assert m.test_flag(CEPH_OSDMAP_NOOUT) == bool(after)
    again = OSDMap.decode(m.encode())
    assert again.flags == after
    assert again.to_dict()["flags"] == after


def test_a_map_from_before_the_flags_word_decodes_with_none_set():
    d = OSDMap().to_dict()
    del d["flags"]
    assert OSDMap.from_dict(d).flags == 0
    inc = OSDMap().new_incremental().to_dict()
    del inc["new_flags"]
    assert Incremental.from_dict(inc).new_flags == -1


def test_noout_is_the_only_flag():
    assert CLUSTER_FLAGS == {"noout": CEPH_OSDMAP_NOOUT}


# -- the monitor -----------------------------------------------------------


def test_flag_survives_a_mon_restart():
    async def main():
        store = MemKV()
        mon = Monitor(Context("mon"), store=store)
        await mon.start()
        mon._run_command("osd set", {"prefix": "osd set", "key": "noout"})
        epoch = mon.osdmap.epoch
        assert mon.osdmap.test_flag(CEPH_OSDMAP_NOOUT)
        await mon.shutdown()

        mon2 = Monitor(Context("mon"), store=store)
        assert mon2.osdmap.epoch == epoch
        assert mon2.osdmap.test_flag(CEPH_OSDMAP_NOOUT)
        assert mon2._run_command("osd dump", {})["flags_set"] == ["noout"]
        mon2._run_command("osd unset", {"key": "noout"})
        assert mon2.osdmap.flags == 0 and mon2.osdmap.epoch == epoch + 1
        await mon2.msgr.shutdown()
        mon2.store.close()

    asyncio.run(asyncio.wait_for(main(), 20))


def test_set_and_unset_in_one_pending_epoch_compose():
    """Two flag commands inside one batch window act on the pending
    word, not on the committed one."""
    async def main():
        mon = Monitor(Context("mon"), store=MemKV())
        inc = mon._pending()
        inc.new_flags = CEPH_OSDMAP_NOOUT      # staged, not proposed
        mon._run_command("osd unset", {"key": "noout"})
        assert mon.osdmap.flags == 0
        await mon.msgr.shutdown()
        mon.store.close()

    asyncio.run(asyncio.wait_for(main(), 20))


@pytest.fixture(scope="module")
def maintenance():
    """One k2m1 cluster walked through the procedure; every step's
    observation is kept for the cases below."""
    seen = {}

    async def refused(c, prefix, key):
        try:
            await c.client.mon_command(prefix, key=key)
        except RadosError as e:
            return e.code
        return 0

    async def main():
        c = await LocalCluster(n_osds=3).start()
        try:
            pid = await c.create_pool("maint", pg_num=8,
                                      pool_type="erasure")
            await c.wait_health(pid)
            io = c.client.io_ctx("maint")
            pay = Payloads(29, 65536, 4)
            objects = list(range(24))
            for n in objects:
                await io.write_full(pay.name(n), pay.data(n))

            for prefix, key in (("osd set", "nodown"), ("osd set", ""),
                                ("osd unset", "noin"),
                                ("osd set", None)):
                seen["refused", prefix, key] = await refused(c, prefix,
                                                             key)
            seen["flags_after_refusals"] = c.leader().osdmap.flags

            await c.client.mon_command("osd set", key="noout")
            await c.client.wait_for_epoch(c.leader().osdmap.epoch)
            seen["dump_set"] = (await c.client.mon_command(
                "osd dump"))["flags_set"]
            seen["client_flags"] = c.client.osdmap.flags

            # the data holders, from the map alone
            om, k = c.client.osdmap, 2

            def data_osds(n):
                pg = om.pools[pid].raw_pg_to_pg(
                    om.object_locator_to_pg(pay.name(n), pid))
                return om.pg_to_up_acting_osds(pg)[2][:k]

            holders = {n: data_osds(n) for n in objects}
            victim = max(range(3), key=lambda o: sum(
                o in h for h in holders.values()))
            seen["hit"] = sum(victim in h for h in holders.values())
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            await asyncio.sleep(2.5 * INTERVAL)
            seen["in_past_interval"] = c.leader().osdmap.is_in(victim)
            seen["up_past_interval"] = c.leader().osdmap.is_up(victim)
            await wait_for(lambda: c.healthy(pid), 20,
                           what="undersized PGs active")

            def total(attr):
                return sum(getattr(o.ec, attr) for o in c.live_osds)

            before = {a: total(a) for a in (
                "reconstructed_reads", "reconstructed_read_bytes",
                "sub_read_bytes")}
            plans, wrong = [], 0
            for n in objects:
                wrong += await io.read(pay.name(n)) != pay.data(n)
                _osd, pg = c.pg_primary(pid, om.pools[pid].raw_pg_to_pg(
                    om.object_locator_to_pg(pay.name(n), pid)).ps)
                plans.append(dict(_osd.ec.last_read_plan))
            seen["wrong"] = wrong
            seen["plans"] = plans
            seen["delta"] = {a: total(a) - v for a, v in before.items()}
            seen["in_after_reads"] = c.leader().osdmap.is_in(victim)

            t_unset = time.monotonic()
            await c.client.mon_command("osd unset", key="noout")
            await wait_for(lambda: not c.leader().osdmap.is_in(victim),
                           20, what="auto-out once noout is cleared")
            seen["out_after_s"] = time.monotonic() - t_unset
            seen["dump_unset"] = (await c.client.mon_command(
                "osd dump"))["flags_set"]
        finally:
            await c.stop()

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CEPH_TPU_EC_OFFLOAD", "1")
        asyncio.run(asyncio.wait_for(main(), 240))
    return seen


@pytest.mark.parametrize("prefix,key", [
    ("osd set", "nodown"), ("osd set", ""), ("osd unset", "noin"),
    ("osd set", None)])
def test_an_unknown_flag_is_refused(maintenance, prefix, key):
    assert maintenance["refused", prefix, key] == -22
    assert maintenance["flags_after_refusals"] == 0


def test_set_shows_in_osd_dump_and_reaches_the_client(maintenance):
    assert maintenance["dump_set"] == ["noout"]
    assert maintenance["client_flags"] == CEPH_OSDMAP_NOOUT


def test_a_killed_osd_stays_in_past_the_down_out_interval(maintenance):
    assert maintenance["up_past_interval"] is False
    assert maintenance["in_past_interval"] is True
    assert maintenance["in_after_reads"] is True


def test_unset_starts_the_clock_over_and_the_osd_goes_out(maintenance):
    # down for 2.5 intervals already: out at once if the old clock ran
    assert maintenance["out_after_s"] >= 0.9 * INTERVAL
    assert maintenance["dump_unset"] == []


def test_every_object_reads_back_with_a_data_holder_stopped(maintenance):
    assert maintenance["wrong"] == 0


def test_reconstructed_reads_are_those_the_map_put_on_the_victim(
        maintenance):
    assert maintenance["hit"] > 0
    d = maintenance["delta"]
    assert d["reconstructed_reads"] == maintenance["hit"]
    assert d["reconstructed_read_bytes"] == maintenance["hit"] * 65536
    # k2m1 with one OSD stopped: every read fetches the one other shard
    assert d["sub_read_bytes"] == 24 * 32768


def test_degraded_reads_fetch_the_minimal_plan(maintenance):
    for plan in maintenance["plans"]:
        assert plan["widened"] is False
        assert plan["queried"] | {plan["local"]} == plan["minimal"], plan
