"""End-to-end cluster tests: mon + OSDs + client in one event loop.

The framework's fake-cluster tier (SURVEY §4.2/§4.3): real daemons and
real wire protocol over loopback TCP, in-process for determinism —
the moral equivalent of qa/standalone/ceph-helpers.sh run_mon/run_osd
plus librados_test_stub's in-process convenience.  The harness itself
lives in ceph_tpu.testing.cluster (shared with the thrasher and the
vstart CLI); this file keeps the end-to-end scenarios.
"""

import asyncio

import pytest

from ceph_tpu.client import ObjectNotFound
from ceph_tpu.osd.daemon import OSD
from ceph_tpu.testing.cluster import FAST_CONF, LocalCluster
from ceph_tpu.utils.context import Context


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


class Cluster(LocalCluster):
    """Back-compat shim: the scenarios below predate LocalCluster and
    address the single monitor as ``c.mon``."""

    def __init__(self, n_osds=3):
        super().__init__(n_osds=n_osds)

    @property
    def mon(self):
        return self.mons[0]

    @mon.setter
    def mon(self, value):
        # some scenarios hand-boot the monitor before start()
        if self.mons:
            self.mons[0] = value
        else:
            self.mons = [value]


def test_cluster_boot_and_pool_create():
    async def main():
        c = await Cluster(3).start()
        try:
            status = await c.client.mon_command("status")
            assert status["num_osds"] == 3
            assert status["num_up_osds"] == 3
            out = await c.client.mon_command(
                "osd pool create", pool="rbd", pg_num=8, size=3)
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
        finally:
            await c.stop()

    run(main())


def test_put_get_roundtrip():
    async def main():
        c = await Cluster(3).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="data", pg_num=8, size=3)
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("data")
            payloads = {}
            for i in range(20):
                oid = "obj-%d" % i
                data = bytes([i % 256]) * (100 + i * 37)
                payloads[oid] = data
                await io.write_full(oid, data)
            for oid, data in payloads.items():
                assert await io.read(oid) == data
                assert await io.stat(oid) == len(data)
            # omap + xattr round trip
            await io.omap_set("obj-0", {b"k1": b"v1", b"k2": b"v2"})
            kv = await io.omap_get("obj-0")
            assert kv == {b"k1": b"v1", b"k2": b"v2"}
            # delete
            await io.remove("obj-1")
            with pytest.raises(ObjectNotFound):
                await io.read("obj-1")
        finally:
            await c.stop()

    run(main())


def test_replication_on_all_acting():
    """Every acting osd holds every object replica after writes."""

    async def main():
        c = await Cluster(3).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="data", pg_num=8, size=3)
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("data")
            await io.write_full("x", b"payload")
            await asyncio.sleep(0.2)  # let replica acks land
            from ceph_tpu.store.objectstore import coll_t, hobject_t

            pool = c.client.osdmap.pools[pid]
            pgid = pool.raw_pg_to_pg(
                c.client.osdmap.object_locator_to_pg("x", pid))
            up, upp, acting, actingp = \
                c.client.osdmap.pg_to_up_acting_osds(pgid)
            assert len(acting) == 3
            for osd_id in acting:
                store = c.osds[osd_id].store
                data = store.read(coll_t.pg(pid, pgid.ps),
                                  hobject_t("x"))
                assert data == b"payload", "osd.%d missing" % osd_id
        finally:
            await c.stop()

    run(main())


def test_kill_osd_degraded_get_then_recover():
    """SURVEY §7 acceptance core: kill an osd, degraded get works, the
    cluster remaps + recovers, and bytes survive re-replication."""

    async def main():
        c = await Cluster(3).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="data", pg_num=8, size=3)
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("data")
            payloads = {}
            for i in range(12):
                oid = "k-%d" % i
                data = ("value-%d" % i).encode() * 50
                payloads[oid] = data
                await io.write_full(oid, data)

            victim = 2
            await c.kill_osd(victim)
            # heartbeats detect the failure; mon marks it down
            epoch0 = c.client.osdmap.epoch
            t0 = asyncio.get_running_loop().time()
            while c.client.osdmap.is_up(victim):
                assert asyncio.get_running_loop().time() - t0 < 30, \
                    "mon never marked osd.%d down" % victim
                await asyncio.sleep(0.05)
            assert c.client.osdmap.epoch > epoch0

            # degraded reads: remaining replicas serve everything
            for oid, data in payloads.items():
                assert await io.read(oid) == data

            # degraded write still works
            await io.write_full("post-kill", b"written degraded")

            # auto-out fires -> remap -> recovery to the survivors
            t0 = asyncio.get_running_loop().time()
            while c.client.osdmap.is_in(victim):
                assert asyncio.get_running_loop().time() - t0 < 30, \
                    "mon never marked osd.%d out" % victim
                await asyncio.sleep(0.05)
            await c.wait_health(pid, timeout=30)

            # all objects fully re-replicated on both survivors
            from ceph_tpu.osd.osdmap import pg_t as PgT
            from ceph_tpu.store.objectstore import coll_t, hobject_t

            m = c.client.osdmap
            for oid, data in list(payloads.items()) + [
                    ("post-kill", b"written degraded")]:
                assert await io.read(oid) == data
                pgid = m.pools[pid].raw_pg_to_pg(
                    m.object_locator_to_pg(oid, pid))
                up, upp, acting, actingp = m.pg_to_up_acting_osds(pgid)
                assert victim not in acting
                for osd_id in acting:
                    store = c.osds[osd_id].store
                    got = store.read(coll_t.pg(pid, pgid.ps),
                                     hobject_t(oid))
                    assert got == data, \
                        "osd.%d stale for %s" % (osd_id, oid)
        finally:
            await c.stop()

    run(main(), timeout=120)


def test_ec_pool_put_get():
    """EC pool (k=2,m=1): objects round trip and each acting osd holds
    exactly its shard, not the whole object."""

    async def main():
        c = await Cluster(3).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="ecpool", pg_num=8,
                pool_type="erasure")
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.allow_ec_overwrites("ecpool")
            await c.wait_health(pid)
            io = c.client.io_ctx("ecpool")
            payloads = {}
            for i in range(10):
                oid = "e-%d" % i
                data = bytes([i]) * (200 + i * 61)
                payloads[oid] = data
                await io.write_full(oid, data)
            for oid, data in payloads.items():
                assert await io.read(oid) == data
                assert await io.stat(oid) == len(data)
            # offset read + RMW partial write
            assert await io.read("e-3", length=10, offset=5) == \
                payloads["e-3"][5:15]
            await io.write("e-3", b"PATCH", offset=3)
            want = bytearray(payloads["e-3"])
            want[3:8] = b"PATCH"
            assert await io.read("e-3") == bytes(want)
            # shards: each acting osd stores 1/k-ish of the payload
            from ceph_tpu.store.objectstore import coll_t, hobject_t

            m = c.client.osdmap
            pool = m.pools[pid]
            pgid = pool.raw_pg_to_pg(
                m.object_locator_to_pg("e-0", pid))
            up, upp, acting, actingp = m.pg_to_up_acting_osds(pgid)
            assert len(acting) == 3
            for osd_id in acting:
                shard = c.osds[osd_id].store.read(
                    coll_t.pg(pid, pgid.ps), hobject_t("e-0"))
                assert 0 < len(shard) < len(payloads["e-0"])
            # delete
            await io.remove("e-9")
            with pytest.raises(ObjectNotFound):
                await io.read("e-9")
        finally:
            await c.stop()

    run(main())


def test_ec_pool_degraded_and_recovery():
    """Kill a shard holder: reads reconstruct from survivors; after
    remap the shard is rebuilt on the replacement layout."""

    async def main():
        c = await Cluster(3).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="ecpool", pg_num=8,
                pool_type="erasure")
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("ecpool")
            payloads = {}
            for i in range(8):
                oid = "d-%d" % i
                data = ("ec-data-%d|" % i).encode() * 40
                payloads[oid] = data
                await io.write_full(oid, data)

            victim = 2
            await c.kill_osd(victim)
            t0 = asyncio.get_running_loop().time()
            while c.client.osdmap.is_up(victim):
                assert asyncio.get_running_loop().time() - t0 < 30
                await asyncio.sleep(0.05)

            # degraded reads reconstruct missing shards
            for oid, data in payloads.items():
                assert await io.read(oid) == data

            # after auto-out the pg has a hole (only 2 osds for k+m=3):
            # IO must still work at k survivors
            t0 = asyncio.get_running_loop().time()
            while c.client.osdmap.is_in(victim):
                assert asyncio.get_running_loop().time() - t0 < 30
                await asyncio.sleep(0.05)
            for oid, data in payloads.items():
                assert await io.read(oid) == data
            await io.write_full("post-kill", b"degraded ec write")
            assert await io.read("post-kill") == b"degraded ec write"
        finally:
            await c.stop()

    run(main(), timeout=120)


def test_thrash_kill_revive_converges():
    """Thrasher (qa/tasks/ceph_manager.py kill_osd/revive_osd analog):
    alternately kill and revive osds under live IO; the cluster must
    converge clean with every object intact."""

    async def main():
        c = await Cluster(3).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="data", pg_num=8, size=3)
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("data")
            payloads = {}
            seq = 0

            async def write_some(n):
                nonlocal seq
                for _ in range(n):
                    oid = "t-%d" % seq
                    data = ("thrash-%d|" % seq).encode() * 20
                    payloads[oid] = data
                    await io.write_full(oid, data)
                    seq += 1

            await write_some(6)
            loop = asyncio.get_running_loop()
            for round_no in range(2):
                victim = round_no % 3
                store = c.osds[victim].store
                await c.kill_osd(victim)
                t0 = loop.time()
                while c.client.osdmap.is_up(victim):
                    assert loop.time() - t0 < 30
                    await asyncio.sleep(0.05)
                await write_some(4)  # degraded writes
                # revive on the same disk (fresh messenger nonce)
                osd = OSD(victim, c.mon.addr,
                          Context("osd.%d" % victim,
                                  conf_overrides=FAST_CONF),
                          store=store)
                await osd.start()
                await osd.wait_for_boot()
                c.osds[victim] = osd
                await c.wait_health(pid, timeout=30)
                for oid, data in payloads.items():
                    assert await io.read(oid) == data, \
                        "round %d lost %s" % (round_no, oid)
        finally:
            await c.stop()

    run(main(), timeout=180)


def test_osd_restart_rejoins_and_backfills():
    """A rebooted osd (fresh messenger nonce, same store) rejoins and
    reconverges."""

    async def main():
        c = await Cluster(3).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="data", pg_num=8, size=2)
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("data")
            for i in range(8):
                await io.write_full("r-%d" % i, b"x" * (50 + i))

            victim = 1
            store = c.osds[victim].store  # keep the "disk"
            await c.kill_osd(victim)
            t0 = asyncio.get_running_loop().time()
            while c.client.osdmap.is_up(victim):
                assert asyncio.get_running_loop().time() - t0 < 30
                await asyncio.sleep(0.05)

            # write while it is down (its copy goes stale)
            await io.write_full("while-down", b"fresh data")

            # restart on the same store
            osd = OSD(victim, c.mon.addr,
                      Context("osd.%d" % victim,
                              conf_overrides=FAST_CONF), store=store)
            await osd.start()
            await osd.wait_for_boot()
            c.osds[victim] = osd
            await c.wait_health(pid, timeout=30)
            for i in range(8):
                assert await io.read("r-%d" % i) == b"x" * (50 + i)
            assert await io.read("while-down") == b"fresh data"
        finally:
            await c.stop()

    run(main(), timeout=120)


def test_ec_pool_with_device_offload(monkeypatch):
    """The same EC cluster flow with the device codec batcher active
    (CEPH_TPU_EC_OFFLOAD=1): writes, degraded reads and recovery all
    route their GF matmuls through ceph_tpu.ec.batcher, and stored
    bytes stay bit-identical to the host path."""
    monkeypatch.setenv("CEPH_TPU_EC_OFFLOAD", "1")

    async def main():
        from ceph_tpu.ec.batcher import DeviceBatcher

        c = await Cluster(4).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="ecdev", pg_num=8,
                pool_type="erasure")
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("ecdev")
            batcher = DeviceBatcher.get()
            payloads = {}
            await asyncio.gather(*[
                io.write_full("d-%d" % i, bytes([i]) * (300 + 37 * i))
                for i in range(12)])
            for i in range(12):
                payloads["d-%d" % i] = bytes([i]) * (300 + 37 * i)
            assert batcher.items_encoded >= 12
            for oid, data in payloads.items():
                assert await io.read(oid) == data
            # degraded read: kill one shard holder
            m = c.client.osdmap
            pool = m.pools[pid]
            pgid = pool.raw_pg_to_pg(
                m.object_locator_to_pg("d-0", pid))
            up, _, acting, _ = m.pg_to_up_acting_osds(pgid)
            await c.kill_osd(acting[0])
            assert await io.read("d-0") == payloads["d-0"]
        finally:
            await c.stop()

    run(main())
