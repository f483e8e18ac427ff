"""RadosModel-style randomized stress: a model of expected object
state tracks every applied op; reads are verified against it
continuously while the thrasher kills and revives OSDs
(src/test/osd/RadosModel.h + TestRados.cc + qa/tasks ceph_manager
kill_osd/revive_osd analog)."""

import asyncio

import numpy as np

from ceph_tpu.osd.daemon import OSD
from ceph_tpu.utils.context import Context
from tests.test_cluster import FAST_CONF, Cluster, run


class Model:
    """Expected object state (RadosModel's ObjectDesc registry)."""

    def __init__(self):
        self.objects: dict[str, bytearray] = {}
        self.xattrs: dict[str, dict[str, bytes]] = {}

    def write_full(self, oid, data):
        self.objects[oid] = bytearray(data)
        self.xattrs.setdefault(oid, {})

    def write(self, oid, data, offset):
        cur = self.objects.setdefault(oid, bytearray())
        if len(cur) < offset + len(data):
            cur.extend(b"\0" * (offset + len(data) - len(cur)))
        cur[offset:offset + len(data)] = data
        self.xattrs.setdefault(oid, {})

    def remove(self, oid):
        self.objects.pop(oid, None)
        self.xattrs.pop(oid, None)

    def setxattr(self, oid, name, val):
        if oid in self.objects:
            self.xattrs.setdefault(oid, {})[name] = val


async def _apply_random_op(rng, io, model, seq):
    """One random op applied to cluster AND model (op table mirrors
    TestOpType in TestRados.cc: write/read/delete/attrs)."""
    kind = rng.choice(["write_full", "write", "read", "remove",
                       "setxattr", "stat"],
                      p=[0.3, 0.2, 0.25, 0.1, 0.1, 0.05])
    oids = sorted(model.objects)
    if kind in ("read", "remove", "setxattr", "stat") and not oids:
        kind = "write_full"
    if kind == "write_full":
        oid = "m-%d" % int(rng.integers(0, 40))
        data = bytes([int(rng.integers(1, 256))]) * int(
            rng.integers(1, 4000))
        await io.write_full(oid, data)
        model.write_full(oid, data)
    elif kind == "write":
        oid = (rng.choice(oids) if oids and rng.random() < 0.7
               else "m-%d" % int(rng.integers(0, 40)))
        off = int(rng.integers(0, 2000))
        data = bytes([int(rng.integers(1, 256))]) * int(
            rng.integers(1, 500))
        await io.write(oid, data, offset=off)
        model.write(oid, data, off)
    elif kind == "read":
        oid = rng.choice(oids)
        got = await io.read(oid)
        want = bytes(model.objects[oid])
        assert got == want, "op %d: %s diverged (%d vs %d bytes)" % (
            seq, oid, len(got), len(want))
    elif kind == "stat":
        oid = rng.choice(oids)
        assert await io.stat(oid) == len(model.objects[oid])
    elif kind == "remove":
        oid = rng.choice(oids)
        await io.remove(oid)
        model.remove(oid)
    elif kind == "setxattr":
        oid = rng.choice(oids)
        name = "x%d" % int(rng.integers(0, 4))
        val = b"v%d" % seq
        await io.setxattr(oid, name, val)
        model.setxattr(oid, name, val)


async def _verify_all(io, model):
    for oid, data in sorted(model.objects.items()):
        got = await io.read(oid)
        assert got == bytes(data), "%s lost/diverged" % oid


def test_radosmodel_stress_under_thrashing():
    """500+ randomized ops with 3 kill/revive cycles interleaved; the
    model must match the cluster exactly at every read and at the
    final full verification."""

    async def main():
        rng = np.random.default_rng(1234)
        c = await Cluster(4).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="model", pg_num=8, size=3)
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("model")
            model = Model()
            loop = asyncio.get_running_loop()
            seq = 0
            for cycle in range(3):
                for _ in range(90):
                    await _apply_random_op(rng, io, model, seq)
                    seq += 1
                victim = int(rng.integers(0, 4))
                store = c.osds[victim].store
                await c.kill_osd(victim)
                t0 = loop.time()
                while c.client.osdmap.is_up(victim):
                    assert loop.time() - t0 < 30
                    await asyncio.sleep(0.05)
                for _ in range(40):        # degraded ops
                    await _apply_random_op(rng, io, model, seq)
                    seq += 1
                osd = OSD(victim, c.mon.addr,
                          Context("osd.%d" % victim,
                                  conf_overrides=FAST_CONF),
                          store=store)
                await osd.start()
                await osd.wait_for_boot()
                c.osds[victim] = osd
                await c.wait_health(pid, timeout=40)
                for _ in range(40):        # post-recovery ops
                    await _apply_random_op(rng, io, model, seq)
                    seq += 1
            assert seq >= 500
            await c.wait_health(pid, timeout=40)
            await _verify_all(io, model)
            # scrub confirms replica-level consistency too
            from ceph_tpu.osd.osdmap import pg_t

            m = c.client.osdmap
            pool = m.pools[pid]
            total_errors = 0
            for ps in range(pool.pg_num):
                _up, _upp, acting, actingp = m.pg_to_up_acting_osds(
                    pg_t(pid, ps))
                prim = c.osds[actingp]
                pg = prim.pgs.get(pg_t(pid, ps))
                if pg is not None:
                    res = await prim.scrubber.scrub_pg(pg)
                    total_errors += res["errors"]
            assert total_errors == 0
        finally:
            await c.stop()

    run(main(), timeout=300)


def test_radosmodel_stress_ec_pool():
    """The same model over an EC pool (writes route through the device
    batcher when offload is on in other suites; here the host path)."""

    async def main():
        rng = np.random.default_rng(77)
        c = await Cluster(4).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="emodel", pg_num=8,
                pool_type="erasure")
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mon.osdmap.epoch)
            await c.allow_ec_overwrites("emodel")
            await c.wait_health(pid)
            io = c.client.io_ctx("emodel")
            model = Model()
            for seq in range(150):
                await _apply_random_op(rng, io, model, seq)
            await _verify_all(io, model)
        finally:
            await c.stop()

    run(main(), timeout=180)
