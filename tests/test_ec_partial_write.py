"""Stripe/chunk-aware partial EC writes: parity-delta RMW.

Mirrors ECBackend::start_rmw + ECUtil stripe math + ExtentCache
(ECBackend.cc:1898, ECUtil.h:25-66): an in-place overwrite must move
bytes proportional to the touched extent, not the object size, while
staying bit-correct (reads, crc metadata, deep scrub, snapshots).
"""

import asyncio

from test_cluster import Cluster, run


async def _ec_pool(c, name="ecp"):
    out = await c.client.mon_command(
        "osd pool create", pool=name, pg_num=8, pool_type="erasure")
    pid = out["pool_id"]
    await c.client.wait_for_epoch(c.mon.osdmap.epoch)
    await c.allow_ec_overwrites(name)
    await c.wait_health(pid)
    return pid


def _read_bytes(c):
    return sum(o.ec.sub_read_bytes for o in c.osds if not o.stopping)


def test_partial_write_traffic_proportional_to_extent():
    async def main():
        c = await Cluster(3).start()
        try:
            await _ec_pool(c)
            io = c.client.io_ctx("ecp")
            size = 200 * 1024
            base = bytes(range(256)) * (size // 256)
            await io.write_full("obj", base)
            before = _read_bytes(c)
            patch = b"\xAB" * 2048
            await io.write("obj", patch, 1000)   # 2 KiB of 200 KiB
            moved = _read_bytes(c) - before
            # delta RMW reads the touched column range from the data
            # chunk + every parity chunk — nowhere near the object
            assert moved < 16 * 1024, \
                "partial write read %d bytes of a %d-byte object" \
                % (moved, size)
            want = bytearray(base)
            want[1000:1000 + len(patch)] = patch
            assert await io.read("obj") == bytes(want)

            # chunk-boundary-crossing write (k=2: boundary at size/2)
            before = _read_bytes(c)
            cross = b"\xCD" * 4096
            off = size // 2 - 2048
            await io.write("obj", cross, off)
            moved = _read_bytes(c) - before
            assert moved < 32 * 1024
            want[off:off + len(cross)] = cross
            assert await io.read("obj") == bytes(want)

            # the incrementally-updated crc metadata matches a real
            # recompute: deep scrub must find nothing to flag
            from ceph_tpu.osd.osdmap import pg_t
            errors = 0
            for ps in range(8):
                pgid = pg_t(io.pool_id, ps)
                _, _, acting, actingp = \
                    c.mon.osdmap.pg_to_up_acting_osds(pgid)
                if actingp < 0:
                    continue
                osd = c.osds[actingp]
                pg = osd.pgs.get(pgid)
                if pg is None:
                    continue
                res = await osd.scrubber.scrub_pg(pg, deep=True)
                errors += res["errors"]
            assert errors == 0, "deep scrub flagged %d errors" % errors

            # snapshots compose with the delta path: clone-on-write
            # then partial overwrite; the snap view keeps old bytes
            sid = await io.snap_create("s")
            await io.write("obj", b"\xEE" * 128, 500)
            io.set_read_snap(sid)
            assert (await io.read("obj", 128, 500)) == bytes(
                want[500:628])
            io.set_read_snap(None)
            got = await io.read("obj", 128, 500)
            assert got == b"\xEE" * 128
        finally:
            await c.stop()

    run(main(), timeout=90)


def test_growth_and_big_span_fall_back():
    async def main():
        c = await Cluster(3).start()
        try:
            await _ec_pool(c, "ecp2")
            io = c.client.io_ctx("ecp2")
            await io.write_full("obj", b"a" * 1000)
            # growth: delta path refuses, whole-object RMW handles it
            await io.write("obj", b"b" * 500, 900)
            assert await io.read("obj") == b"a" * 900 + b"b" * 500
            # big span: also whole-object path, still correct
            await io.write("obj", b"c" * 1200, 0)
            assert await io.read("obj") == b"c" * 1200 + b"b" * 200
        finally:
            await c.stop()

    run(main())
