"""An RBD image on an erasure-coded data pool with overwrites
(doc/rados/operations/erasure-code.rst, "Erasure Coding with Overwrites":
`ceph osd pool set <pool> allow_ec_overwrites true`, `rbd create
--data-pool`).

The gate (a partial write to an EC pool without the flag answers
-EOPNOTSUPP; the flag is never cleared), the image (header and directory in
the replicated pool, `rbd_data.*` only in the data pool) and the
parity-delta path under a few hundred seeded 4 KiB-and-odd overwrites on
isa k=4,m=2, whose second parity row has coefficients other than 1: every
parity shard is held to the plain Reed-Solomon encoder of
benchmark/reference/rs_isa.py, the image to benchmark/reference/
rbd_image.py, also with each single OSD down.

Nobody's failure detector is shortened here: the clusters keep the shipped
heartbeat grace, and at most one OSD is ever down.
"""

import asyncio
import zlib

import pytest

from benchmark.reference import rs_isa
from benchmark.reference.rbd_image import Image as RefImage
from ceph_tpu.client.rados import RadosError
from ceph_tpu.client.striper import FileLayout
from ceph_tpu.ec import matrices
from ceph_tpu.osd.ecbackend import (HINFO_XATTR, RMW_FALLBACK_WHY,
                                    SHARD_XATTR)
from ceph_tpu.osd.osdmap import FLAG_EC_OVERWRITES
from ceph_tpu.services.rbd import DATA_PREFIX, RBD, RBDError
from ceph_tpu.store.objectstore import hobject_t
from ceph_tpu.testing import LocalCluster
from test_spans import warm_write

SHIPPED_GRACE = {"heartbeat_grace": 6.0}
K, M = 4, 2
OBJECT = 1 << 16            # 64 KiB objects: chunks of 16 KiB
IO = 4096
IMAGE = 8 * OBJECT
LAYOUT = FileLayout(stripe_unit=OBJECT, stripe_count=1, object_size=OBJECT)


def run(coro, timeout=240):
    return asyncio.run(asyncio.wait_for(coro, timeout))


async def boot(n_osds: int, flag: bool):
    """A cluster with the replicated pool `rbd` and the isa k4m2 pool
    `ec_pool`, both clean; the EC pool allows overwrites if `flag`."""
    c = await LocalCluster(n_osds=n_osds, conf=SHIPPED_GRACE).start()
    await c.client.mon_command(
        "osd erasure-code-profile set", name="k4m2",
        profile={"plugin": "isa", "technique": "reed_sol_van",
                 "k": str(K), "m": str(M), "crush-failure-domain": "osd"})
    await c.client.wait_for_epoch(c.leader().osdmap.epoch)
    ec = await c.create_pool("ec_pool", pg_num=4, pool_type="erasure",
                             erasure_code_profile="k4m2")
    rep = await c.create_pool("rbd", pg_num=4)
    if flag:
        await c.allow_ec_overwrites("ec_pool")
    await c.wait_health(ec)
    await c.wait_health(rep)
    await warm_write(c, c.client.io_ctx("ec_pool"), ec)
    return c, ec, rep


def names_in(c, pid: int) -> set:
    """Every head object the OSDs' stores hold in pool `pid`."""
    out = set()
    for o in c.live_osds:
        for pgid, pg in o.pgs.items():
            if pgid.pool == pid:
                out |= {h.name for h in o.store.collection_list(pg.cid)
                        if h.name != "__pgmeta__"}
    return out


def shards_of(c, pid: int, oid: str) -> dict:
    """position -> (bytes, attrs) of `oid` as the OSDs' stores hold it."""
    out = {}
    for o in c.live_osds:
        for pgid, pg in o.pgs.items():
            ho = hobject_t(oid)
            if pgid.pool == pid and o.store.exists(pg.cid, ho):
                attrs = o.store.getattrs(pg.cid, ho)
                out[int(attrs[SHARD_XATTR])] = (
                    bytes(o.store.read(pg.cid, ho)), attrs)
    return out


def counted(c, name: str) -> int:
    return sum(getattr(o.ec, name) for o in c.osds)


# -- the gate and the flag --------------------------------------------------


def test_without_the_flag_an_ec_pool_refuses_partial_writes():
    async def main():
        c, ec, _rep = await boot(6, flag=False)
        try:
            assert not c.client.osdmap.pools[ec].allows_ecoverwrites()
            io = c.client.io_ctx("ec_pool")
            await io.write_full("o", b"a" * 8192)
            await io.write("o", b"b" * 100, 8192)        # an append
            for op in (io.write("o", b"c" * 10, 5),
                       io.write("o", b"c" * 10, 9000),   # past the end
                       io.truncate("o", 100)):
                with pytest.raises(RadosError) as e:
                    await op
                assert e.value.code == -95
            assert await io.read("o") == b"a" * 8192 + b"b" * 100
            # what never overwrites is served as before
            await io.write("new", b"n" * 10)             # from nothing
            await io.write_full("o", b"d" * 4096)
            await io.remove("new")
            assert await io.read("o") == b"d" * 4096
            rbd = RBD(c.client.io_ctx("rbd"))
            with pytest.raises(RBDError, match="allow_ec_overwrites"):
                await rbd.create("img", IMAGE, LAYOUT, data_pool="ec_pool")
            with pytest.raises(RBDError, match="no data pool"):
                await rbd.create("img", IMAGE, LAYOUT, data_pool="nope")
            assert await rbd.list() == []
        finally:
            await c.stop()
    run(main())


def test_the_flag_commits_reaches_every_osd_and_stays():
    async def main():
        c, ec, _rep = await boot(6, flag=False)
        try:
            cmd = c.client.mon_command
            with pytest.raises(RadosError) as e:
                await cmd("osd pool set", pool="rbd",
                          var="allow_ec_overwrites", val="true")
            assert e.value.code == -22          # replicated: refused
            await cmd("osd pool set", pool="ec_pool",
                      var="allow_ec_overwrites", val="false")   # a no-op
            await c.allow_ec_overwrites("ec_pool")
            from ceph_tpu.utils.backoff import wait_for
            await wait_for(lambda: all(
                o.osdmap.pools[ec].flags & FLAG_EC_OVERWRITES
                for o in c.osds), 20, what="the flag on every OSD's map")
            with pytest.raises(RadosError) as e:
                await cmd("osd pool set", pool="ec_pool",
                          var="allow_ec_overwrites", val="false")
            assert e.value.code == -22          # never cleared
            assert c.leader().osdmap.pools[ec].allows_ecoverwrites()
            io = c.client.io_ctx("ec_pool")
            await io.write_full("o", b"a" * 8192)
            await io.write("o", b"c" * 10, 5)
            await io.truncate("o", 4096)
            assert await io.read("o") == b"a" * 5 + b"c" * 10 \
                + b"a" * 4081
        finally:
            await c.stop()
    run(main())


# -- the image --------------------------------------------------------------


@pytest.fixture(scope="module")
def overwritten():
    """One run of seeded overwrites of a prefilled image on isa k4m2,
    and what was seen: filled once for all the cases below."""
    seen = {}

    async def main():
        c, ec, rep = await boot(K + M + 1, flag=True)
        try:
            rbd = RBD(c.client.io_ctx("rbd"))
            await rbd.create("disk", IMAGE, LAYOUT, data_pool="ec_pool")
            img = await rbd.open("disk")
            seen["data_pool"] = img.data_io.pool_id == ec \
                and img.io.pool_id == rep
            ref = RefImage(11, IMAGE, OBJECT, IO, 4)
            for n in range(ref.objects):
                await img.write(n * OBJECT, ref.object(n))
            seen["rep_names"] = names_in(c, rep)
            seen["ec_names"] = names_in(c, ec)
            seen["prefill"] = (counted(c, "delta_writes"),
                               counted(c, "rmw_fallbacks"))
            # 300 aligned 4 KiB blocks from the reference's draw, four
            # writers in flight, judged by the reference ...
            todo = iter(range(300))

            async def writer():
                for i in todo:
                    ref.submitted(i)
                    await img.write(ref.block(i) * IO, ref.payload(i))
                    ref.acknowledged(i)

            await asyncio.gather(*[writer() for _ in range(4)])
            got = await img.read(0, IMAGE)
            seen["image_bad_blocks"] = sum(
                ref.mismatched_blocks(n, got[n * OBJECT:(n + 1) * OBJECT])
                for n in range(ref.objects))
            seen["blocks_overwritten"] = len(ref.overwritten())
            # ... then the odd ones, one at a time over what is there:
            # across a chunk boundary, a single byte, the object's last
            # bytes, across two objects (two ops)
            cs = OBJECT // K
            odd = [(2 * OBJECT + cs - 100, b"\xc3" * 300),
                   (3 * OBJECT + 12345, b"\x5a"),
                   (5 * OBJECT - 7, b"\xee" * 7),
                   (6 * OBJECT - 2000, b"\x99" * 4000)]
            flat = bytearray(got)
            for off, data in odd:
                await img.write(off, data)
                flat[off:off + len(data)] = data
            got = await img.read(0, IMAGE)
            seen["odd_ok"] = got == bytes(flat)
            seen["sent"] = 300 + len(odd) + 1
            seen["after"] = (counted(c, "delta_writes"),
                             counted(c, "delta_write_bytes"),
                             counted(c, "rmw_fallbacks"))
            seen["bytes_sent"] = 300 * IO + sum(len(d) for _o, d in odd)
            # what the image holds now: the reference's verdict above,
            # so `got` is the expectation for the degraded reads
            seen["shards"] = {n: shards_of(c, ec, img._data_name(n))
                              for n in range(ref.objects)}
            seen["degraded"] = {}
            for victim in range(K + M + 1):
                await c.kill_osd(victim)
                await c.wait_osd_down(victim, timeout=60)
                seen["degraded"][victim] = \
                    await img.read(0, IMAGE) == got
                await c.revive_osd(victim)
                await c.wait_osd_up(victim)
                await c.wait_health(ec)
                await c.wait_health(rep)
            # a write that grows an object takes the whole-object path
            before = counted(c, "rmw_fallbacks")
            io = c.client.io_ctx("ec_pool")
            await io.write_full("grows", b"g" * 8192)
            await io.write("grows", b"h" * 8192, 4096)
            seen["growth"] = (counted(c, "rmw_fallbacks") - before,
                              await io.read("grows")
                              == b"g" * 4096 + b"h" * 8192)
        finally:
            await c.stop()

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CEPH_TPU_EC_OFFLOAD", "1")
        run(main(), 600)
    return seen


def test_header_and_directory_stay_in_the_replicated_pool(overwritten):
    assert overwritten["data_pool"]
    assert overwritten["rep_names"] == {"rbd_header.disk", "rbd_directory"}
    data = overwritten["ec_names"] - {"warm"}    # boot()'s own object
    assert len(data) == IMAGE // OBJECT
    assert all(n.startswith(DATA_PREFIX + "disk.") for n in data)


def test_every_overwrite_took_the_delta_path(overwritten):
    # the prefill's first writes create their objects: whole-object path
    assert overwritten["prefill"] == (0, IMAGE // OBJECT)
    writes, nbytes, fallbacks = overwritten["after"]
    assert writes == overwritten["sent"]
    assert nbytes == overwritten["bytes_sent"]
    assert fallbacks == IMAGE // OBJECT         # none since the prefill


def test_the_image_equals_the_reference(overwritten):
    assert overwritten["image_bad_blocks"] == 0
    assert overwritten["blocks_overwritten"] > 100
    assert overwritten["odd_ok"]


@pytest.mark.parametrize("parity", range(M))
def test_parity_shards_equal_the_reference_encode(overwritten, parity):
    for n, shards in overwritten["shards"].items():
        assert sorted(shards) == list(range(K + M))
        want = rs_isa.encode([shards[j][0] for j in range(K)], M)
        assert shards[K + parity][0] == want[parity], \
            "object %d, parity %d" % (n, parity)


def test_stored_crcs_are_the_crcs_of_the_bytes(overwritten):
    for n, shards in overwritten["shards"].items():
        crcs = [zlib.crc32(shards[j][0]) & 0xFFFFFFFF
                for j in range(K + M)]
        for j in range(K + M):
            stored = [int(x) for x in
                      shards[j][1][HINFO_XATTR].split(b",")]
            assert stored == crcs, "object %d, shard %d" % (n, j)


@pytest.mark.parametrize("victim", range(K + M + 1))
def test_a_read_with_one_osd_down_equals_it_too(overwritten, victim):
    assert overwritten["degraded"][victim]


def test_a_write_that_grows_an_object_falls_back_once(overwritten):
    assert overwritten["growth"] == (1, True)
    assert RMW_FALLBACK_WHY[0] == "growth"


def test_the_second_parity_row_is_not_all_ones():
    rows = rs_isa.coding_rows(K, M)
    assert rows[0] == [1] * K and set(rows[1]) != {1}
    assert rows == [list(r) for r in
                    matrices.isa_rs_vandermonde_matrix(K, M)]


# -- Image.read: only ENOENT means "never written" --------------------------


def test_image_read_raises_what_is_not_enoent(monkeypatch):
    async def main():
        c, _ec, _rep = await boot(6, flag=True)
        try:
            rbd = RBD(c.client.io_ctx("rbd"))
            await rbd.create("disk", IMAGE, LAYOUT, data_pool="ec_pool")
            img = await rbd.open("disk")
            await img.write(0, b"w" * OBJECT)
            # never written: sparse zeros
            assert await img.read(OBJECT, 100) == b"\0" * 100
            real = type(img.data_io).read

            async def failing(self, oid, *a, **kw):
                if oid == img._data_name(0):
                    raise RadosError(-110, "timed out")
                return await real(self, oid, *a, **kw)

            monkeypatch.setattr(type(img.data_io), "read", failing)
            with pytest.raises(RadosError) as e:
                await img.read(0, 100)
            assert e.value.code == -110
            assert await img.read(OBJECT, 100) == b"\0" * 100
        finally:
            await c.stop()
    run(main())
