"""Data-reduction plane: content-defined chunking and batched
fingerprints (device/host bit-parity + the chip's
"device_fingerprint_chunks" / "device_fingerprint_bytes" gauges), the
refcounted chunk store (cls_refcount cluster semantics including
journaled resends), dedup end to end through a base/chunk pool pair,
deep scrub of content-addressed chunk objects, the thrasher's dedup
arms, and the telemetry fabric (osd perf -> mgr digest ->
"ceph_tpu_dedup_chunks_stored_total" /
"ceph_tpu_dedup_chunks_deduped_total" /
"ceph_tpu_dedup_bytes_saved_total" exporter families).

CEPH_TPU_EC_OFFLOAD=1 exercises the device path on the CPU backend —
the programs are identical on TPU (same recipe as test_ec_batcher)."""

import asyncio
import random
import zlib

import pytest

from ceph_tpu.client.rados import ObjectNotFound, RadosError
from ceph_tpu.dedup import (CHUNK_AVG, CHUNK_MAX, CHUNK_MIN,
                            OBJ_MANIFEST_ATTR, boundary_batch,
                            chunk_host, chunk_oid, fingerprint,
                            fingerprint_batch, parse_chunk_oid,
                            split)
from ceph_tpu.testing import ClusterThrasher, LocalCluster
from ceph_tpu.utils.backoff import wait_for


@pytest.fixture(autouse=True)
def _offload(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_EC_OFFLOAD", "1")


def run(coro, timeout=180):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


# -- chunker ---------------------------------------------------------------


def test_chunk_host_properties():
    """The host reference: deterministic, cuts honor the
    [CHUNK_MIN, CHUNK_MAX] envelope, split() reassembles exactly."""
    rng = random.Random(7)
    for size in (0, 1, CHUNK_MIN - 1, CHUNK_MIN, CHUNK_AVG,
                 5 * CHUNK_AVG + 137):
        data = rng.randbytes(size)
        cuts = chunk_host(data)
        assert cuts == chunk_host(data)
        chunks = split(data, cuts)
        assert b"".join(chunks) == data
        for ch in chunks[:-1]:
            assert CHUNK_MIN <= len(ch) <= CHUNK_MAX
        for ch in chunks:
            assert len(ch) <= CHUNK_MAX


def test_chunking_is_content_defined():
    """Boundaries derive from content, not offsets: a prefix
    insertion leaves the downstream chunk stream shared — the
    property the dedup ratio on shifted duplicates rides on."""
    rng = random.Random(8)
    base = rng.randbytes(10 * CHUNK_AVG)
    shifted = rng.randbytes(CHUNK_MIN // 2 + 13) + base
    a = set(split(base, chunk_host(base)))
    b = set(split(shifted, chunk_host(shifted)))
    assert len(a & b) >= len(a) // 2, (len(a), len(a & b))


def test_chunk_oid_roundtrip():
    fp = fingerprint(0xDEADBEEF, 12345)
    assert parse_chunk_oid(chunk_oid(fp)) == (0xDEADBEEF, 12345)
    assert parse_chunk_oid("rbd_data.1") is None
    assert parse_chunk_oid("chunk.nothex00-10") is None
    assert parse_chunk_oid("chunk.0011223344-10") is None


def test_device_chunk_and_fingerprint_parity():
    """Device boundary candidates and CRC-lane fingerprints are
    bit-identical to the numpy/zlib references, and the chip's
    fingerprint gauges account the dispatched work."""
    from ceph_tpu.device.runtime import DeviceRuntime

    async def main():
        rt = DeviceRuntime.reset()
        chip = rt.chips[0]
        rng = random.Random(11)
        blobs = [rng.randbytes(rng.randrange(1, 4 * CHUNK_AVG))
                 for _ in range(9)]
        blobs.append(b"")                       # degenerate lane
        cuts, path = await boundary_batch(blobs, chip=0)
        assert path == "device"
        assert cuts == [chunk_host(b) for b in blobs]
        chunks = [ch for b, cc in zip(blobs, cuts)
                  for ch in split(b, cc)]
        fps, fpath = await fingerprint_batch(chunks, chip=0)
        assert fpath == "device"
        assert fps == [fingerprint(zlib.crc32(ch), len(ch))
                       for ch in chunks]
        m = chip.metrics()
        assert m["device_fingerprint_chunks"] >= len(chunks)
        assert m["device_fingerprint_bytes"] >= sum(
            len(ch) for ch in chunks)
        assert rt.host_fallbacks == 0

    run(main())


# -- cls_refcount on a cluster ---------------------------------------------


def test_cls_refcount_cluster_lifecycle():
    """get-on-absent creates holding [tag] (size 0), get on a stored
    object reports its committed size, duplicate tags canonicalize so
    one put per logical ref reaches the self-delete, and a
    pre-existing object holds the single wildcard ref."""

    async def main():
        c = await LocalCluster(n_osds=3).start()
        try:
            pid = await c.create_pool("rc", pg_num=8, size=3)
            await c.wait_health(pid)
            io = c.client.io_ctx("rc")
            out = await io.exec("chk", "refcount", "get",
                                {"tag": "a"})
            assert out["size"] == 0     # created by this get
            assert out["created"] is True
            out = await io.exec("chk", "refcount", "read", {})
            assert out["refs"] == ["a"]
            await io.write_full("chk", b"x" * 777)
            out = await io.exec("chk", "refcount", "get",
                                {"tag": "b"})
            assert out["size"] == 777   # already stored
            assert out["created"] is False
            # duplicate tags collapse on every mutation
            await io.exec("chk", "refcount", "set",
                          {"refs": ["a", "a", "b"]})
            out = await io.exec("chk", "refcount", "read", {})
            assert out["refs"] == ["a", "b"]
            out = await io.exec("chk", "refcount", "put",
                                {"tag": "a"})
            assert out["removed"] is False
            with pytest.raises(RadosError):     # no such tag now
                await io.exec("chk", "refcount", "put",
                              {"tag": "a"})
            out = await io.exec("chk", "refcount", "put",
                                {"tag": "b"})
            assert out["removed"] is True       # last put self-deletes
            with pytest.raises(ObjectNotFound):
                await io.stat("chk")
            # wildcard: an object predating any refcount state
            await io.write_full("w", b"data")
            out = await io.exec("w", "refcount", "put",
                                {"tag": "whatever"})
            assert out["removed"] is True
            with pytest.raises(ObjectNotFound):
                await io.stat("w")
        finally:
            await c.stop()

    run(main())


def test_cls_refcount_resend_answered_from_journal():
    """A timeout-triggered resend of a committed (non-idempotent)
    refcount put is answered from the replicated reqid journal, never
    re-executed — the ref drops exactly once."""
    from ceph_tpu.msg.messages import MOSDOp, MOSDOpReply
    from ceph_tpu.osd.osdmap import pg_t

    async def main():
        c = await LocalCluster(n_osds=3).start()
        try:
            pid = await c.create_pool("rcj", pg_num=8, size=3)
            await c.wait_health(pid)
            io = c.client.io_ctx("rcj")
            await io.exec("chk", "refcount", "set",
                          {"refs": ["a", "b"]})
            out = await io.exec("chk", "refcount", "put",
                                {"tag": "a"})
            assert out["removed"] is False
            src, tid = c.client.msgr.entity, c.client._tid
            m = c.client.osdmap
            pgid = m.pools[pid].raw_pg_to_pg(
                m.object_locator_to_pg("chk", pid))
            _u, _up, _acting, prim = m.pg_to_up_acting_osds(pgid)
            osd = next(o for o in c.live_osds if o.whoami == prim)
            pg = osd.pgs[pg_t(pid, pgid.ps)]
            assert pg.lookup_reqid(src, tid) is not None

            class _Conn:
                peer_entity = src
                is_open = True

                def __init__(self):
                    self.sent = []

                def send(self, msg):
                    self.sent.append(msg)

            conn = _Conn()
            resend = MOSDOp(tid=tid, pool=pid, ps=pgid.ps, oid="chk",
                            snapc=None, snapid=None,
                            ops=[{"op": "call", "cls": "refcount",
                                  "method": "put",
                                  "input": {"tag": "a"}}],
                            epoch=m.epoch, flags=0)
            resend.src = src
            osd._handle_op(conn, resend)
            await wait_for(lambda: len(conn.sent) > 0, 10.0,
                           what="dup answered from the journal")
            rep = conn.sent[0]
            assert isinstance(rep, MOSDOpReply)
            assert rep.result == 0
            # answered WITHOUT re-executing: b's ref survived
            out = await io.exec("chk", "refcount", "read", {})
            assert out["refs"] == ["b"]
        finally:
            await c.stop()

    run(main())


# -- dedup end to end ------------------------------------------------------


async def _dedup_pair(c, base: str, chunks: str):
    pid = await c.create_pool(base, pg_num=8, size=3)
    cpid = await c.create_pool(chunks, pg_num=8, size=3)
    await c.client.mon_command("osd pool set", pool=base,
                               var="dedup_chunk_pool", val=chunks)
    await wait_for(
        lambda: getattr(c.client.osdmap.pools.get(pid),
                        "dedup_chunk_pool", -1) == cpid,
        30.0, what="dedup binding visible on the client")
    await wait_for(
        lambda: all(o.osdmap is not None
                    and o.osdmap.pools.get(pid) is not None
                    and getattr(o.osdmap.pools[pid],
                                "dedup_chunk_pool", -1) == cpid
                    for o in c.live_osds),
        30.0, what="dedup binding visible on every OSD")
    await c.wait_health(pid)
    await c.wait_health(cpid)
    return pid, cpid


def _chunk_rows(c, cpid):
    """(ps, oid, bytes) of every content-addressed chunk object the
    chunk pool's primaries hold."""
    rows = []
    for o in c.live_osds:
        for pg in o.pgs.values():
            if pg.pool_id != cpid or not pg.is_primary():
                continue
            for h in o.store.collection_list(pg.cid):
                if parse_chunk_oid(h.name) is not None:
                    rows.append((pg.ps, h.name,
                                 bytes(o.store.read(pg.cid, h))))
    return rows


def test_dedup_end_to_end():
    """A redundant corpus through a dedup pool pair: reads/stats see
    the logical objects, the base store holds manifests, shared
    chunks land once (>= 2x reduction) with bytes matching their
    content address, the op trace carries the plan stage, overwrite
    and delete drain the refs until the chunk store is empty, and the
    counters ride osd perf -> digest -> exporter -> mon status."""
    from ceph_tpu.store.objectstore import hobject_t

    async def main():
        c = await LocalCluster(n_osds=3, with_mgr=True).start()
        try:
            pid, cpid = await _dedup_pair(c, "dp", "dp-chunks")
            io = c.client.io_ctx("dp")
            rng = random.Random(5)
            # identical payloads chunk identically (boundaries are
            # content-defined), so 3 copies of each unique payload
            # must store its chunks once: ~3x reduction
            uniq = [rng.randbytes(3 * CHUNK_AVG +
                                  rng.randrange(CHUNK_MIN))
                    for _ in range(3)]
            blobs = {"o-%d" % i: uniq[i % 3] for i in range(9)}
            for oid, b in sorted(blobs.items()):
                await asyncio.wait_for(io.write_full(oid, b), 30.0)
            for oid, b in sorted(blobs.items()):
                assert await io.read(oid) == b
                assert await io.stat(oid) == len(b)
            # base store: manifests + logical-size attr, not raw data
            m = c.client.osdmap
            for oid, b in sorted(blobs.items()):
                pgid = m.pools[pid].raw_pg_to_pg(
                    m.object_locator_to_pg(oid, pid))
                osd, pg = c.pg_primary(pid, pgid.ps)
                assert osd.store.getattr(pg.cid, hobject_t(oid),
                                         OBJ_MANIFEST_ATTR)
                assert osd.store.stat(pg.cid,
                                      hobject_t(oid)) < len(b)
            # chunk store: content-addressed, shared blocks once
            rows = _chunk_rows(c, cpid)
            assert rows
            for _ps, oid, blob in rows:
                assert parse_chunk_oid(oid) == (
                    zlib.crc32(blob) & 0xFFFFFFFF, len(blob))
            logical = sum(len(b) for b in blobs.values())
            stored = sum(len(blob) for _ps, _o, blob in rows)
            assert stored * 2 <= logical, (stored, logical)
            # the plan stage rides the op trace (exporter histograms)
            trace = next(rec.trace for rec in
                         reversed(c.client.optracker.historic)
                         if "o-0 " in rec.desc
                         and "write" in rec.desc)
            events = {e["event"] for rec in c.op_timeline(trace)
                      for e in rec["events"]}
            assert "dedup_planned" in events, events
            # fleet ledger folded by the digest; exporter families
            await c.wait_stats(
                lambda d: int((((d or {}).get("dedup_pools") or {})
                               .get(str(pid)) or {})
                              .get("chunks_deduped", 0)) > 0,
                60.0, what="dedup counters in the mgr digest")
            text = c.mgr.exporter.render()
            for fam in ("ceph_tpu_dedup_chunks_stored_total",
                        "ceph_tpu_dedup_chunks_deduped_total",
                        "ceph_tpu_dedup_bytes_saved_total"):
                assert '%s{pool_id="%d"}' % (fam, pid) in text, fam
            st = await c.client.mon_command("status")
            assert str(pid) in (st.get("dedup") or {})
            # overwrite: the old manifest's refs drain, reads follow
            nb = rng.randbytes(3 * CHUNK_MIN)
            await io.write_full("o-0", nb)
            assert await io.read("o-0") == nb
            # delete everything: last puts self-delete every chunk
            for oid in sorted(blobs):
                await io.remove(oid)
            await wait_for(lambda: not _chunk_rows(c, cpid), 30.0,
                           what="chunk store drained by last puts")
        finally:
            await c.stop()

    run(main(), timeout=300)


def test_scrub_all_replica_chunk_rot_unrepairable():
    """Unanimous chunk rot: every replica rotted with identical junk
    still scrubs INCONSISTENT (the content address outvotes the
    unanimous digests) and repair reports residual damage rather than
    crowning the rot."""
    from ceph_tpu.osd.osdmap import pg_t
    from ceph_tpu.store.objectstore import Transaction, hobject_t

    async def main():
        c = await LocalCluster(n_osds=3).start()
        try:
            pid, cpid = await _dedup_pair(c, "sp", "sp-chunks")
            io = c.client.io_ctx("sp")
            rng = random.Random(9)
            data = rng.randbytes(5 * CHUNK_MIN)
            await asyncio.wait_for(io.write_full("obj", data), 30.0)
            rows = _chunk_rows(c, cpid)
            assert rows
            ps, oid, blob = sorted(rows)[0]
            alive = {o.whoami: o for o in c.live_osds}
            _u, _up, acting, _p = c.client.osdmap.pg_to_up_acting_osds(
                pg_t(cpid, ps))
            junk = rng.randbytes(len(blob))
            for v in [o for o in acting if o >= 0 and o in alive]:
                osd = alive[v]
                pg = osd.pgs[pg_t(cpid, ps)]
                t = Transaction()
                t.truncate(pg.cid, hobject_t(oid), 0)
                t.write(pg.cid, hobject_t(oid), 0, len(junk), junk)
                osd.store.apply_transaction(t)
            posd, ppg = c.pg_primary(cpid, ps)
            res = await posd.scrubber.scrub_pg(ppg, deep=True,
                                               recheck=True)
            assert oid in set(res["inconsistent"]), res
            res = await posd.scrubber.scrub_pg(ppg, deep=True,
                                               repair=True,
                                               only={oid})
            assert res["residual"] >= 1, res
        finally:
            await c.stop()

    run(main(), timeout=300)


def test_mon_rejects_invalid_dedup_bindings():
    async def main():
        c = await LocalCluster(n_osds=3).start()
        try:
            await c.create_pool("base", pg_num=4, size=3)
            await c.create_pool("ecp", pg_num=4,
                                pool_type="erasure")
            with pytest.raises(RadosError):     # self-dedup
                await c.client.mon_command(
                    "osd pool set", pool="base",
                    var="dedup_chunk_pool", val="base")
            with pytest.raises(RadosError):     # EC chunk pool
                await c.client.mon_command(
                    "osd pool set", pool="base",
                    var="dedup_chunk_pool", val="ecp")
            with pytest.raises(RadosError):     # EC base pool
                await c.client.mon_command(
                    "osd pool set", pool="ecp",
                    var="dedup_chunk_pool", val="base")
        finally:
            await c.stop()

    run(main())


@pytest.mark.slow
def test_thrasher_dedup_rounds():
    """Both thrasher arms end to end with their built-in oracles:
    corrupt_dedup_index (majority chunk rot detected by address,
    repaired from the single healthy copy) and poison_mid_chunk
    (mid-write chip loss lands every write on the host reference and
    the chips heal)."""

    async def main():
        c = await LocalCluster(n_osds=3).start()
        try:
            th = ClusterThrasher(c, seed=3, actions=[])
            await th._corrupt_dedup_index_round(c, 3)
            await th._poison_mid_chunk_round(c, 3)
        finally:
            await c.stop()

    run(main(), timeout=420)


# -- registry ----------------------------------------------------------------


def test_registry_lint_clean_with_dedup_series():
    from ceph_tpu.trace import registry
    assert registry.lint_repo() == []
