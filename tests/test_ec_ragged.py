"""Ragged EC batching + device-batched parity-delta writes.

Tentpole coverage for ISSUE 8: the batcher's bucket-ladder (ragged)
staging must be bit-identical to the host codecs across adversarial
size mixes (1-word items beside bucket-ceiling items, w=8/16/32) while
killing bucket-ceiling padding within the <=8-program compile budget;
and the codec's `delta_async` parity-delta path must batch concurrent
partial overwrites into shared device dispatches (asserted via
tickets), fall back to the host numpy path under poison, ride the
cluster's `_try_delta_write` with ticket attribution and RMW
amplification preserved, journal delta commits in the REPLICATED shard
txns (promoted replicas answer resends), and survive the `mixed_rmw`
thrash oracle bit-identical to the host codec.

CEPH_TPU_EC_OFFLOAD=1 exercises the device path on the CPU backend —
the programs are identical on TPU (same recipe as test_ec_batcher)."""

import asyncio

import numpy as np
import pytest

from ceph_tpu.device.runtime import DeviceRuntime
from ceph_tpu.ec.batcher import DeviceBatcher
from ceph_tpu.ec.plugin import ErasureCodePluginRegistry


@pytest.fixture(autouse=True)
def _offload(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_EC_OFFLOAD", "1")


def _codec(plugin, **profile):
    prof = {k: str(v) for k, v in profile.items()}
    return ErasureCodePluginRegistry.instance().factory(plugin, prof)


def run(coro, timeout=300):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


# -- the bucket ladder (ragged plan) ---------------------------------------


def test_ragged_plan_properties():
    """Every plan: pow2 segments >= 512 words, contiguous coverage of
    >= n, tail-only rounding, never worse than the single pow2
    bucket, and degenerate to one segment when the ladder cannot
    beat it."""
    for n in (1, 17, 512, 513, 820, 2048, 6144, 37_123, 100_001,
              (1 << 20) + 7):
        plan = DeviceRuntime.ragged_plan(n)
        lo = 0
        for off, seg in plan:
            assert off == lo
            assert seg >= 512 and seg & (seg - 1) == 0, plan
            lo += seg
        padded = lo
        assert padded >= n
        assert padded <= DeviceRuntime.bucket_for(n), (n, plan)
        # non-tail segments never pad (greedy largest-pow2 <= rest)
        assert sum(seg for _o, seg in plan[:-1]) <= n
    # exact pow2 totals are one exact segment
    assert DeviceRuntime.ragged_plan(4096) == [(0, 4096)]
    # the canonical win: 37123 words pad 253, not 28413
    plan = DeviceRuntime.ragged_plan(37_123)
    assert sum(s for _o, s in plan) - 37_123 < 512
    assert len(plan) <= 6


# -- ragged flush bit-parity across adversarial mixes ----------------------


@pytest.mark.parametrize("plugin,profile", [
    ("isa", dict(technique="reed_sol_van", k=8, m=3)),
    ("isa", dict(technique="cauchy", k=5, m=2)),
    ("jerasure", dict(technique="reed_sol_van", k=3, m=2, w=16)),
    ("jerasure", dict(technique="reed_sol_van", k=4, m=2, w=32)),
])
def test_ragged_flush_bit_parity_adversarial_mix(plugin, profile):
    """One heterogeneous flush: 1-word-class items right beside
    bucket-ceiling items, encoded concurrently so they pack into one
    ragged ladder — every item bit-identical to the host codec."""
    codec = _codec(plugin, **profile)
    n = codec.get_chunk_count()
    rng = np.random.default_rng(41)
    # sizes chosen adversarially: tiny (sub-word chunks), just over a
    # bucket edge, just under one, and a big non-bucket blob
    sizes = (3, 17, 512, 4097, 65_537, 262_143, 40_000, 1)
    datas = [rng.integers(0, 256, s, dtype=np.uint8).tobytes()
             for s in sizes]
    hosts = [codec.encode(set(range(n)), d) for d in datas]

    async def main():
        rt = DeviceRuntime.reset()
        outs = await asyncio.gather(*[
            codec.encode_async(set(range(n)), d) for d in datas])
        for s, h, o in zip(sizes, hosts, outs):
            for i in h:
                assert o[i] == h[i], (plugin, profile, s, i)
        return rt

    rt = run(main())
    assert rt.dispatches >= 1
    # the ragged ladder staged less padding than whole-flush pow2
    assert rt.bucket_waste_ratio <= rt.pow2_waste_ratio


def test_ragged_waste_telemetry_and_exporter():
    """The padding-waste satellite: a mixed concurrent flush records
    a waste ratio far below the pow2 counterfactual, and the exporter
    renders `device_bucket_waste_ratio` per chip, TYPE-once
    lint-clean."""
    codec = _codec("isa", technique="reed_sol_van", k=4, m=2)
    n = codec.get_chunk_count()
    rng = np.random.default_rng(43)
    datas = [rng.integers(0, 256, s, dtype=np.uint8).tobytes()
             for s in (600_000, 50_000, 3000, 77)]

    async def main():
        rt = DeviceRuntime.reset(chips=2)
        await asyncio.gather(*[
            codec.encode_async(set(range(n)), d) for d in datas])
        from ceph_tpu.utils.exporter import (device_runtime_lines,
                                             validate_exposition)
        text = "\n".join(device_runtime_lines())
        assert validate_exposition(text) == []
        for chip in range(2):
            assert ('ceph_tpu_device_bucket_waste_ratio{chip="%d"}'
                    % chip) in text
        assert text.count(
            "# TYPE ceph_tpu_device_bucket_waste_ratio") == 1
        return rt

    rt = run(main())
    assert 0.0 <= rt.bucket_waste_ratio < 0.1
    assert rt.bucket_waste_ratio < 0.5 * rt.pow2_waste_ratio


def test_ragged_compile_budget_mixed_stream():
    """The acceptance budget: a steady mixed-size stream stays within
    8 distinct compiled programs, and repeating it compiles nothing
    new (ladder segments are shared pow2 programs)."""
    codec = _codec("isa", technique="reed_sol_van", k=8, m=3)
    n = codec.get_chunk_count()
    rng = np.random.default_rng(47)
    datas = [rng.integers(0, 256, s, dtype=np.uint8).tobytes()
             for s in (4096, 16384, 5000, 64_000, 4096, 130_000)]

    async def main():
        rt = DeviceRuntime.reset()
        await asyncio.gather(*[
            codec.encode_async(set(range(n)), d) for d in datas])
        first = rt.compile_count
        assert first <= 8, sorted(rt.programs)
        await asyncio.gather(*[
            codec.encode_async(set(range(n)), d) for d in datas])
        assert rt.compile_count == first, "steady state recompiled"
        assert rt.bucket_hits >= 1

    run(main())


# -- delta_async: device-batched parity deltas -----------------------------


@pytest.mark.parametrize("plugin,profile", [
    ("isa", dict(technique="reed_sol_van", k=8, m=3)),
    ("isa", dict(technique="cauchy", k=4, m=2)),
    ("jerasure", dict(technique="reed_sol_van", k=3, m=2, w=16)),
    ("jerasure", dict(technique="reed_sol_van", k=4, m=2, w=32)),
])
def test_delta_async_bit_parity(plugin, profile):
    """Device parity deltas == host numpy deltas == what a full host
    re-encode of the patched object implies (the GF-linearity
    algebra the partial-write path rests on), across w=8/16/32."""
    codec = _codec(plugin, **profile)
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    m = n - k
    rng = np.random.default_rng(53)
    cs = 8192                       # per-chunk bytes (word-aligned)
    data = rng.integers(0, 256, k * cs, dtype=np.uint8).tobytes()
    old = codec.encode(set(range(n)), data)
    a, b = 512, 2560                # patched column range
    patches = {0: rng.integers(0, 256, b - a,
                               dtype=np.uint8).tobytes(),
               k - 1: rng.integers(0, 256, b - a,
                                   dtype=np.uint8).tobytes()}
    deltas = {j: bytes(x ^ y for x, y in zip(old[j][a:b], p))
              for j, p in patches.items()}
    host_pd = codec.parity_delta(deltas)

    dev_pd = run(codec.delta_async(deltas))
    assert dev_pd == host_pd

    # algebraic oracle: old parity ^ delta == encode(new object)
    new_data = bytearray(data)
    for j, p in patches.items():
        new_data[j * cs + a:j * cs + b] = p
    new = codec.encode(set(range(n)), bytes(new_data))
    for i in range(m):
        got = bytes(x ^ y for x, y in zip(old[k + i][a:b],
                                          host_pd[i]))
        assert got == new[k + i][a:b], (plugin, profile, i)
        # untouched parity columns are untouched
        assert old[k + i][:a] == new[k + i][:a]


def test_concurrent_deltas_batch_one_dispatch():
    """N concurrent partial writes -> ONE device dispatch, asserted
    via tickets: every delta (and a full write sharing the stream)
    receives the same flush ticket."""
    codec = _codec("isa", technique="reed_sol_van", k=8, m=3)
    n = codec.get_chunk_count()
    k = codec.get_data_chunk_count()
    rng = np.random.default_rng(59)
    deltas = [{int(i % k): rng.integers(0, 256, 2048,
                                        dtype=np.uint8).tobytes()}
              for i in range(6)]
    full = rng.integers(0, 256, 30_000, dtype=np.uint8).tobytes()
    tickets = []

    async def main():
        rt = DeviceRuntime.reset()
        bat = DeviceBatcher.get()
        before = bat.batches_flushed
        host_pds = [codec.parity_delta(d) for d in deltas]
        outs = await asyncio.gather(
            codec.encode_async(set(range(n)), full,
                               on_ticket=tickets.append),
            *[codec.delta_async(d, on_ticket=tickets.append)
              for d in deltas])
        for pd, want in zip(outs[1:], host_pds):
            assert pd == want
        assert bat.batches_flushed - before == 1
        assert rt.dispatches == 1

    run(main())
    assert len(tickets) == 7
    assert len({t.seq for t in tickets}) == 1   # the SAME flush


def test_48_concurrent_deltas_ride_one_dispatch():
    """48 concurrent 8 KiB partial writes on one matrix (3/4 of a
    stream slot) are one slot, one ticket, one device dispatch: 48
    ops per dispatch, each bit-identical to the host codec."""
    codec = _codec("isa", technique="reed_sol_van", k=8, m=3)
    k = codec.get_data_chunk_count()
    rng = np.random.default_rng(37)
    deltas = [{int(rng.integers(0, k)):
               rng.integers(0, 256, 8192, dtype=np.uint8).tobytes()}
              for _ in range(48)]
    tickets = []

    async def main():
        rt = DeviceRuntime.reset()
        bat = DeviceBatcher.get()
        outs = await asyncio.gather(
            *[codec.delta_async(d, on_ticket=tickets.append)
              for d in deltas])
        for d, out in zip(deltas, outs):
            assert out == codec.parity_delta(d)
        assert rt.dispatches == 1
        assert rt.chips[0].stream.slot_dispatches == 1
        assert (bat.batches_flushed, bat.items_encoded) == (1, 48)
        assert rt.host_fallbacks == 0

    run(main())
    assert len(tickets) == 48
    assert len({t.seq for t in tickets}) == 1


def test_delta_host_fallback_under_poison():
    """device_fallback poison: delta_async serves the exact numpy
    result with zero device dispatches and no ticket delivered."""
    codec = _codec("isa", technique="reed_sol_van", k=4, m=2)
    rng = np.random.default_rng(61)
    deltas = {1: rng.integers(0, 256, 4096,
                              dtype=np.uint8).tobytes()}
    host_pd = codec.parity_delta(deltas)
    tickets = []

    async def main():
        rt = DeviceRuntime.reset()
        rt.poison("test: delta fallback")
        out = await codec.delta_async(deltas,
                                      on_ticket=tickets.append)
        assert out == host_pd
        assert rt.dispatches == 0

    run(main())
    assert tickets == []


# -- cluster: the delta write path on-device -------------------------------


def test_delta_write_device_route_and_amplification():
    """A cluster partial overwrite rides the device delta path: the
    primary's op_ec_device_dispatch histogram samples the delta
    flush's ticket, bytes read stay proportional to the touched
    range (the RMW-amplification counters must not regress), and the
    result is exact."""
    from ceph_tpu.testing import LocalCluster

    async def main():
        # nobody dies in this test, and the first encode compiles on
        # the loop every daemon shares: at FAST_CONF's 0.6 s grace that
        # stall gets all three OSDs marked down (ROADMAP A-first) just
        # as the partial write is sent, and a write that lands in a
        # degraded interval is a whole-object RMW by design, 64 KiB of
        # sub-reads.  The shipped grace keeps the premise: a clean PG.
        c = await LocalCluster(n_osds=3, seed=77,
                               conf={"heartbeat_grace": 6.0}).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="ragdelta", pg_num=8,
                pool_type="erasure")
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mons[0].osdmap.epoch)
            await c.allow_ec_overwrites("ragdelta")
            await c.wait_health(pid)
            io = c.client.io_ctx("ragdelta")
            size = 128 * 1024
            base = bytes(range(256)) * (size // 256)
            await io.write_full("obj", base)
            rt = DeviceRuntime.get()
            m = c.client.osdmap
            pool = m.pools[pid]
            pgid = pool.raw_pg_to_pg(
                m.object_locator_to_pg("obj", pid))
            _u, _up, _acting, prim = m.pg_to_up_acting_osds(pgid)
            osd = c.osds[prim]

            def _hist_count():
                h = osd.ctx.perf.dump().get("osd", {}).get(
                    "op_ec_device_dispatch")
                return sum(h["buckets_us_pow2"]) if h else 0

            before_reads = sum(o.ec.sub_read_bytes
                               for o in c.osds if not o.stopping)
            before_disp = rt.dispatches
            before_hist = _hist_count()
            patch = b"\xAB" * 2048
            await io.write("obj", patch, 1000)
            moved = sum(o.ec.sub_read_bytes
                        for o in c.osds
                        if not o.stopping) - before_reads
            assert moved < 16 * 1024, moved
            # the parity products dispatched on-device, and the op's
            # exact flush ticket fed the dispatch-stage histogram
            assert rt.dispatches > before_disp
            assert _hist_count() > before_hist
            want = bytearray(base)
            want[1000:1000 + len(patch)] = patch
            assert await io.read("obj") == bytes(want)
        finally:
            await c.stop()

    run(main())


def test_delta_write_journal_replicated_and_promoted_dup():
    """The reqid satellite: a delta write's dup row rides the
    REPLICATED shard txns (present in every live member's pgmeta
    omap), and after the primary dies a promoted replica answers the
    client's resend from its own store — no reload, no
    re-execution."""
    from ceph_tpu.msg.messages import MOSDOp
    from ceph_tpu.osd.osdmap import pg_t
    from ceph_tpu.osd.pg import PGMETA_OID
    from ceph_tpu.testing import LocalCluster
    from ceph_tpu.utils.backoff import wait_for

    class Conn:
        def __init__(self):
            self.sent = []
            self.peer_entity = "client.test"
            self.is_open = True

        def send(self, msg):
            self.sent.append(msg)

    async def main():
        c = await LocalCluster(n_osds=3, seed=88).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="dupdelta", pg_num=4,
                pool_type="erasure")
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mons[0].osdmap.epoch)
            await c.allow_ec_overwrites("dupdelta")
            await c.wait_health(pid)
            io = c.client.io_ctx("dupdelta")
            await io.write_full("obj", b"\x5a" * 65536)
            m = c.client.osdmap
            pool = m.pools[pid]
            pgid = pool.raw_pg_to_pg(
                m.object_locator_to_pg("obj", pid))
            ps = pgid.ps
            _u, _up, acting, prim = m.pg_to_up_acting_osds(pgid)
            osd = c.osds[prim]

            def mk_op(epoch):
                mm = MOSDOp(tid=4242, pool=pid, ps=ps, oid="obj",
                            snapc=None,
                            ops=[{"op": "write", "offset": 700,
                                  "data": b"\xCD" * 1024}],
                            epoch=epoch, flags=0)
                mm.src = "client.test"
                return mm

            conn = Conn()
            osd._handle_op(conn, mk_op(osd.osdmap.epoch))
            await wait_for(lambda: len(conn.sent) == 1, 20.0,
                           what="delta write reply")
            assert conn.sent[0].result == 0
            first_version = conn.sent[0].version
            # the delta path was taken (one MODIFY entry, no rewrite
            # of untouched shards) and the dup row replicated to
            # EVERY live acting member's store
            row = b"dup.client.test.4242"
            for osd_id in acting:
                member = c.osds[osd_id]
                pg = member.pgs[pg_t(pid, ps)]
                got = member.store.omap_get_values(
                    pg.cid, PGMETA_OID, [row])
                assert row in got, \
                    "dup row missing on osd.%d" % osd_id

            # primary loss: a surviving member promotes and answers
            # the resend from its own replicated journal
            await c.kill_osd(prim)
            await c.wait_osd_down(prim)

            def promoted():
                for o in c.live_osds:
                    pg = o.pgs.get(pg_t(pid, ps))
                    if pg is not None and pg.is_primary():
                        return o
                return None

            await wait_for(lambda: promoted() is not None, 30.0,
                           what="replica promoted to primary")
            osd2 = promoted()
            assert osd2.whoami != prim
            dups_before = osd2.ctx.perf.dump()["osd"].get("dup_ops",
                                                          0)
            conn2 = Conn()
            osd2._handle_op(conn2, mk_op(osd2.osdmap.epoch))
            assert len(conn2.sent) == 1    # synchronous journal hit
            assert conn2.sent[0].result == 0
            assert conn2.sent[0].version == first_version
            assert osd2.ctx.perf.dump()["osd"]["dup_ops"] \
                == dups_before + 1
        finally:
            await c.stop()

    run(main())


# -- mixed_rmw thrash oracle -----------------------------------------------


def test_mixed_rmw_thrash_round():
    """ROADMAP direction-2 oracle: a thrash round of interleaved full
    writes and partial overwrites on the same EC objects, asserted
    bit-identical to the host codec (stored shards AND hinfo crcs)
    with zero lost acked writes."""
    from ceph_tpu.testing import ClusterThrasher, LocalCluster, \
        Workload

    async def main():
        c = await LocalCluster(n_osds=3, seed=99).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="mixrmw", pg_num=4,
                pool_type="erasure")
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mons[0].osdmap.epoch)
            await c.allow_ec_overwrites("mixrmw")
            await c.wait_health(pid)
            rt = DeviceRuntime.get()
            before = rt.dispatches
            wl = Workload(c.client.io_ctx("mixrmw"), seed=3,
                          prefix="mixbg").start()
            th = ClusterThrasher(c, seed=13,
                                 actions=[("mixed_rmw", 5)])
            await th.run(pid, wl)
            await wl.stop()
            await wl.verify()
            assert wl.acked, "workload never acked a write"
            # the round genuinely exercised the device path
            assert rt.dispatches > before
        finally:
            await c.stop()

    run(main())


# -- workload-aware warmup for ragged streams ------------------------------


def test_derive_warmup_buckets_ragged():
    """The warmup satellite: a mixed-size histogram warms the ladder
    segments its flush totals imply — including the combined
    heterogeneous-flush total — not each item's pow2 ceiling."""
    from ceph_tpu.osd.ecbackend import derive_warmup_buckets

    hist = [0] * 32
    hist[14] = 300          # 16 KiB-class writes
    hist[17] = 200          # 128 KiB-class writes
    out = derive_warmup_buckets(hist, k=2, w=8)
    assert out == tuple(sorted(set(out)))
    words = [(1 << 15) // 2, (1 << 18) // 2]
    expect = set()
    for n in words + [sum(words)]:
        for _lo, seg in DeviceRuntime.ragged_plan(n):
            expect.add(seg)
    assert set(out) == expect
    # every warmed bucket is a pow2 ladder segment, so warmup's
    # compiled programs are exactly what ragged flushes dispatch
    assert all(b & (b - 1) == 0 for b in out)
