"""Device-native recovery codecs: LRC/SHEC/CLAY through the ragged
dispatch path, plus repair-traffic accounting.

Covers the direction-3 codec-plane contract end to end:

* device-vs-host bit-parity for all three codecs — SHEC and LRC
  across w=8/16/32 (LRC via explicit per-layer w profiles), CLAY at
  its GF(256) construction across d variants — over ragged size
  mixes, encode AND single/multi-failure decode;
* mid-decode chip poison completes on the host path with every
  future retired exactly once;
* `minimum_to_decode` drives degraded-read AND recovery read
  planning (fetched shard set == minimal set), and targeted shard
  reconstruction accounts repair-bytes-read / repair-bytes-moved per
  codec through perf counters -> MMgrReport -> digest and the
  chip-labeled `device_repair_bytes_read` / `device_repair_bytes_moved`
  series plus the mgr's codec-labeled
  `ceph_tpu_repair_bytes_read_total` / `ceph_tpu_repair_bytes_moved_total`
  families;
* cluster e2e write/kill/recover on an lrc pool through LocalCluster;
* the thrasher's `repair_compare` oracle: the LRC repair of the same
  planted loss reads fewer survivor bytes than the RS repair;
* the corrupt_shard matrix extended to shec/clay pools
  (detect-exactly -> repair-to-clean).
"""

import asyncio
import json
import random

import numpy as np
import pytest

from ceph_tpu.device.runtime import DeviceRuntime, K_RECOVERY_EC
from ceph_tpu.ec.plugin import ErasureCodePluginRegistry
from ceph_tpu.testing import LocalCluster

EC_CONF = {"osd_ec_subop_timeout": 1.0}

# the 8-OSD comparison cluster encodes on every member: at the dev
# 0.6s heartbeat grace a loaded CI box flaps healthy daemons, so the
# heavier clusters here run with production-ish failure detection
BIG_CONF = {"osd_ec_subop_timeout": 1.0,
            "heartbeat_grace": 6.0,
            "mon_osd_down_out_interval": 10.0}


@pytest.fixture(autouse=True)
def _offload(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_EC_OFFLOAD", "1")


def _codec(plugin, **profile):
    prof = {k: str(v) for k, v in profile.items()}
    return ErasureCodePluginRegistry.instance().factory(plugin, prof)


def _lrc_w_profile(w: int) -> dict:
    """The k=4,m=2,l=3 kml shape with an explicit per-layer word
    width (the kml shorthand pins w=8 via the sub-codec defaults)."""
    layers = [["DDc_DDc_", "w=%d" % w],
              ["DDDc____", "w=%d" % w],
              ["____DDDc", "w=%d" % w]]
    return {"mapping": "DD__DD__", "layers": json.dumps(layers)}


def run(coro, timeout=300):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


# -- device-vs-host bit parity ---------------------------------------------


def _loss_patterns(codec, rng):
    """A few recoverable erasure sets: single data, single parity,
    and a double loss when m allows."""
    n = codec.get_chunk_count()
    k = codec.get_data_chunk_count()
    mapping = codec.get_chunk_mapping()
    data_pos = ([mapping[i] for i in range(k)] if mapping
                else list(range(k)))
    parity_pos = [i for i in range(n) if i not in data_pos]
    pats = [{data_pos[0]}, {parity_pos[0]}]
    if len(parity_pos) > 1:
        pats.append({data_pos[-1], parity_pos[-1]})
    return pats


def _parity_case(codec, sizes, seed=3):
    """Encode + decode parity sweep: device paths vs host codec."""
    n = codec.get_chunk_count()
    rng = np.random.default_rng(seed)

    async def main():
        DeviceRuntime.reset()
        for size in sizes:
            data = rng.integers(0, 256, size,
                                dtype=np.uint8).tobytes()
            host = codec.encode(set(range(n)), data)
            dev = await codec.encode_async(set(range(n)), data)
            assert dev == host, "encode parity at %d bytes" % size
            for lost in _loss_patterns(codec, rng):
                chunks = {i: host[i] for i in range(n)
                          if i not in lost}
                want = set(lost)
                try:
                    hd = codec.decode(want, chunks)
                except (IOError, OSError):
                    continue        # pattern unrecoverable: skip
                dd = await codec.decode_async(want, chunks)
                assert dd == hd, \
                    "decode parity, lost %s at %d bytes" % (
                        sorted(lost), size)

    run(main())


@pytest.mark.parametrize("w", [8, 16, 32])
def test_shec_device_parity_w(w):
    codec = _codec("shec", k=4, m=3, c=2, w=w)
    _parity_case(codec, (5000, 64 << 10))


@pytest.mark.parametrize("w", [8, 16, 32])
def test_lrc_device_parity_w(w):
    codec = _codec("lrc", **_lrc_w_profile(w))
    _parity_case(codec, (5000, 64 << 10))


@pytest.mark.parametrize("d", [5, 6])
def test_clay_device_parity(d):
    codec = _codec("clay", k=4, m=3, d=d)
    _parity_case(codec, (4096, 48 << 10))


def test_ragged_mix_parity_concurrent():
    """A log-uniform size mix across all three codecs issued
    CONCURRENTLY — the heterogeneous flushes batch through the same
    bucket-ladder staging, and every result is bit-identical to the
    host codec."""
    codecs = {
        "lrc": _codec("lrc", k=4, m=2, l=3),
        "shec": _codec("shec", k=4, m=3, c=2, w=8),
        "clay": _codec("clay", k=4, m=2),
    }
    rng = np.random.default_rng(13)
    sizes = [int(s) for s in np.exp(rng.uniform(
        np.log(1 << 10), np.log(1 << 17), 6))]

    async def main():
        DeviceRuntime.reset()
        objs = {name: [rng.integers(0, 256, s,
                                    dtype=np.uint8).tobytes()
                       for s in sizes]
                for name in codecs}
        hosts = {name: [codecs[name].encode(
                    set(range(codecs[name].get_chunk_count())), d)
                 for d in objs[name]] for name in codecs}
        outs = await asyncio.gather(*[
            codecs[name].encode_async(
                set(range(codecs[name].get_chunk_count())), d)
            for name in codecs for d in objs[name]])
        it = iter(outs)
        for name in codecs:
            for i in range(len(sizes)):
                assert next(it) == hosts[name][i], \
                    "%s ragged encode parity at %d bytes" % (
                        name, sizes[i])

    run(main())


def test_poison_mid_decode_completes_on_host():
    """A chip lost mid-decode: the armed fault fires inside the
    dispatch, the batcher poisons the chip and host-encodes the
    flush, and every awaiting decode future retires exactly once
    with bit-correct bytes."""
    codec = _codec("shec", k=4, m=3, c=2, w=8)
    n = codec.get_chunk_count()
    rng = np.random.default_rng(17)

    async def main():
        rt = DeviceRuntime.reset()
        datas = [rng.integers(0, 256, 16 << 10,
                              dtype=np.uint8).tobytes()
                 for _ in range(4)]
        hosts = [codec.encode(set(range(n)), d) for d in datas]
        chip = rt.chips[0]
        chip.inject_fault(1)        # first dispatch on chip 0 dies
        results = await asyncio.gather(*[
            codec.decode_async({0}, {i: h[i] for i in range(1, n)},
                               chip=0)
            for h in hosts])
        for res, h in zip(results, hosts):
            assert res[0] == h[0], "mid-poison decode lost parity"
        assert rt.host_fallbacks >= 1
        chip.clear_faults()
        chip.heal()
        # healed chip serves on-device again, still bit-exact
        res = await codec.decode_async(
            {0}, {i: hosts[0][i] for i in range(1, n)}, chip=0)
        assert res[0] == hosts[0][0]

    run(main())


# -- warmup families -------------------------------------------------------


def test_device_families_cover_codec_shapes():
    """Every recovery codec advertises the program families its
    dispatches ride — encode AND decode/repair shapes — so
    `_maybe_warmup` compiles them at boot instead of on the first
    repair's hot path."""
    lrc = _codec("lrc", k=4, m=2, l=3)
    shec = _codec("shec", k=4, m=3, c=2, w=8)
    clay = _codec("clay", k=4, m=2)
    rs = _codec("jerasure", technique="reed_sol_van", k=4, m=2, w=8)
    assert len(rs.device_families()) == 1
    # LRC: global layer + shared local family + local repair rows
    fams = lrc.device_families()
    assert len(fams) == 3
    # SHEC: the shingled matrix + the single-failure decode inverse
    assert len(shec.device_families()) == 2
    # CLAY: encode MDS rows + single-node repair MDS rows
    assert len(clay.device_families()) == 2

    async def main():
        rt = DeviceRuntime.reset()
        for fam_codec in (lrc, shec, clay):
            for matrix, w in fam_codec.device_families():
                await rt.warmup_ec(matrix, w, buckets=(1024,))
        assert rt.compile_count > 0
        before = rt.compile_count
        # re-warming the same families compiles nothing new
        for matrix, w in lrc.device_families():
            await rt.warmup_ec(matrix, w, buckets=(1024,))
        assert rt.compile_count == before

    run(main())


# -- repair-traffic series (registry + exporter) ---------------------------


def test_chip_repair_series_exported():
    """The chip-labeled repair counters: note_repair accumulates,
    metrics() exports `device_repair_bytes_read` /
    `device_repair_bytes_moved`, and prom_lines carries them with
    the chip label (lint-clean exposition)."""
    from ceph_tpu.utils.exporter import validate_exposition
    rt = DeviceRuntime(chips=2)
    rt.chips[1].note_repair(4096, 1024)
    m = rt.chips[1].metrics()
    assert m["device_repair_bytes_read"] == 4096
    assert m["device_repair_bytes_moved"] == 1024
    assert rt.chips[0].metrics()["device_repair_bytes_read"] == 0
    lines = rt.prom_lines()
    text = "\n".join(lines) + "\n"
    validate_exposition(text)
    assert any("device_repair_bytes_read" in ln
               and 'chip="1"' in ln and " 4096" in ln
               for ln in lines)
    assert any("device_repair_bytes_moved" in ln
               and 'chip="1"' in ln for ln in lines)


def test_registry_lint_clean_with_repair_series():
    from ceph_tpu.trace import registry
    assert registry.lint_repo() == []


def test_digest_folds_repair_traffic():
    """osd_stats.repair rows sum per codec into the digest's
    repair_traffic section — identically on the columnar PGMap and
    the DictPGMap golden reference."""
    from ceph_tpu.mgr.pgmap import DictPGMap, PGMap
    rows = {
        "osd.0": {"repair": {"lrc": {"read": 100, "moved": 40,
                                     "objects": 2, "targeted": 2,
                                     "full": 0}}},
        "osd.1": {"repair": {"lrc": {"read": 50, "moved": 10,
                                     "objects": 1, "targeted": 0,
                                     "full": 1},
                             "jerasure": {"read": 300, "moved": 80,
                                          "objects": 1,
                                          "targeted": 1,
                                          "full": 0}}},
    }
    for cls in (PGMap, DictPGMap):
        pm = cls(stale_after=1e9)
        for d, st in rows.items():
            pm.apply_report(d, [], dict(st), stamp=10.0)
        rep = pm.digest(now=11.0)["repair_traffic"]
        assert rep["lrc"] == {"read": 150, "moved": 50, "objects": 3,
                              "targeted": 2, "full": 1}
        assert rep["jerasure"]["read"] == 300, rep


def test_best_version_cost_planning_minimum_to_decode():
    """Version selection is minimum_to_decode-costed, not
    MDS-assumed: the newest decodable version still wins (recency is
    correctness), but the decode stages exactly the minimal planned
    shard set — and every candidate version's cost is recorded in
    `last_version_plan` in sub-chunk units."""
    from ceph_tpu.osd.ecbackend import ECPGBackend
    be = ECPGBackend.__new__(ECPGBackend)
    codec = _codec("shec", k=4, m=3, c=2, w=8)
    n = codec.get_chunk_count()
    k = codec.get_data_chunk_count()
    data = b"version-plan " * 700
    enc = codec.encode(set(range(n)), data)
    old, new = (1, 5), (2, 9)
    by_ver = {
        old: {j: (enc[j], len(data)) for j in range(n)},
        new: {j: (enc[j], len(data)) for j in (0, 1, 2, 4, 5)},
    }
    best = be._best_version(codec, k, by_ver)
    assert best is not None
    ver, use = best
    assert ver == new, "newest decodable version must win"
    assert use <= {0, 1, 2, 4, 5}
    plan = be.last_version_plan
    assert plan["version"] == new
    assert set(plan["shards"]) == use
    assert set(plan["candidates"]) == {old, new}
    # the complete old version costs exactly its data set (want is
    # fully present: no shingle fetch at all)
    assert plan["candidates"][old]["cost_chunks"] == float(k)
    # the winning plan is decodable from EXACTLY the planned set
    out = codec.decode_concat({j: enc[j] for j in use})
    assert out[:len(data)] == data
    # a fully-present newest version decodes from its data shards
    # alone — the gathered parity shards are never staged
    by_ver2 = {new: {j: (enc[j], len(data)) for j in range(n)}}
    ver2, use2 = be._best_version(codec, k, by_ver2)
    assert ver2 == new
    assert use2 == set(range(k))
    assert be.last_version_plan["cost_chunks"] == float(k)


def test_lrc_single_shard_repair_reads_three_eighths_of_rs():
    """At matched durability (RS k=8,m=4 against LRC k=8,m=4,l=3) a
    lost data shard of a 256 KiB object is rebuilt on the device from
    exactly the shards `minimum_to_decode` plans: RS reads its k
    survivors (262,144 bytes), LRC its local group (98,304): 0.375x."""
    rng = np.random.default_rng(43)
    obj = rng.integers(0, 256, 256 << 10, dtype=np.uint8).tobytes()
    read = {}

    async def main():
        for name, codec in (
                ("rs", _codec("jerasure", technique="reed_sol_van",
                              k=8, m=4, w=8)),
                ("lrc", _codec("lrc", k=8, m=4, l=3))):
            rt = DeviceRuntime.reset()
            n = codec.get_chunk_count()
            full = codec.encode(set(range(n)), obj)
            mapping = codec.get_chunk_mapping()
            lost = mapping[0] if mapping else 0
            plan = codec.minimum_to_decode({lost},
                                           set(range(n)) - {lost})
            chunks = {h: full[h] for h in plan}
            rebuilt = await codec.decode_async({lost}, chunks,
                                               klass=K_RECOVERY_EC)
            assert rebuilt[lost] == full[lost], name
            assert rt.dispatches >= 1 and rt.host_fallbacks == 0, name
            read[name] = sum(len(b) for b in chunks.values())

    run(main())
    assert read == {"rs": 262144, "lrc": 98304}
    assert read["lrc"] / read["rs"] == 0.375


# -- cluster e2e -----------------------------------------------------------


def _acting_of(client, pool_id, oid):
    m = client.osdmap
    pgid = m.pools[pool_id].raw_pg_to_pg(
        m.object_locator_to_pg(oid, pool_id))
    up, upp, acting, actingp = m.pg_to_up_acting_osds(pgid)
    return pgid, acting, actingp


def test_lrc_cluster_write_kill_recover():
    """Cluster e2e on an lrc pool: writes land on all 6 shards
    (k=2,m=2,l=2 -> 4+2 local chunks), a killed+wiped member is
    rebuilt through recovery's TARGETED minimal-set reconstruction
    (repair-traffic counters account it per codec), degraded reads
    plan their fetch through minimum_to_decode (fetched == minimal),
    and the repair figures flow to the mgr digest and the
    codec-labeled exporter families."""

    async def main():
        c = await LocalCluster(n_osds=7, with_mgr=True,
                               conf=EC_CONF).start()
        try:
            await c.client.mon_command(
                "osd erasure-code-profile set", name="lrc22",
                profile={"plugin": "lrc", "k": "2", "m": "2",
                         "l": "2"})
            pid = await c.create_pool("lrcpool", pg_num=4,
                                      pool_type="erasure",
                                      erasure_code_profile="lrc22")
            pool = c.client.osdmap.pools[pid]
            assert pool.size == 6, pool.size   # 4 + 2 local parities
            await c.wait_health(pid, timeout=120.0)
            io = c.client.io_ctx("lrcpool")
            payloads = {}
            rng = random.Random(5)
            for i in range(6):
                oid = "lrc-%d" % i
                payloads[oid] = rng.randbytes(
                    rng.randrange(4, 17) * 1024)
                await asyncio.wait_for(
                    io.write_full(oid, payloads[oid]), 30.0)
            # --- degraded-read planning: kill a non-primary member,
            # the primary's plan must fetch exactly the minimal set
            pgid, acting, prim = _acting_of(c.client, pid, "lrc-0")
            victim = next(o for o in acting if o != prim and o >= 0)
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            got = await asyncio.wait_for(io.read("lrc-0"), 30.0)
            assert got == payloads["lrc-0"]
            from ceph_tpu.osd.osdmap import pg_t
            posd = next(o for o in c.live_osds if o.whoami == prim)
            plan = posd.ec.last_read_plan
            assert plan is not None and plan["minimal"], plan
            assert not plan["widened"], plan
            # every remotely queried shard was in the minimal set
            assert plan["queried"] <= plan["minimal"], plan
            assert plan["queried"] == plan["minimal"] - {
                plan["local"]}, plan
            # --- kill+wipe -> recovery rebuilds the wiped member's
            # shards through targeted reconstruction
            await c.revive_osd(victim, wipe=True)
            await c.wait_osd_up(victim)
            await c.wait_health(pid, timeout=120.0)
            for oid, data in sorted(payloads.items()):
                got = await asyncio.wait_for(io.read(oid), 30.0)
                assert got == data, "lost %s after recovery" % oid
            rep = {}
            for o in c.live_osds:
                for cname, row in o.ec.repair_traffic.items():
                    agg = rep.setdefault(cname, {"read": 0,
                                                 "targeted": 0})
                    agg["read"] += row["read"]
                    agg["targeted"] += row["targeted"]
            assert rep.get("lrc", {}).get("targeted", 0) > 0, rep
            assert rep["lrc"]["read"] > 0, rep
            # --- the accounting reached the mgr digest...
            from ceph_tpu.utils.backoff import wait_for
            await wait_for(
                lambda: (c.digest() or {}).get(
                    "repair_traffic", {}).get("lrc", {}).get(
                        "read", 0) > 0,
                30.0, what="repair_traffic in the mgr digest")
            # ...and the codec-labeled exporter families render
            text = c.mgr.exporter.render()
            assert 'ceph_tpu_repair_bytes_read_total{codec="lrc"}' \
                in text
            assert "ceph_tpu_repair_bytes_moved_total" in text
            from ceph_tpu.utils.exporter import validate_exposition
            validate_exposition(text)
            # ...and `status` renders the cross-codec repair-bytes
            # panel beside device_util (the direction-3 follow-on)
            st = await c.client.mon_command("status")
            panel = st.get("repair_traffic") or {}
            assert panel.get("lrc", {}).get("read", 0) > 0, st
            assert set(panel["lrc"]) == {"read", "moved", "objects",
                                         "targeted", "full"}
        finally:
            await c.stop()

    run(main())


def test_clay_cluster_subchunk_recovery():
    """Cluster e2e on a clay pool: a wiped member's shards rebuild
    through the sub-chunk ranged repair path — `_reconstruct_shard`
    preflights the geometry with a length-0 attr read, fetches only
    each helper's repair planes, and `repair_async` couples the lost
    shard back out — with the per-codec targeted counter proving the
    bandwidth-optimal path (not the full read + re-encode) served."""

    async def main():
        c = await LocalCluster(n_osds=5, conf=EC_CONF).start()
        try:
            await c.client.mon_command(
                "osd erasure-code-profile set", name="clay22",
                profile={"plugin": "clay", "k": "2", "m": "2"})
            pid = await c.create_pool("claypool", pg_num=4,
                                      pool_type="erasure",
                                      erasure_code_profile="clay22")
            await c.wait_health(pid, timeout=120.0)
            io = c.client.io_ctx("claypool")
            payloads = {}
            rng = random.Random(11)
            for i in range(5):
                oid = "clay-%d" % i
                payloads[oid] = rng.randbytes(
                    rng.randrange(4, 13) * 1024)
                await asyncio.wait_for(
                    io.write_full(oid, payloads[oid]), 30.0)
            _pgid, acting, prim = _acting_of(c.client, pid, "clay-0")
            victim = next(o for o in acting if o != prim and o >= 0)
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            await c.revive_osd(victim, wipe=True)
            await c.wait_osd_up(victim)
            await c.wait_health(pid, timeout=120.0)
            for oid, data in sorted(payloads.items()):
                got = await asyncio.wait_for(io.read(oid), 30.0)
                assert got == data, "lost %s after clay recovery" \
                    % oid
            targeted = sum(
                o.ec.repair_traffic.get("clay", {}).get("targeted", 0)
                for o in c.live_osds)
            assert targeted > 0, [
                o.ec.repair_traffic for o in c.live_osds]
        finally:
            await c.stop()

    run(main())


def test_thrash_repair_compare_lrc_beats_rs():
    """The thrasher's repair_compare oracle: the same planted
    single-shard loss repairs with strictly fewer survivor bytes
    read on the LRC pool than on the RS pool, both rebuilds
    bit-identical to the stored shards."""

    async def main():
        c = await LocalCluster(n_osds=8, conf=BIG_CONF).start()
        try:
            await c.client.mon_command(
                "osd erasure-code-profile set", name="cmp-rs",
                profile={"plugin": "jerasure", "k": "4", "m": "2",
                         "technique": "reed_sol_van"})
            await c.client.mon_command(
                "osd erasure-code-profile set", name="cmp-lrc",
                profile={"plugin": "lrc", "k": "4", "m": "2",
                         "l": "3"})
            rs_pid = await c.create_pool(
                "cmp-rs", pg_num=4, pool_type="erasure",
                erasure_code_profile="cmp-rs")
            lrc_pid = await c.create_pool(
                "cmp-lrc", pg_num=4, pool_type="erasure",
                erasure_code_profile="cmp-lrc")
            await c.wait_health(rs_pid, timeout=120.0)
            await c.wait_health(lrc_pid, timeout=120.0)
            from ceph_tpu.testing.thrasher import ClusterThrasher
            t = ClusterThrasher(c, seed=9,
                                actions=[("repair_compare", 7)])
            t._pool_ids = [rs_pid, lrc_pid]
            await t._dispatch(t.plan[0], None)
            assert any("repair_compare" in ln for ln in t.log), t.log
        finally:
            await c.stop()

    run(main())


def test_corrupt_shard_on_shec_and_clay_pools():
    """The corrupt_shard matrix extended to shec/clay profiles:
    planted rot on pools of both codecs is detected exactly,
    repaired to clean, and the payloads survive — the scrub plane is
    codec-agnostic all the way through the recovery codecs."""

    async def main():
        c = await LocalCluster(n_osds=6, with_mgr=True,
                               conf=BIG_CONF).start()
        try:
            await c.client.mon_command(
                "osd erasure-code-profile set", name="rot-shec",
                profile={"plugin": "shec", "k": "2", "m": "2",
                         "c": "1", "w": "8"})
            await c.client.mon_command(
                "osd erasure-code-profile set", name="rot-clay",
                profile={"plugin": "clay", "k": "2", "m": "2"})
            shec_pid = await c.create_pool(
                "rot-shec", pg_num=4, pool_type="erasure",
                erasure_code_profile="rot-shec")
            clay_pid = await c.create_pool(
                "rot-clay", pg_num=4, pool_type="erasure",
                erasure_code_profile="rot-clay")
            await c.wait_health(shec_pid, timeout=120.0)
            await c.wait_health(clay_pid, timeout=120.0)
            from ceph_tpu.testing.thrasher import ClusterThrasher
            t = ClusterThrasher(c, seed=21, actions=[
                ("corrupt_shard", 3), ("corrupt_shard", 4)])
            t._pool_ids = [shec_pid, clay_pid]
            t.scrub_oracle = False
            await t._corrupt_round(c, shec_pid, 3, ec=True)
            await t._corrupt_round(c, clay_pid, 4, ec=True)
        finally:
            await c.stop()

    run(main())
