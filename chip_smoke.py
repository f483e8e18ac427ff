#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two device hot paths once through the entry points users have
(the EC plug-in registry, the bulk PG mapper, a LocalCluster serving an EC
pool), compares every result with the host reference, and fails on any host
fallback.  One process, one import of JAX, no child that needs the chip.

    python3 chip_smoke.py             # one TPU chip, the driver's run
    python3 chip_smoke.py --chips 4   # the mesh comparison only, four chips
    CEPH_TPU_EC_OFFLOAD=1 CEPH_TPU_PALLAS_INTERPRET=1 JAX_PLATFORMS=cpu \\
      python3 chip_smoke.py --rehearse --ec-mib 1 --pgs 8192 --pg-num 8 \\
        --objects 4 --object-kib 64 --overwrites 4 --degraded 2
                      # sandbox rehearsal (k2m1 on 3 OSDs), never "ok"

Every earlier line of standard output is one JSON object per phase; the last
line is {"ok": ..., "device": {...}}.  Off the chip it never says ok and exits
non-zero, rehearsal or not.
"""

import argparse
import asyncio
import faulthandler
import json
import os
import sys
import time

import numpy as np

# switches that turn a kernel off, or the device path, without a trace
KILL_SWITCHES = ("CEPH_TPU_EC_FUSED", "CEPH_TPU_NO_PALLAS_CRUSH",
                 "CEPH_TPU_PALLAS_INTERPRET", "CEPH_TPU_EC_OFFLOAD",
                 "CEPH_TPU_MESH_CHIPS")
REHEARSE = False
CACHE = {"requests": 0, "hits": 0}  # persistent compile cache, process-wide


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}, default=str), flush=True)


def check(cond, what: str, tpu_only: bool = False) -> None:
    """A failed check ends the run; a rehearsal off the chip lets the
    checks that only a TPU can meet go by (it never reports ok anyway)."""
    if not cond and not (tpu_only and REHEARSE):
        raise SystemExit("chip_smoke: FAILED: " + what)


def _dump_tasks() -> None:
    """Where every coroutine is waiting: a phase that hangs in an await
    leaves the thread stacks faulthandler prints saying only `select`."""
    for task in asyncio.all_tasks():
        # thousands of idle messenger readers and writers say nothing
        if not task.get_coro().__qualname__.startswith(
                ("Connection.", "Messenger.")):
            task.print_stack(limit=8, file=sys.stderr)


class deadline:
    """A phase that has quietly gone to the host, or waits for an answer
    that never comes, ends with the stacks of its threads and tasks."""

    def __init__(self, seconds: float):
        self.seconds = seconds

    def __enter__(self):
        faulthandler.dump_traceback_later(self.seconds, exit=True)
        self.soft = asyncio.get_running_loop().call_later(
            self.seconds - 10, _dump_tasks)
        self.t0 = time.monotonic()
        self.c0 = dict(CACHE)
        return self

    def __exit__(self, *exc):
        self.soft.cancel()
        faulthandler.cancel_dump_traceback_later()

    def took(self) -> dict:
        return {"seconds": round(time.monotonic() - self.t0, 3),
                "xla_compiled": (CACHE["requests"] - self.c0["requests"]
                                 - CACHE["hits"] + self.c0["hits"]),
                "xla_from_cache": CACHE["hits"] - self.c0["hits"]}


def counters(rt, before: int = 0) -> dict:
    """The check that matters most: work reached the device and nothing
    was served by a host fallback."""
    from ceph_tpu.ec.batcher import DeviceBatcher
    c = {"dispatches": rt.dispatches, "compile_count": rt.compile_count,
         "host_fallbacks": rt.host_fallbacks,
         "fallback_count": rt.fallback_count,
         "fallback_reason": rt.fallback_reason,
         "host_flushes": DeviceBatcher.get().host_flushes,
         "chips_in_fallback": [ch.index for ch in rt.chips if ch.fallback]}
    check(c["dispatches"] > before, "no device dispatch in this phase")
    check(c["host_fallbacks"] == c["fallback_count"] == c["host_flushes"]
          == 0 and not c["chips_in_fallback"],
          "host fallback served device work: %s" % c)
    return c


# -- ec_codec: registry -> encode/decode/delta_async -> batcher -> stream ----

def _split(shards, k):
    return np.stack([np.frombuffer(shards[i], np.uint8) for i in range(k)])


def k8m3_codec():
    """(codec, k, n, matrix, w) through the plug-in registry, as an OSD
    gets its codec."""
    from ceph_tpu.ec.plugin import ErasureCodePluginRegistry
    codec = ErasureCodePluginRegistry.instance().factory(
        "isa", {"technique": "reed_sol_van", "k": "8", "m": "3"})
    return (codec, codec.get_data_chunk_count(), codec.get_chunk_count(),
            *codec._device_matrix())


async def encode_checked(codec, data: bytes, chip=None) -> tuple:
    """encode_async -> batcher -> stream -> kernel, then every parity
    byte compared with the host codec's.  Returns (shards, seconds the
    device path took)."""
    from ceph_tpu.ec.batcher import host_encode
    k, n = codec.get_data_chunk_count(), codec.get_chunk_count()
    t0 = time.monotonic()
    sh = await codec.encode_async(set(range(n)), data, chip=chip)
    dt = round(time.monotonic() - t0, 3)
    ref = host_encode(*codec._device_matrix(), _split(sh, k))
    check(all(sh[k + i] == ref[i].tobytes() for i in range(n - k)),
          "device parity != host codec (%d B, chip %s)" % (len(data), chip))
    return sh, dt


async def phase_ec(args, rng, rt) -> None:
    import jax
    import jax.numpy as jnp
    from ceph_tpu import native
    from ceph_tpu.ec.batcher import DeviceBatcher, host_encode
    from ceph_tpu.ec.kernels import FusedEncoder
    with deadline(args.deadline) as dl:
        codec, k, n, matrix, w = k8m3_codec()
        # 1/2 of the bytes as 4 MiB stripes, 1/4 as 64 KiB, 1/4 as 4 KiB
        total = args.ec_mib << 20
        sizes = ([4 << 20] * (total // 2 >> 22) + [64 << 10] * (total >> 18)
                 + [4 << 10] * (total >> 14))
        rng.shuffle(sizes)
        sem = asyncio.Semaphore(64)

        async def encode(size):
            data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            async with sem:
                return (await encode_checked(codec, data))[0]

        stripes = await asyncio.gather(*map(encode, sizes))
        t_enc = dl.took()["seconds"]

        async def decode(sh, erased):
            async with sem:
                got = await codec.decode_async(
                    set(range(n)),
                    {i: sh[i] for i in range(n) if i not in erased})
            check(all(got[i] == sh[i] for i in range(n)),
                  "reconstruct of %s differs" % (erased,))

        for erased in ((2,), (1, 9), (0, 5, 10)):
            await asyncio.gather(*(decode(sh, erased) for sh in stripes))

        async def delta(sh):
            size = len(sh[0])
            j, lo = int(rng.integers(k)), int(rng.integers(size // 2))
            d = rng.integers(0, 256, size // 2, dtype=np.uint8).tobytes()
            got = await codec.delta_async({j: d})
            check(got == codec.parity_delta({j: d}),
                  "device parity delta != host")
            data = _split(sh, k).copy()
            data[j, lo:lo + len(d)] ^= np.frombuffer(d, np.uint8)
            new = host_encode(matrix, w, data)
            for i in range(n - k):
                old = np.frombuffer(sh[k + i], np.uint8).copy()
                old[lo:lo + len(d)] ^= np.frombuffer(got[i], np.uint8)
                check((old == new[i]).all(), "delta-updated parity wrong")

        await asyncio.gather(*(delta(sh) for sh in stripes[:64]))
        enc = DeviceBatcher._encoder(tuple(tuple(r) for r in matrix), w)
        fused = isinstance(enc, FusedEncoder)
        check(fused, "batcher built %s, not FusedEncoder"
              % type(enc).__name__, tpu_only=True)
        custom = fused and "tpu_custom_call" in enc._fn_for(1 << 17).lower(
            jax.ShapeDtypeStruct((k, 1 << 17), jnp.uint32)).as_text()
        check(custom, "no tpu_custom_call in the encoder's program",
              tpu_only=True)
        emit("ec_codec", profile="isa reed_sol_van k8m3", mib=args.ec_mib,
             stripes=len(sizes), erasures=[1, 2, 3], deltas=64,
             encoder=type(enc).__name__, tpu_custom_call=custom,
             host_reference="native gfec.c" if native.lib() else "numpy",
             encode_seconds=t_enc, **dl.took(), counters=counters(rt))


# -- crush: OSDMapMapping over one 10M-PG pool ------------------------------

def build_osdmap(n_osds: int, pg_num: int):
    """Hosts of 20 OSDs, straw2, chooseleaf firstn host."""
    from ceph_tpu.models.crushmap import (CHOOSELEAF_FIRSTN, EMIT, STRAW2,
                                          TAKE, CrushMap)
    from ceph_tpu.osd.osdmap import (OSD_EXISTS, OSD_UP, Incremental,
                                     OSDMap, PGPool)
    crush, host_ids = CrushMap(), []
    for h in range(n_osds // 20):
        b = crush.add_bucket(STRAW2, 1, list(range(h * 20, h * 20 + 20)),
                             [0x10000] * 20, id=-(h + 2))
        host_ids.append(b.id)
    crush.add_bucket(STRAW2, 2, host_ids,
                     [crush.buckets[h].weight for h in host_ids], id=-1)
    crush.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1), (EMIT, 0, 0)],
                   id=0)
    m = OSDMap()
    inc = Incremental(epoch=1)
    inc.new_max_osd = n_osds
    inc.new_crush = crush
    inc.new_pools[1] = PGPool(id=1, name="smoke", pg_num=pg_num, size=3,
                              crush_rule=0)
    m.apply_incremental(inc)
    inc = m.new_incremental()
    for o in range(n_osds):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = 0x10000
    m.apply_incremental(inc)
    return m


def phase_crush(args, rng, rt, chip=None, before=0) -> None:
    from ceph_tpu.osd.osdmap import pg_t
    from ceph_tpu.parallel.mapping import OSDMapMapping
    with deadline(args.deadline) as dl:
        m = build_osdmap(1000, args.pgs)
        sample = [int(p) for p in rng.choice(args.pgs, 2000, replace=False)]
        secs, prev, moved = [], None, None
        for step in ("cold", "10 osds out"):
            t0 = time.monotonic()
            mp = OSDMapMapping(m, runtime=rt, chip=chip)
            secs.append(round(time.monotonic() - t0, 3))
            check(mp.device_pools == 1 and mp.scalar_pools == 0,
                  "%s: pool mapped on the host (device_pools=%d)"
                  % (step, mp.device_pools))
            for ps in sample:
                check(mp.get(pg_t(1, ps)) ==
                      tuple(m.pg_to_up_acting_osds(pg_t(1, ps))),
                      "%s: pg 1.%x differs from the host engine"
                      % (step, ps))
            if prev is not None:
                moved = int((prev != mp.pools[1].up).any(axis=1).sum())
            prev = mp.pools[1].up
            inc = m.new_incremental()
            for o in rng.choice(m.max_osd, 10, replace=False):
                inc.new_weight[int(o)] = 0
            m.apply_incremental(inc)
        pallas = bool(m.device_mapper().fm.__dict__.get("_pallas_cache"))
        check(pallas, "the Pallas descent was not used", tpu_only=True)
        emit("crush", osds=m.max_osd, pg_num=args.pgs, size=3,
             chip=chip, pallas_descent=pallas, sampled=len(sample),
             map_seconds=secs, moved_pgs=moved, cut=args.pgs_cut, **dl.took(),
             counters=counters(rt, before))


# -- cluster: LocalCluster, EC pool, write / overwrite / read / degraded ----

async def phase_cluster(args, rng, rt, before) -> None:
    import copy
    from ceph_tpu.osd.osdmap import POOL_TYPE_ERASURE, PGPool
    from ceph_tpu.parallel.mapping import OSDMapMapping
    from ceph_tpu.testing.cluster import FAST_CONF, LocalCluster
    from ceph_tpu.utils.backoff import wait_for
    from ceph_tpu.utils.config import DEFAULT_SCHEMA
    k, m, n_osds = args.profile
    # a deployment's timers, not test pacing: every option the harness
    # paces for tests goes back to its shipped default
    shipped = {o.name: o.default for o in DEFAULT_SCHEMA}
    conf = {name: shipped[name] for name in FAST_CONF if name in shipped}
    timers = {t: conf[t] for t in (
        "heartbeat_interval", "heartbeat_grace",
        "mon_osd_down_out_interval", "osd_ec_subop_timeout")}
    name = "k%dm%d" % (k, m)

    async def boot():
        c = await LocalCluster(n_osds=n_osds, conf=conf).start()
        await c.client.mon_command(
            "osd erasure-code-profile set", name=name,
            profile={"plugin": "isa", "k": str(k), "m": str(m),
                     "crush-failure-domain": "osd"})
        await c.client.wait_for_epoch(c.leader().osdmap.epoch)
        return c

    def worst_ping(c):
        """The margin against heartbeat_grace: the slowest ping round
        trip any OSD has seen from any peer since boot, in seconds."""
        return round(max((p.max_s for o in c.osds
                          for p in o.network.peers.values()), default=0.0), 3)

    async def marked_down(c):
        log = (await c.client.mon_command("log last", n=1000))["lines"]
        return log, [e["message"] for e in log
                     if "marked down" in e.get("message", "")]

    with deadline(args.deadline) as dl:
        # Every daemon shares this one event loop, and a CRUSH compile on
        # it outlasts the heartbeat grace.  So boot once to learn the map
        # the mon builds for these OSDs, stop, map the pool-to-be with
        # nobody else on the loop (the daemons share the DeviceMapper and
        # its programs by crush content), then boot the cluster that serves.
        c = await boot()
        ahead = copy.deepcopy(c.client.osdmap)
        await c.stop()
        inc = ahead.new_incremental()
        pid = max(ahead.pool_max, 0) + 1
        inc.new_pools[pid] = PGPool(
            id=pid, name="smoke", type=POOL_TYPE_ERASURE, size=k + m,
            min_size=k, pg_num=args.pg_num, crush_rule=1,
            erasure_code_profile=name)
        ahead.apply_incremental(inc)
        t0 = time.monotonic()
        OSDMapMapping(ahead, runtime=rt)
        t_premap = round(time.monotonic() - t0, 3)
        # the payload too is made off the daemons' clock
        size = args.object_kib << 10
        objs = {"obj-%d" % i: bytearray(
            rng.integers(0, 256, size, dtype=np.uint8).tobytes())
            for i in range(args.objects)}
        c = await boot()
        try:
            client = c.client
            epoch0 = client.osdmap.epoch
            check(client.osdmap.crush.to_dict() == ahead.crush.to_dict(),
                  "the mon built another crush map than the one mapped ahead")
            # a pool is created on a cluster that has been up a while: let
            # every OSD hear every peer's heartbeat first, so that each
            # holds a round-trip time for each peer (worst_ping below)
            t0 = time.monotonic()
            await wait_for(
                lambda: all(len(o.network.peers) == n_osds - 1
                            for o in c.osds), 60, what="heartbeat mesh")
            t_mesh = round(time.monotonic() - t0, 3)
            t0 = time.monotonic()
            got = await c.create_pool("smoke", pg_num=args.pg_num,
                                      pool_type="erasure",
                                      erasure_code_profile=name)
            check(got == pid, "pool id %d, mapped ahead as %d" % (got, pid))
            await c.allow_ec_overwrites("smoke")    # the overwrites below
            await c.wait_health(pid, timeout=300)
            # first sight of the profile starts each OSD's warmup_ec
            codec = [o.ec.codec(client.osdmap.pools[pid])
                     for o in c.osds][0]
            mkey = tuple(tuple(r) for r in codec._device_matrix()[0])
            await wait_for(
                lambda: all(("ec", mkey, 8, b) in rt.programs
                            for b in (1024, 4096, 16384)),
                300, what="warmup_ec")
            t_ready = round(time.monotonic() - t0, 3)
            log, downs = await marked_down(c)
            check(not downs, "marked down while the pool peered: %s" % downs)
            ping_peering = worst_ping(c)

            io = client.io_ctx("smoke")
            sem = asyncio.Semaphore(16)

            async def bounded(coro):
                async with sem:
                    return await coro

            async def put(oid):
                async with sem:     # the 4 MiB copy is made when it is sent
                    await io.write_full(oid, bytes(objs[oid]))

            t0 = time.monotonic()
            await asyncio.gather(*map(put, objs))
            t_write = round(time.monotonic() - t0, 3)
            t0 = time.monotonic()
            over = []
            for oid in list(objs)[:args.overwrites]:
                n = int(rng.integers(4 << 10, (64 << 10) + 1))
                off = int(rng.integers(0, size - n))
                patch = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                objs[oid][off:off + n] = patch
                over.append(bounded(io.write(oid, patch, offset=off)))
            await asyncio.gather(*over)
            t_over = round(time.monotonic() - t0, 3)

            async def verify(oid):
                check(await bounded(io.read(oid)) == bytes(objs[oid]),
                      "read of %s differs from the acknowledged write" % oid)

            t0 = time.monotonic()
            await asyncio.gather(*map(verify, objs))
            t_read = round(time.monotonic() - t0, 3)

            # lose the OSD that holds a data shard of the most objects,
            # then read objects that need that shard rebuilt (one bulk
            # map: the host engine takes 12 ms a PG, on this loop)
            placed = OSDMapMapping(client.osdmap, runtime=rt)

            def data_osds(oid):
                om = client.osdmap
                pg = om.pools[pid].raw_pg_to_pg(
                    om.object_locator_to_pg(oid, pid))
                return placed.get(pg)[2][:k]

            holders = {o: data_osds(o) for o in objs}
            victim = max(range(n_osds), key=lambda i: sum(
                i in h for h in holders.values()))
            hit = [o for o, h in holders.items() if victim in h]
            hit = hit[:args.degraded]
            check(len(hit) == args.degraded, "too few objects on the victim")
            from ceph_tpu.ec.batcher import DeviceBatcher
            bat = DeviceBatcher.get()
            b0 = bat.batches_flushed
            ping_workload = worst_ping(c)   # before the kill prunes rows
            t0 = time.monotonic()
            await c.kill_osd(victim)
            await c.wait_osd_down(victim, timeout=120)
            t_down = round(time.monotonic() - t0, 3)
            t0 = time.monotonic()
            await asyncio.gather(*map(verify, hit))
            t_degraded = round(time.monotonic() - t0, 3)
            # mapping dispatches count in rt.dispatches; these are EC only
            rebuilds = bat.batches_flushed - b0
            check(rebuilds > 0, "degraded reads made no EC dispatch")
            log, downs = await marked_down(c)
            ok_downs = (len(downs) == 1
                        and downs[0].startswith("osd.%d " % victim))
            if not ok_downs:
                emit("cluster_clog", tail=[e.get("message") for e in
                                           log[-40:]])
                faulthandler.dump_traceback()
            check(ok_downs, "marked down: %s (killed osd.%d only)"
                  % (downs, victim))
            epochs = client.osdmap.epoch - epoch0
        finally:
            await c.stop()
        emit("cluster", osds=n_osds, profile="isa k%dm%d" % (k, m),
             pg_num=args.pg_num, objects=args.objects,
             object_kib=args.object_kib, in_flight=16,
             overwrites=args.overwrites, degraded_reads=len(hit),
             degraded_ec_dispatches=rebuilds,
             killed="osd.%d" % victim, marked_down=downs, map_epochs=epochs,
             timers=timers, reduced=args.reduced,
             premap_seconds=t_premap, heartbeat_mesh_seconds=t_mesh,
             pool_ready_seconds=t_ready,
             worst_ping_seconds={"pool_peering": ping_peering,
                                 "workload": ping_workload},
             write_seconds=t_write, overwrite_seconds=t_over,
             read_seconds=t_read, down_seconds=t_down,
             degraded_seconds=t_degraded, **dl.took(),
             counters=counters(rt, before))


# -- mesh: the four-chip comparison, and nothing else ----------------------

def _peaks():
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


async def phase_mesh(args, rng, rt) -> None:
    from ceph_tpu.ec.batcher import DeviceBatcher
    with deadline(args.deadline) as dl:
        check(rt.n_chips == 4, "runtime sees %d chips" % rt.n_chips)
        devices = [c.jax_device for c in rt.chips]
        check(len(set(devices)) == 4, "chips share devices: %s" % devices)
        codec, k, n, _matrix, _w = k8m3_codec()
        bat = DeviceBatcher.get()

        async def encode(nbytes, chip):
            data = rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes()
            return (await encode_checked(codec, data, chip))[1]

        def lived(need, chips):
            """Did at least `need` bytes live on each of `chips` at once?
            A dispatch keeps its staged [k, n] input and its [m, n] parity
            there; less means the program ran somewhere else."""
            peaks = _peaks()
            ok = [peaks[i] is not None and peaks[i] >= need for i in chips]
            check(all(ok), "arrays did not live on chips %s: peak bytes %s, "
                  "need %d" % (chips, peaks, need), tpu_only=True)
            return peaks

        # OSD affinity first, while the devices are empty: one encode bound
        # to each chip, below the size at which a flush is sharded
        shard_min = rt.shard_min_words
        small = (shard_min // 2) * k
        t_aff = [await encode(small, i) for i in range(4)]
        p_aff = lived(small * n // k, range(4))
        check(all(c.dispatches == 1 for c in rt.chips),
              "affinity encodes: dispatches %s"
              % [c.dispatches for c in rt.chips])
        emit("mesh_affinity", affinity_bytes=small, seconds_each=t_aff,
             devices=[str(d) for d in devices], peak_bytes=p_aff,
             **dl.took())
    # bulk mapping bound to chip 2: its tables must grow that device
    phase_crush(args, rng, rt, chip=2, before=rt.dispatches)
    with deadline(args.deadline) as dl2:
        p_map = lived(args.pgs * 3 * 4, [2])
        # one payload on chip 0 alone, then sharded over the stripe axis
        big = args.mesh_mib << 20
        check(big // k >= shard_min, "payload under device_shard_min_words")
        d0 = [c.dispatches for c in rt.chips]
        rt.shard_min_words = 1 << 62
        t_solo = [await encode(big, 0) for _ in range(2)]
        rt.shard_min_words = shard_min
        d1 = [c.dispatches for c in rt.chips]
        check(d1[0] > d0[0] and d1[1:] == d0[1:] and
              bat.sharded_flushes == 0, "solo encode left chip 0")
        t_shard = [await encode(big, 0) for _ in range(2)]
        check(bat.sharded_flushes == 2, "flush was not sharded")
        p_shard = lived(big // 4 * n // k, range(4))
        per_chip = [{"chip": c.index, "device": str(c.jax_device),
                     "dispatches": c.dispatches,
                     "host_fallbacks": c.host_fallbacks,
                     "fallback_count": c.fallback_count}
                    for c in rt.chips]
        check(all(c.dispatches > d for c, d in zip(rt.chips, d1)),
              "a chip took no shard: %s" % per_chip)
        emit("mesh_encode", mib=args.mesh_mib, solo_seconds=t_solo,
             sharded_seconds=t_shard, sharded_flushes=bat.sharded_flushes,
             peak_bytes={"after_mapping_on_chip_2": p_map,
                         "after_sharded": p_shard},
             chips=per_chip, **dl2.took(), counters=counters(rt))


# -- the run ----------------------------------------------------------------

async def run(args) -> None:
    from ceph_tpu.device.runtime import DeviceRuntime
    rng = np.random.default_rng(args.seed)
    rt = DeviceRuntime.get()
    if args.chips == 4:
        return await phase_mesh(args, rng, rt)
    await phase_ec(args, rng, rt)
    before = rt.dispatches
    phase_crush(args, rng, rt, before=before)
    before = rt.dispatches
    await phase_cluster(args, rng, rt, before)


def main() -> int:
    global REHEARSE
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--seed", type=int, default=22)
    p.add_argument("--rehearse", action="store_true",
                   help="run the phases off the chip; never reports ok")
    p.add_argument("--deadline", type=float, default=600.0,
                   help="seconds a phase may take before the run is "
                        "ended with a stack dump")
    p.add_argument("--ec-mib", type=int, default=64)
    p.add_argument("--pgs", type=int,
                   help="PGs of the bulk-mapped pool: 10,000,000, or 2^20 "
                        "with --chips 4 (a multiple of 4096 lanes, or the "
                        "descent is XLA's, not Pallas)")
    p.add_argument("--mesh-mib", type=int, default=256,
                   help="--chips 4: the payload encoded solo and sharded")
    p.add_argument("--reduced", action="store_true",
                   help="the one allowed cut of the cluster phase: "
                        "k4m2 on 7 OSDs instead of k8m3 on 12")
    p.add_argument("--pg-num", type=int, default=256)
    p.add_argument("--objects", type=int, default=256)
    p.add_argument("--object-kib", type=int, default=4096)
    p.add_argument("--overwrites", type=int, default=64)
    p.add_argument("--degraded", type=int, default=32)
    args = p.parse_args()
    REHEARSE = args.rehearse
    full = 1 << 20 if args.chips == 4 else 10_000_000
    args.pgs = args.pgs or full
    args.pgs_cut = (None if args.pgs == full
                    else "pg_num cut from %d by --pgs" % full)
    # (k, m, OSDs): the rehearsal is the size the tests use
    args.profile = ((2, 1, 3) if REHEARSE else (4, 2, 7) if args.reduced
                    else (8, 3, 12))
    args.reduced = ("k%dm%d on %d OSDs" % args.profile
                    if args.profile != (8, 3, 12) else None)
    set_ = [v for v in KILL_SWITCHES if os.environ.get(v) is not None]
    if set_ and not REHEARSE:
        print("chip_smoke: refusing to start with %s set" % ", ".join(set_),
              file=sys.stderr)
        return 2

    import jax
    import ceph_tpu  # noqa: F401  (turns x64 on before any computation)
    from ceph_tpu.device import mesh
    from ceph_tpu.utils.jaxenv import enable_compile_cache
    cache_dir = enable_compile_cache()

    def count_cache(event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            CACHE["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            CACHE["hits"] += 1

    jax.monitoring.register_event_listener(count_cache)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    emit("device", jax=jax.__version__, **dev, mesh=mesh.describe(),
         compile_cache=cache_dir, rehearsal=REHEARSE)
    on_chip = dev["platform"] == "tpu" and dev["count"] == args.chips
    if not on_chip and not REHEARSE:
        print("chip_smoke: need %d TPU chip(s), JAX reports %s"
              % (args.chips, dev), file=sys.stderr)
        print(json.dumps({"ok": False, "device": dev}))
        return 1
    asyncio.run(run(args))
    print(json.dumps({"ok": on_chip, "device": dev}))
    return 0 if on_chip else 1


if __name__ == "__main__":
    sys.exit(main())
