"""`rbd bench` on an image whose data pool is erasure-coded with
overwrites: closed loop, `io_threads` I/Os of `io_size` bytes in flight at
seeded random aligned offsets of a fully written image, then the
correctness pass.

The deployment is upstream's own recipe (doc/rados/operations/
erasure-code.rst, "Erasure Coding with Overwrites"):

    ceph osd pool set ec_pool allow_ec_overwrites true
    rbd create --size 1G --data-pool ec_pool replicated_pool/image_name

and the traffic `rbd bench --io-type write --io-pattern rand` (doc/man/8/
rbd.rst) through `ceph_tpu/services/rbd.py`, as librbd's users call it.
The mix takes `--io-type` (`write`; `read` and `readwrite` with
`rw_mix_read` per cent of reads, which no cell uses yet), `io_size`,
`io_threads` and `io_pattern` (`rand` only).

Set-up keeps rados_bench's order, every compile off the serving cluster's
loop: the reference image; boot, learn the map (and whether the program
counts delta writes at all: one that does not ends the run here, non-zero,
with no result), stop; map both pools-to-be; warm the coding matrix at
the sizes the prefill's whole-object writes and the 4 KiB deltas reach, and
the reconstructions the degraded reads need; boot, create the data pool,
set `allow_ec_overwrites` (a monitor that refuses, or does not answer in
`FLAG_DEADLINE_S`, ends the run the same way), create the metadata pool,
wait clean; create and open the image; write all of it through
`Image.write` (a preconditioned disk: `rbd bench --io-type write --io-size
4M --io-pattern seq`); then the cell's own traffic runs on, and the window
opens once `warm_ops` of it are acknowledged.

Correctness, after the window and the drain, every limit 0 or "at least
1": the whole image read back and held to benchmark/reference/
rbd_image.py; for `parity_objects` objects overwritten in the window, all
k+m shards taken from the OSDs' stores, the parity shards held to
benchmark/reference/rs_isa.py's encode of the data shards (a healthy read
never reads parity) and each shard's stored crc to the crc of its bytes;
the OSD with a data shard of most of those objects killed and
`degraded_objects` of them read through device reconstruct; and the
program's own counts: every overwrite of the window through the
parity-delta path on the device, none through the whole-object fallback or
a host fallback, nobody marked down, no program new.
"""

import asyncio
import copy
import inspect
import itertools
import statistics
import sys
import time
import zlib

import numpy as np

from ..harness import trace
from ..harness.stats import pctl
from ..reference import rs_isa
from ..reference.rbd_image import Image as ReferenceImage
from .rados_bench import (END_TIMEOUT_S, LATENCY_QUANTILES, TICK_S, counters,
                          ec_buckets, marked_down, pool_clean, quiet,
                          shipped_conf, warm_ec)

FLAG_DEADLINE_S = 20    # for the monitor's answer to allow_ec_overwrites


def delta_counters(c) -> dict:
    """What the OSDs' EC backends counted, summed (a stopped OSD's
    counts stay)."""
    return {name: sum(getattr(o.ec, name) for o in c.osds)
            for name in ("delta_writes", "delta_write_bytes",
                         "rmw_fallbacks", "sub_read_bytes")}


def stored_shards(c, pid: int, oid: str) -> dict:
    """position -> (bytes, stored crcs) of `oid` as the live OSDs' stores
    hold it, read beside the program and not through it."""
    from ceph_tpu.osd.ecbackend import HINFO_XATTR, SHARD_XATTR
    from ceph_tpu.store.objectstore import hobject_t
    om = c.client.osdmap
    pgid = om.pools[pid].raw_pg_to_pg(om.object_locator_to_pg(oid, pid))
    ho, out = hobject_t(oid), {}
    for o in c.live_osds:
        pg = o.pgs.get(pgid)
        if pg is None or not o.store.exists(pg.cid, ho):
            continue
        attrs = o.store.getattrs(pg.cid, ho)
        out[int(attrs[SHARD_XATTR])] = (
            bytes(o.store.read(pg.cid, ho)),
            [int(x) for x in attrs.get(HINFO_XATTR, b"").split(b",") if x])
    return out


async def _run(s) -> None:
    from ceph_tpu.client.rados import RadosError
    from ceph_tpu.client.striper import FileLayout
    from ceph_tpu.device.runtime import DeviceRuntime
    from ceph_tpu.ec.plugin import ErasureCodePluginRegistry
    from ceph_tpu.osd.osdmap import (POOL_TYPE_ERASURE, POOL_TYPE_REPLICATED,
                                     PGPool)
    from ceph_tpu.parallel.mapping import OSDMapMapping
    from ceph_tpu.services.rbd import RBD
    from ceph_tpu.testing.cluster import LocalCluster
    from ceph_tpu.utils.backoff import wait_for
    cfg, mix = s.config, s.mix
    prof, image = cfg["profile"], cfg["image"]
    data_pool, meta_pool = cfg["pools"]["data"], cfg["pools"]["metadata"]
    k, m, n_osds = prof["k"], prof["m"], cfg["osds"]
    io_size, depth = mix["io_size"], mix["io_threads"]
    osz = image["object_size"]
    io_type = mix["io_type"]
    if mix["io_pattern"] != "rand" or \
            io_type not in ("write", "read", "readwrite") or \
            not data_pool.get("allow_ec_overwrites"):
        raise SystemExit("benchmark: the driver runs rbd bench "
                         "--io-pattern rand on an EC data pool with "
                         "overwrites")
    read_pct = {"write": 0, "read": 100}.get(io_type, mix.get("rw_mix_read"))
    rt = DeviceRuntime.get()
    conf = shipped_conf(cfg)
    pname = "k%dm%d" % (k, m)
    loop = asyncio.get_running_loop()
    took = {}

    def lap(what: str, since: float) -> float:
        took[what] = round(time.monotonic() - since, 3)
        return time.monotonic()

    def cannot_run(why: str):
        print("benchmark: this program cannot run %s: %s"
              % (s.cell["name"], why), file=sys.stderr)
        return SystemExit(3)

    async def boot():
        c = await LocalCluster(n_osds=n_osds, conf=conf).start()
        await c.client.mon_command(
            "osd erasure-code-profile set", name=pname,
            profile={key: str(v) for key, v in prof.items()})
        await c.client.wait_for_epoch(c.leader().osdmap.epoch)
        return c

    async def serve():
        """rados_bench's: the cluster that serves, both pools clean; the
        flag is set before anything else is asked of the data pool, and a
        cluster that lost an OSD while it peered is not measured."""
        c = await boot()
        try:
            if c.client.osdmap.crush.to_dict() != ahead.crush.to_dict():
                raise SystemExit("benchmark: the mon built another crush "
                                 "map than the one mapped ahead")
            await wait_for(lambda: all(len(o.network.peers) == n_osds - 1
                                       for o in c.osds), 60,
                           what="heartbeat mesh")
            got = await c.create_pool(data_pool["name"],
                                      pg_num=data_pool["pg_num"],
                                      pool_type=data_pool["type"],
                                      erasure_code_profile=pname)
            if got != pid:
                raise SystemExit("benchmark: pool id %d, mapped ahead as "
                                 "%d" % (got, pid))
            try:
                await asyncio.wait_for(c.client.mon_command(
                    "osd pool set", pool=data_pool["name"],
                    var="allow_ec_overwrites", val="true"),
                    FLAG_DEADLINE_S)
            except (RadosError, TimeoutError) as e:
                raise cannot_run("the monitor does not set "
                                 "allow_ec_overwrites: %r" % e) from None
            got = await c.create_pool(meta_pool["name"],
                                      pg_num=meta_pool["pg_num"],
                                      size=meta_pool["size"])
            if got != pid + 1:
                raise SystemExit("benchmark: pool id %d, mapped ahead as "
                                 "%d" % (got, pid + 1))
            memo, memo2 = {}, {}
            await wait_for(lambda: pool_clean(c, pid, rt, memo)
                           and pool_clean(c, pid + 1, rt, memo2), 120,
                           what="both pools active+clean")
            await quiet(s)
            down = await marked_down(c)
        except BaseException:
            await c.stop()
            raise
        if down:
            await c.stop()
            raise SystemExit("benchmark: OSDs were marked down while the "
                             "pools peered: %s" % down[:3])
        return c

    with trace.span("setup"):
        t = time.monotonic()
        ref = ReferenceImage(s.seed, image["size"], osz, io_size,
                             mix["ring_buffers"])
        c = await boot()
        ahead = copy.deepcopy(c.client.osdmap)
        counts = hasattr(c.osds[0].ec, "delta_writes")
        await c.stop()
        if not counts:
            raise cannot_run("its EC backend does not count delta writes")
        t = lap("boot_learn_s", t)
        inc = ahead.new_incremental()
        pid = max(ahead.pool_max, 0) + 1
        inc.new_pools[pid] = PGPool(
            id=pid, name=data_pool["name"], type=POOL_TYPE_ERASURE,
            size=k + m, min_size=k, pg_num=data_pool["pg_num"],
            crush_rule=1, erasure_code_profile=pname)
        inc.new_pools[pid + 1] = PGPool(
            id=pid + 1, name=meta_pool["name"], type=POOL_TYPE_REPLICATED,
            size=meta_pool["size"], pg_num=meta_pool["pg_num"])
        ahead.apply_incremental(inc)
        OSDMapMapping(ahead, runtime=rt)
        t = lap("premap_s", t)
        # the prefill's whole 4 MiB objects and the degraded reads'
        # reconstructions, as rados_bench warms them; then the buckets
        # 1..io_threads deltas of io_size bytes reach when they share a
        # dispatch (a delta is a k x io_size array on the coding matrix)
        await warm_ec(rt, conf, prof, osz, depth, ref.object(0))
        codec = ErasureCodePluginRegistry.instance().factory(
            prof["plugin"], {key: str(v) for key, v in prof.items()
                             if key not in ("plugin",
                                            "crush-failure-domain")})
        matrix, w = codec._device_matrix()
        default = inspect.signature(
            rt.warmup_ec).parameters["buckets"].default
        await rt.warmup_ec(matrix, w, buckets=tuple(sorted(
            set(default) | set(ec_buckets(rt, conf,
                                          io_size // max(1, w // 8),
                                          depth)))))
        t = lap("ec_warm_s", t)
        c = await serve()
        t = lap("serve_s", t)
    try:
        rbd = RBD(c.client.io_ctx(meta_pool["name"]))
        await rbd.create(image["name"], image["size"],
                         FileLayout(stripe_unit=osz, stripe_count=1,
                                    object_size=osz),
                         data_pool=data_pool["name"])
        img = await rbd.open(image["name"])
        with trace.span("prefill"):
            todo = iter(range(ref.objects))

            async def filler():
                for n in todo:
                    await asyncio.wait_for(
                        img.write(n * osz, ref.object(n)),
                        mix["op_timeout_s"])

            await asyncio.gather(*[filler() for _ in range(depth)])
            t = lap("prefill_s", t)
        filled = delta_counters(c)

        # -- the traffic: `depth` threads, each submits its next I/O when
        # the last is acknowledged; it runs from warm-up through the window
        ops, number, stopping = [], itertools.count(), False
        reads = np.random.default_rng([s.seed, 5])
        read_wrong = 0

        async def thread():
            nonlocal read_wrong
            while not stopping:
                i = next(number)
                b = ref.block(i)
                is_read = read_pct and reads.integers(100) < read_pct
                t_submit = time.monotonic()
                try:
                    if is_read:
                        got = await asyncio.wait_for(
                            img.read(b * io_size, io_size),
                            mix["op_timeout_s"])
                        read_wrong += got not in ref.readable(b)
                    else:
                        ref.submitted(i)
                        await asyncio.wait_for(
                            img.write(b * io_size, ref.payload(i)),
                            mix["op_timeout_s"])
                        ref.acknowledged(i)
                    ok = True
                except Exception:           # a failed op is counted
                    ok = False
                ops.append((i, t_submit, time.monotonic(), ok, is_read))

        lag = []

        async def ticker():
            due = loop.time() + TICK_S
            while True:
                await asyncio.sleep(max(0.0, due - loop.time()))
                lag.append((time.monotonic(), max(0.0, loop.time() - due)))
                due = max(due + TICK_S, loop.time())

        threads = [asyncio.ensure_future(thread()) for _ in range(depth)]
        tick = asyncio.ensure_future(ticker())
        with trace.span("warm"):
            await wait_for(lambda: len(ops) >= mix["warm_ops"], 120,
                           what="%d warm ops" % mix["warm_ops"])
            await quiet(s)
        lap("warm_ops_s", t)
        s.facts.update(took)
        before = {**counters(rt), **delta_counters(c)}
        t0 = s.open_window()
        await asyncio.sleep(s.seconds)
        t1 = t0 + s.close_window()
        stopping = True
        after = {**counters(rt), **delta_counters(c)}
        with trace.span("drain"):     # each op ends or times out
            await asyncio.wait_for(asyncio.gather(*threads),
                                   mix["op_timeout_s"] + 30)
        tick.cancel()
        s.read_memory_peak()
        drained = delta_counters(c)

        acked = [o for o in ops if o[3] and t0 <= o[2] <= t1]
        sent = [o for o in ops if t0 <= o[1] <= t1]
        lat = [o[2] - o[1] for o in sent if o[3]]
        s.attempted = len(sent)
        s.failed = sum(1 for o in sent if not o[3])
        s.end_to_end["ops_per_s"] = len(acked) / (t1 - t0)
        for q in LATENCY_QUANTILES:     # BENCHMARK.json names which it holds
            s.end_to_end["lat_p%d_ms" % q] = 1e3 * (pctl(lat, q / 100) or 0.0)
        s.end_to_end.update(
            lat_mean_ms=1e3 * statistics.fmean(lat or [0.0]),
            lat_max_ms=1e3 * max(lat, default=0.0))
        third = (t1 - t0) / 3
        writes_acked = sum(1 for o in acked if not o[4])
        s.facts.update(
            ops_completed=len(acked), writes_completed=writes_acked,
            payload_bytes=writes_acked * io_size,
            latency_samples=len(lat), latency_s=lat,
            latency_ms={key: v for key, v in s.end_to_end.items()
                        if key.startswith("lat_")},
            acked_by_third={str(i): sum(
                1 for o in acked
                if i == min(2, int((o[2] - t0) / third))) for i in range(3)},
            loop_lag_s=[late for at, late in lag if t0 <= at <= t1],
            loop_lag_max_ms=1e3 * max(
                (late for at, late in lag if t0 <= at <= t1), default=0.0),
            **{key: after[key] - before[key] for key in after})

        with trace.span("correctness"):
            in_window = sorted({ref.block(o[0]) * io_size // osz
                                for o in acked if not o[4]})
            # every write since the prefill has ended by now, so what the
            # OSDs counted since then is held to what was acknowledged
            # since then, warm-up and drain included: a window's edges
            # cut ops between their commit and their ack
            served = {key: drained[key] - filled[key] for key in drained}
            served["writes_acked"] = sum(1 for o in ops
                                         if o[3] and not o[4])
            await correctness(s, c, rt, img, ref, pid, k, m, in_window,
                              served, read_wrong)
    finally:    # a cluster that flaps may never stop: the result counts
        try:
            await asyncio.wait_for(c.stop(), END_TIMEOUT_S)
        except TimeoutError:
            print("benchmark: the cluster did not stop", file=sys.stderr)


async def correctness(s, c, rt, img, ref, pid, k, m, in_window: list,
                      served: dict, read_wrong: int) -> None:
    """`in_window`: the objects some write acknowledged inside the window
    overwrote; `served`: what the OSDs counted from the prefill's end to
    the drain's, and the writes acknowledged meanwhile."""
    from ceph_tpu.parallel.mapping import OSDMapMapping
    mix, osz = s.mix, ref.object_bytes
    try:        # the mon's own log, before this pass stops anybody
        down = len(await asyncio.wait_for(marked_down(c), END_TIMEOUT_S))
    except Exception as e:
        print("benchmark: the mon's log could not be read: %r" % e,
              file=sys.stderr)
        down = None

    async def bad_blocks(objects: list, in_flight: int) -> dict:
        """object -> blocks of it that hold what they may not; an answer
        that never comes counts every block of the object."""
        sem, out = asyncio.Semaphore(in_flight), {}

        async def one(n):
            async with sem:
                try:
                    got = await asyncio.wait_for(
                        img.read(n * osz, osz), mix["op_timeout_s"])
                except Exception:
                    got = b""
            out[n] = ref.mismatched_blocks(n, got)

        await asyncio.gather(*map(one, objects))
        return out

    # (a) the whole image
    wrong = await bad_blocks(list(range(ref.objects)), mix["io_threads"])
    s.compare("image_mismatched_blocks", sum(wrong.values()), 0)
    s.facts["image_blocks"] = ref.blocks
    if mix["io_type"] != "read":
        s.compare("blocks_overwritten", len(ref.overwritten()), 1, ">=")
    if mix["io_type"] != "write":
        s.compare("read_mismatches", read_wrong, 0)

    # (b) the shards in the stores: parity and stored crcs
    rng = np.random.default_rng([s.seed, 1])
    if mix["io_type"] == "read":    # nothing was overwritten: any object
        in_window = list(range(ref.objects))
    sample = [int(n) for n in rng.choice(
        in_window, min(mix["parity_objects"], len(in_window)),
        replace=False)] if in_window else []
    parity_bad = crc_bad = 0
    for n in sample:
        shards = stored_shards(c, pid, img._data_name(n))
        have = [shards.get(j, (b"", []))[0] for j in range(k + m)]
        if len({len(x) for x in have}) != 1 or not have[0]:
            parity_bad, crc_bad = parity_bad + m, crc_bad + k + m
            continue
        want = rs_isa.encode(have[:k], m)
        parity_bad += sum(have[k + i] != want[i] for i in range(m))
        crc_bad += sum(
            shards[j][1][j:j + 1] != [zlib.crc32(have[j]) & 0xFFFFFFFF]
            for j in range(k + m))
        await asyncio.sleep(0)      # 12 MiB of table look-ups an object
    s.compare("parity_mismatched_shards", parity_bad, 0)
    s.compare("hinfo_mismatched_shards", crc_bad, 0)
    s.compare("shards_compared", len(sample) * (k + m), 1, ">=")

    # (c) one OSD lost: the device rebuilds what it held
    placed = OSDMapMapping(c.client.osdmap, runtime=rt)

    def data_osds(n):
        om = c.client.osdmap
        pg = om.pools[pid].raw_pg_to_pg(
            om.object_locator_to_pg(img._data_name(n), pid))
        return placed.get(pg)[2][:k]

    holders = {n: data_osds(n) for n in sample}
    victim = max(range(len(c.osds)),
                 key=lambda o: sum(o in h for h in holders.values()))
    hit = [n for n, h in holders.items() if victim in h]
    hit = hit[:mix["degraded_objects"]]
    before = counters(rt)
    await c.kill_osd(victim)
    try:
        await c.wait_osd_down(victim, timeout=60)
    except TimeoutError:
        hit = []        # never seen down: no degraded read was made
    wrong = await bad_blocks(hit, mix["degraded_in_flight"])
    after = counters(rt)
    s.compare("degraded_mismatches",
              sum(1 for bad in wrong.values() if bad), 0)
    s.compare("degraded_objects_read", len(hit), 1, ">=")
    s.compare("degraded_ec_dispatches",
              after["ec_dispatches"] - before["ec_dispatches"], 1, ">=")

    # (d) what served the window
    if mix["io_type"] != "read":
        s.compare("delta_writes", served["delta_writes"],
                  served["writes_acked"], ">=")
        s.compare("ec_dispatches_in_window", s.facts["ec_dispatches"], 1,
                  ">=")
    s.compare("rmw_fallbacks", served["rmw_fallbacks"], 0)
    s.compare("host_fallbacks", after["host_fallbacks"], 0)
    s.compare("osds_marked_down_in_window", down, 0)
    s.facts["killed_osd"] = victim


def run(s) -> None:
    asyncio.run(_run(s))
