"""`rados bench write` on a LocalCluster: closed loop, a fixed number of
write_full in flight for the whole window, then the correctness pass.

Set-up keeps the order chip_smoke.py's cluster phase proved on the chip
(PERF.md, bring-up), with every compile moved off the serving cluster's
loop: payloads first; boot, learn the map, stop; map the pool-to-be and
warm every EC program the cell can reach with nobody on the loop; boot;
wait for the heartbeat mesh; create the pool; wait until it is clean (a
cluster that lost an OSD meanwhile ends the run); then the cell's own traffic
runs on, and the window opens once `warm_ops` of it are acknowledged, so
the window starts in steady state.

The window drives `client.io_ctx(pool).write_full`.  Throughput is the
ops acknowledged inside the window over its length; latency is taken over
every op submitted inside the window, each waited for to its end.
"""

import asyncio
import copy
import inspect
import itertools
import statistics
import sys
import time

from ..harness import trace
from ..harness.stats import pctl
from ..reference.rados_payload import Payloads
from .program import host_fallbacks

TICK_S = 0.05       # the event loop's lateness is sampled this often
LATENCY_QUANTILES = (50, 75, 90, 95, 99)
END_TIMEOUT_S = 30  # for what follows the comparisons: the log, the stop


def counters(rt) -> dict:
    from ceph_tpu.ec.batcher import DeviceBatcher
    bat = DeviceBatcher.get()
    return {"dispatches": rt.dispatches, "compile_count": rt.compile_count,
            "ec_dispatches": bat.batches_flushed,
            "host_fallbacks": host_fallbacks(rt, bat)}


def shipped_conf(config: dict) -> dict:
    """A deployment's timers, not test pacing: every option the cluster
    harness paces for tests goes back to its shipped default, and those
    must be what the configuration states."""
    from ceph_tpu.testing.cluster import FAST_CONF
    from ceph_tpu.utils.config import DEFAULT_SCHEMA
    shipped = {o.name: o.default for o in DEFAULT_SCHEMA}
    conf = {name: shipped[name] for name in FAST_CONF if name in shipped}
    for name, want in config["timers"].items():
        if conf[name] != want:
            raise SystemExit("benchmark: %s ships as %r, the configuration "
                             "states %r" % (name, conf[name], want))
    if shipped["osd_objectstore"] != config["objectstore"]:
        raise SystemExit("benchmark: osd_objectstore ships as %r"
                         % shipped["osd_objectstore"])
    return conf


def ec_buckets(rt, conf: dict, shard_words: int, in_flight: int) -> tuple:
    """Every bucket program the batcher can reach when 1..in_flight
    stripes of this cell's size share a dispatch: the stream packs ops
    into a slot group up to its geometry cap, one op at the least."""
    cap = (rt.stream_slot_words if conf["device_dispatch_mode"] == "stream"
           else in_flight * shard_words)
    totals = {j * shard_words for j in range(1, in_flight + 1)
              if j == 1 or j * shard_words <= cap}
    return tuple(sorted({seg for n in totals
                         for _lo, seg in rt.ragged_plan(n)}))


async def quiet(s, still_s: float = 1.0, timeout: float = 300.0) -> None:
    """Wait until no program has been lowered for `still_s`: the OSDs
    warm their own EC programs in tasks nobody can await."""
    seen, since = s.lowered, time.monotonic()
    t_end = since + timeout
    while time.monotonic() - since < still_s:
        if time.monotonic() > t_end:
            raise SystemExit("benchmark: programs are still being lowered")
        await asyncio.sleep(0.1)
        if s.lowered != seen:
            seen, since = s.lowered, time.monotonic()


async def warm_ec(rt, conf: dict, prof: dict, size: int, depth: int,
                  data: bytes) -> None:
    """Every EC program the cell can reach, compiled or loaded with nobody
    else on the loop (a compile beside live daemons outlasts the heartbeat
    grace): the coding matrix at the buckets the OSDs warm at boot and at
    those this cell's sizes reach, and the k reconstructions of one lost
    data shard at the cell's shard size, for the degraded reads."""
    from ceph_tpu.ec.plugin import ErasureCodePluginRegistry
    codec = ErasureCodePluginRegistry.instance().factory(
        prof["plugin"], {key: str(v) for key, v in prof.items()
                         if key not in ("plugin", "crush-failure-domain")})
    matrix, w = codec._device_matrix()
    k, n = codec.get_data_chunk_count(), codec.get_chunk_count()
    default = inspect.signature(rt.warmup_ec).parameters["buckets"].default
    await rt.warmup_ec(matrix, w, buckets=tuple(sorted(set(default) | set(
        ec_buckets(rt, conf, codec.get_chunk_size(size) // max(1, w // 8),
                   depth)))))
    shards = await codec.encode_async(set(range(n)), data)
    for lost in range(k):
        have = set(range(n)) - {lost}
        plan = codec.minimum_to_decode({lost}, have)
        await codec.decode_async({lost}, {i: shards[i] for i in plan})


def pool_clean(c, pid: int, rt, memo: dict) -> bool:
    """LocalCluster.healthy() with the primaries taken from one bulk map
    per epoch: healthy() maps every PG with the host engine on the shared
    loop, 3 s a poll at pg_num 256 (PERF.md, bring-up)."""
    from ceph_tpu.osd.osdmap import pg_t
    from ceph_tpu.osd.pg import STATE_ACTIVE
    from ceph_tpu.parallel.mapping import OSDMapMapping
    maps = [o.osdmap for o in c.live_osds if o.osdmap is not None]
    if not maps:
        return False
    m = max(maps, key=lambda om: om.epoch)
    if pid not in m.pools:
        return False
    if memo.get("epoch") != m.epoch:
        memo["epoch"] = m.epoch
        memo["primary"] = OSDMapMapping(
            m, runtime=rt).pools[pid].acting_primary
    alive = {o.whoami: o for o in c.live_osds}
    for ps, actingp in enumerate(memo["primary"]):
        prim = alive.get(int(actingp))
        if prim is None or prim.osdmap is None \
                or prim.osdmap.epoch != m.epoch:
            return False
        pg = prim.pgs.get(pg_t(pid, ps))
        if pg is None or pg.state != STATE_ACTIVE or pg.missing \
                or any(pm for pm in pg.peer_missing.values()):
            return False
    return True


async def marked_down(c) -> list:
    log = (await c.client.mon_command("log last", n=1000))["lines"]
    return [e["message"] for e in log
            if "marked down" in e.get("message", "")]


async def _run(s) -> None:
    from ceph_tpu.device.runtime import DeviceRuntime
    from ceph_tpu.osd.osdmap import POOL_TYPE_ERASURE, PGPool
    from ceph_tpu.parallel.mapping import OSDMapMapping
    from ceph_tpu.testing.cluster import LocalCluster
    from ceph_tpu.utils.backoff import wait_for
    cfg, mix = s.config, s.mix
    prof, pool = cfg["profile"], cfg["pool"]
    k, m, n_osds = prof["k"], prof["m"], cfg["osds"]
    size, depth = mix["object_bytes"], mix["in_flight"]
    rt = DeviceRuntime.get()
    conf = shipped_conf(cfg)
    pname = "k%dm%d" % (k, m)
    loop = asyncio.get_running_loop()
    took = {}

    def lap(what: str, since: float) -> float:
        took[what] = round(time.monotonic() - since, 3)
        return time.monotonic()

    async def boot():
        c = await LocalCluster(n_osds=n_osds, conf=conf).start()
        await c.client.mon_command(
            "osd erasure-code-profile set", name=pname,
            profile={key: str(v) for key, v in prof.items()})
        await c.client.wait_for_epoch(c.leader().osdmap.epoch)
        return c

    async def serve():
        """The cluster that serves the window, its pool active and clean.
        Every daemon shares this loop, and a hold of it past the heartbeat
        grace takes every OSD down at once (ROADMAP A-first): a cluster
        that lost an OSD while it peered is not measured and not booted
        again; the run ends without a result."""
        c = await boot()
        if c.client.osdmap.crush.to_dict() != ahead.crush.to_dict():
            raise SystemExit("benchmark: the mon built another crush "
                             "map than the one mapped ahead")
        await wait_for(lambda: all(len(o.network.peers) == n_osds - 1
                                   for o in c.osds), 60,
                       what="heartbeat mesh")
        got = await c.create_pool(pool["name"], pg_num=pool["pg_num"],
                                  pool_type=pool["type"],
                                  erasure_code_profile=pname)
        if got != pid:
            raise SystemExit("benchmark: pool id %d, mapped ahead as %d"
                             % (got, pid))
        memo = {}
        try:
            await wait_for(lambda: pool_clean(c, pid, rt, memo), 120,
                           what="pool active+clean")
            await quiet(s)
            down = await marked_down(c)
        except BaseException:
            await c.stop()
            raise
        if down:
            await c.stop()
            raise SystemExit("benchmark: OSDs were marked down while the "
                             "pool peered: %s" % down[:3])
        return c

    with trace.span("setup"):
        t = time.monotonic()
        payloads = Payloads(s.seed, size, mix["ring_buffers"])
        # every daemon shares this loop, and a CRUSH compile on it
        # outlasts the heartbeat grace: learn the map, stop, map the
        # pool-to-be alone, then boot the cluster that serves
        c = await boot()
        ahead = copy.deepcopy(c.client.osdmap)
        await c.stop()
        t = lap("boot_learn_s", t)
        inc = ahead.new_incremental()
        pid = max(ahead.pool_max, 0) + 1
        inc.new_pools[pid] = PGPool(
            id=pid, name=pool["name"], type=POOL_TYPE_ERASURE, size=k + m,
            min_size=k, pg_num=pool["pg_num"], crush_rule=1,
            erasure_code_profile=pname)
        ahead.apply_incremental(inc)
        OSDMapMapping(ahead, runtime=rt)
        t = lap("premap_s", t)
        await warm_ec(rt, conf, prof, size, depth, payloads.data(0))
        t = lap("ec_warm_s", t)
        c = await serve()
        t = lap("serve_s", t)
    try:
        io = c.client.io_ctx(pool["name"])

        # -- the traffic: `depth` clients, each sends its next op when
        # the last is acknowledged; it runs from warm-up through the window
        ops, number, stopping = [], itertools.count(), False

        async def client_loop():
            while not stopping:
                n = payloads.number(next(number))
                data = payloads.data(n)
                t_submit = time.monotonic()
                try:
                    await asyncio.wait_for(
                        io.write_full(payloads.name(n), data),
                        mix["op_timeout_s"])
                    ok = True
                except Exception:           # a failed op is counted
                    ok = False
                ops.append((n, t_submit, time.monotonic(), ok))

        lag = []

        async def ticker():
            due = loop.time() + TICK_S
            while True:
                await asyncio.sleep(max(0.0, due - loop.time()))
                lag.append((time.monotonic(), max(0.0, loop.time() - due)))
                due = max(due + TICK_S, loop.time())

        clients = [asyncio.ensure_future(client_loop())
                   for _ in range(depth)]
        tick = asyncio.ensure_future(ticker())
        with trace.span("warm"):
            await wait_for(lambda: len(ops) >= mix["warm_ops"], 120,
                           what="%d warm ops" % mix["warm_ops"])
            await quiet(s)
        lap("warm_ops_s", t)
        s.facts.update(took)
        before = counters(rt)
        t0 = s.open_window()
        await asyncio.sleep(s.seconds)
        t1 = t0 + s.close_window()
        stopping = True
        after = counters(rt)
        with trace.span("drain"):     # each op ends or times out
            await asyncio.wait_for(asyncio.gather(*clients),
                                   mix["op_timeout_s"] + 30)
        tick.cancel()
        s.read_memory_peak()

        acked = [o for o in ops if o[3] and t0 <= o[2] <= t1]
        sent = [o for o in ops if t0 <= o[1] <= t1]
        lat = [o[2] - o[1] for o in sent if o[3]]
        s.attempted = len(sent)
        s.failed = sum(1 for o in sent if not o[3])
        s.end_to_end["ops_per_s"] = len(acked) / (t1 - t0)
        for q in LATENCY_QUANTILES:     # BENCHMARK.json names which it holds
            s.end_to_end["lat_p%d_ms" % q] = 1e3 * (pctl(lat, q / 100) or 0.0)
        # what `rados bench` prints beside its rate, and the mean of the
        # slowest tenth: a tail that averages many ops, not one rank
        slowest = sorted(lat)[len(lat) - max(1, len(lat) // 10):]
        s.end_to_end.update(
            lat_mean_ms=1e3 * statistics.fmean(lat or [0.0]),
            lat_stddev_ms=1e3 * statistics.pstdev(lat or [0.0]),
            lat_max_ms=1e3 * max(lat, default=0.0),
            lat_slowest10_ms=1e3 * statistics.fmean(slowest or [0.0]))
        third = (t1 - t0) / 3
        s.facts.update(
            ops_completed=len(acked), payload_bytes=len(acked) * size,
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
            await correctness(s, c, rt, io, payloads, pid, k,
                              [o[0] for o in acked])
    finally:    # a cluster that flaps may never stop: the result counts
        try:
            await asyncio.wait_for(c.stop(), END_TIMEOUT_S)
        except TimeoutError:
            print("benchmark: the cluster did not stop", file=sys.stderr)


async def correctness(s, c, rt, io, payloads, pid, k, acked: list) -> None:
    """Read back a sample of what the window acknowledged, drawn from the
    seed; lose the OSD that holds a data shard of most of the sample and
    read those objects again, which the device must reconstruct."""
    import numpy as np
    from ceph_tpu.parallel.mapping import OSDMapMapping
    mix, client = s.mix, c.client
    rng = np.random.default_rng([s.seed, 1])
    sample = [int(i) for i in rng.choice(
        acked, min(mix["verify_objects"], len(acked)), replace=False)]

    async def count_wrong(objects: list, in_flight: int) -> int:
        """Answers that never come or say the wrong thing."""
        sem = asyncio.Semaphore(in_flight)

        async def differs(i) -> bool:
            async with sem:
                try:
                    got = await asyncio.wait_for(io.read(payloads.name(i)),
                                                 mix["op_timeout_s"])
                except Exception:
                    return True
            return got != payloads.data(i)

        return sum(await asyncio.gather(*map(differs, objects)))

    wrong = await count_wrong(sample, mix["in_flight"])
    s.compare("readback_mismatches", wrong, 0)
    s.compare("objects_read_back", len(sample), 1, ">=")

    placed = OSDMapMapping(client.osdmap, runtime=rt)

    def data_osds(i):
        om = client.osdmap
        pg = om.pools[pid].raw_pg_to_pg(
            om.object_locator_to_pg(payloads.name(i), pid))
        return placed.get(pg)[2][:k]

    holders = {i: data_osds(i) for i in sample}
    victim = max(range(len(c.osds)),
                 key=lambda o: sum(o in h for h in holders.values()))
    hit = [i for i, h in holders.items() if victim in h]
    hit = hit[:mix["degraded_objects"]]
    before = counters(rt)
    await c.kill_osd(victim)
    try:
        await c.wait_osd_down(victim, timeout=60)
    except TimeoutError:
        hit = []        # never seen down: no degraded read was made
    # a burst of reconstructions on a cluster that has just lost an OSD
    # is what the two runs that lost every OSD were in (PERF.md, section
    # 7): the degraded reads go a few at a time
    wrong = await count_wrong(hit, mix["degraded_in_flight"])
    after = counters(rt)
    s.compare("degraded_mismatches", wrong, 0)
    s.compare("degraded_objects_read", len(hit), 1, ">=")
    s.compare("degraded_ec_dispatches",
              after["ec_dispatches"] - before["ec_dispatches"], 1, ">=")
    s.compare("ec_dispatches_in_window", s.facts["ec_dispatches"], 1, ">=")
    s.compare("host_fallbacks", after["host_fallbacks"], 0)
    s.facts["killed_osd"] = victim
    try:        # 1 is the OSD this pass killed; more is ROADMAP A-first
        s.facts["marked_down"] = len(
            await asyncio.wait_for(marked_down(c), END_TIMEOUT_S))
    except Exception as e:
        s.facts["marked_down"] = repr(e)


def run(s) -> None:
    asyncio.run(_run(s))
