"""`rados bench seq` on a LocalCluster: the pool is filled, then a fixed
number of whole-object reads stay in flight for the whole window, each
compared with the bytes written, as upstream's `seq_read_bench` does.

One driver for both kinds of read run.  Where the configuration has a
`failure`, the set-up ends with upstream's maintenance procedure (`ceph
osd set noout`, stop one OSD): the victim stays in, CRUSH keeps its
position, nothing backfills, and every read of an object whose data
shard lived there is rebuilt from the survivors on the device, for the
whole window.  Without one the same reads run on the healthy pool: no
decode, no dispatch, no device operation in the window (which is why
the benchmark can hold no cell of that kind with this mix; the rehearsal
runs one); the degraded kind less the healthy one is what reconstruction
costs a reader.

Set-up keeps rados_bench's order, and every compile off the serving
cluster's loop: payloads; boot, learn the map (and that the program
knows `noout` and counts reconstructed reads: a program that does not
ends the run here, non-zero, with no result), stop; map the pool-to-be;
warm the coding matrix's programs and, for the degraded cell, every
program a lost data position's reconstruction can reach; boot, create
the pool, wait clean; write the objects; set the flag, stop the victim,
wait until the map shows it down and every PG is active again; then the
reads run on, and the window opens once `warm_ops` of them are answered.

Objects are read in number order from a seeded start and the sequence
wraps (upstream's ends at the last object; a 30 s window needs more
reads than a set-up of bearable length can write, and MemStore caches
nothing, so a second pass costs what the first did).
"""

import asyncio
import copy
import inspect
import itertools
import statistics
import sys
import time

import numpy as np

from ..harness import program_spans, trace
from ..harness.stats import pctl
from ..reference.rados_payload import Payloads
from .rados_bench import (END_TIMEOUT_S, LATENCY_QUANTILES, TICK_S, counters,
                          ec_buckets, marked_down, pool_clean, quiet,
                          shipped_conf)


def verify_span():
    """Around the harness's own work inside a reader's coroutine, on the
    loop's thread: the expected object built from the ring and compared
    with what the read returned (upstream's bencher does the same in the
    client).  Named under the prefix the span readers keep, so that
    `loop_span_cover_pct` counts it as named time, `loop_ms_per_op.harness`
    reads it, and what stays uncovered is the receive path alone; it
    falls under no layer of the program."""
    import jax
    return jax.profiler.TraceAnnotation(
        program_spans.PREFIX + "bench.verify")


def read_counters(c) -> dict:
    """What the live OSDs' EC backends counted, summed."""
    return {"reconstructed_reads": sum(o.ec.reconstructed_reads
                                       for o in c.live_osds),
            "reconstructed_payload_bytes": sum(
                o.ec.reconstructed_read_bytes for o in c.live_osds),
            "sub_read_bytes": sum(o.ec.sub_read_bytes for o in c.live_osds)}


def reconstruct_matrices(codec, erased: int) -> list:
    """The matrix of every reconstruction a whole-object read can need
    when `erased` (here 1) data position's OSD is stopped and every other
    OSD answers: the read plans the k lowest surviving positions, and
    the batcher keys its queues and programs by the rows that rebuild
    the lost one from them."""
    from ceph_tpu.ec.batcher import reconstruct_matrix
    if erased != 1:
        raise SystemExit("benchmark: the driver warms one lost position")
    matrix, w = codec._device_matrix()
    k, n = codec.get_data_chunk_count(), codec.get_chunk_count()
    out = []
    for lost in range(k):
        have = tuple(sorted(codec.minimum_to_decode(
            set(range(k)), set(range(n)) - {lost})))
        rows, _chosen = reconstruct_matrix(k, w, matrix, (lost,), have)
        out.append((lost, have, rows))
    return out


async def _run(s) -> None:
    from ceph_tpu.client.rados import RadosError
    from ceph_tpu.device.runtime import DeviceRuntime
    from ceph_tpu.ec.plugin import ErasureCodePluginRegistry
    from ceph_tpu.osd.osdmap import POOL_TYPE_ERASURE, PGPool
    from ceph_tpu.parallel.mapping import OSDMapMapping
    from ceph_tpu.testing.cluster import LocalCluster
    from ceph_tpu.utils.backoff import wait_for
    cfg, mix = s.config, s.mix
    prof, pool, failure = cfg["profile"], cfg["pool"], cfg.get("failure")
    k, m, n_osds = prof["k"], prof["m"], cfg["osds"]
    size, depth, n_objects = (mix["object_bytes"], mix["in_flight"],
                              mix["prefill_objects"])
    if failure and (failure["flags"] != ["noout"]
                    or failure["osds_down"] != 1):
        raise SystemExit("benchmark: the driver stops one OSD under noout")
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

    async def can_run(c) -> str | None:
        """Why this program cannot run the cell, or None.  Asked of the
        cluster that only learns the map, seconds after the start."""
        if not hasattr(c.osds[0].ec, "reconstructed_reads"):
            return "its EC backend does not count reconstructed reads"
        if failure:
            try:
                await c.client.mon_command("osd set", key="noout")
            except RadosError as e:
                return "the monitor refuses `osd set noout`: %s" % e
        return None

    async def serve():
        """rados_bench's: the cluster that serves, its pool clean; a
        cluster that lost an OSD while it peered is not measured."""
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
        await settled(c, "the pool peered", 0)
        return c

    async def settled(c, when: str, may_be_down: int) -> None:
        """Every PG active with nothing missing, no program still being
        lowered, and no OSD marked down beyond those the set-up stopped;
        else the run ends without a result."""
        memo = {}
        try:
            await wait_for(lambda: pool_clean(c, pid, rt, memo), 120,
                           what="every PG active after " + when)
            await quiet(s)
            down = await marked_down(c)
        except BaseException:
            await c.stop()
            raise
        if len(down) > may_be_down:
            await c.stop()
            raise SystemExit("benchmark: OSDs were marked down while %s: "
                             "%s" % (when, down[:3]))

    with trace.span("setup"):
        t = time.monotonic()
        payloads = Payloads(s.seed, size, mix["ring_buffers"])
        c = await boot()
        ahead = copy.deepcopy(c.client.osdmap)
        why_not = await can_run(c)
        await c.stop()
        if why_not:
            print("benchmark: this program cannot run %s: %s"
                  % (s.cell["name"], why_not), file=sys.stderr)
            raise SystemExit(3)
        t = lap("boot_learn_s", t)
        inc = ahead.new_incremental()
        pid = max(ahead.pool_max, 0) + 1
        inc.new_pools[pid] = PGPool(
            id=pid, name=pool["name"], type=POOL_TYPE_ERASURE, size=k + m,
            min_size=k, pg_num=pool["pg_num"], crush_rule=1,
            erasure_code_profile=pname)
        ahead.apply_incremental(inc)
        placed = OSDMapMapping(ahead, runtime=rt)
        t = lap("premap_s", t)

        # -- the victim, from the map alone: the same for every seed,
        # because every seed writes the same names
        def data_osds(n: int) -> list:
            pg = ahead.pools[pid].raw_pg_to_pg(
                ahead.object_locator_to_pg(payloads.name(n), pid))
            return placed.get(pg)[2][:k]

        holders = [data_osds(n) for n in range(n_objects)]
        victim = max(range(n_osds),
                     key=lambda o: sum(o in h for h in holders))
        s.facts["objects_on_victim"] = (
            sum(victim in h for h in holders) if failure else 0)

        # -- programs: the coding matrix for the prefill, as rados_bench
        # warms it; then, with one OSD to stop, every bucket each lost
        # data position's reconstruction can reach when 1..in_flight
        # stripes share a dispatch
        codec = ErasureCodePluginRegistry.instance().factory(
            prof["plugin"], {key: str(v) for key, v in prof.items()
                             if key not in ("plugin",
                                            "crush-failure-domain")})
        matrix, w = codec._device_matrix()
        buckets = ec_buckets(rt, conf, codec.get_chunk_size(size)
                             // max(1, w // 8), depth)
        default = inspect.signature(
            rt.warmup_ec).parameters["buckets"].default
        await rt.warmup_ec(matrix, w, buckets=tuple(sorted(
            set(default) | set(buckets))))
        t = lap("ec_warm_s", t)
        if failure:
            shards = await codec.encode_async(
                set(range(k + m)), payloads.data(0))
            lowered = s.lowered
            family = reconstruct_matrices(codec, failure["erased"])
            # the batcher keys its queue and its programs by the rows:
            # lost positions whose rows are equal share both (isa's
            # first parity row is all ones, so with one data shard gone
            # every position is rebuilt by the same XOR of survivors)
            distinct = {tuple(map(tuple, rows)) for _l, _h, rows in family}
            for lost, have, rows in family:
                await rt.warmup_ec(rows, w, buckets=buckets)
                # and once through the batcher, which leases its ladder
                await codec.decode_async(
                    {lost}, {i: shards[i] for i in have})
            s.facts.update(
                reconstruct_positions=len(family),
                reconstruct_matrices=len(distinct),
                reconstruct_buckets=",".join(map(str, buckets)),
                reconstruct_programs=len(distinct) * len(buckets),
                reconstruct_programs_lowered=s.lowered - lowered)
            t = lap("reconstruct_warm_s", t)
        c = await serve()
        t = lap("serve_s", t)
    try:
        io = c.client.io_ctx(pool["name"])
        with trace.span("prefill"):
            todo = iter(range(n_objects))

            async def writer():
                for n in todo:
                    await asyncio.wait_for(
                        io.write_full(payloads.name(n), payloads.data(n)),
                        mix["op_timeout_s"])

            await asyncio.gather(*[writer() for _ in range(depth)])
            t = lap("prefill_s", t)
        if failure:
            with trace.span("fail"):
                await c.client.mon_command("osd set", key="noout")
                await c.kill_osd(victim)
                await c.wait_osd_down(victim, timeout=60)
                await settled(c, "the victim was stopped", 1)
                t = lap("victim_down_s", t)
        s.facts["victim"] = victim if failure else None

        # -- the traffic: `depth` readers, each asks for its next object
        # when the last is answered and compared
        start = int(np.random.default_rng([s.seed, 2]).integers(n_objects))
        ops, number, stopping = [], itertools.count(start), False

        async def client_loop():
            while not stopping:
                n = next(number) % n_objects
                t_submit = time.monotonic()
                try:
                    got = await asyncio.wait_for(
                        io.read(payloads.name(n)), mix["op_timeout_s"])
                except Exception:           # a failed op is counted
                    ok, same = False, False
                else:
                    with verify_span():
                        ok, same = True, got == payloads.data(n)
                ops.append((n, t_submit, time.monotonic(), ok, same))

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
                           what="%d warm reads" % mix["warm_ops"])
            await quiet(s)
        lap("warm_ops_s", t)
        s.facts.update(took)
        before = {**counters(rt), **read_counters(c)}
        t0 = s.open_window()
        await asyncio.sleep(s.seconds)
        t1 = t0 + s.close_window()
        stopping = True
        after = {**counters(rt), **read_counters(c)}
        om = c.leader().osdmap
        victim_in_and_down = int(om.is_in(victim) and not om.is_up(victim))
        with trace.span("drain"):     # each op ends or times out
            await asyncio.wait_for(asyncio.gather(*clients),
                                   mix["op_timeout_s"] + 30)
        tick.cancel()
        s.read_memory_peak()

        acked = [o for o in ops if o[3] and t0 <= o[2] <= t1]
        sent = [o for o in ops if t0 <= o[1] <= t1]
        lat = [o[2] - o[1] for o in sent if o[3]]
        # upstream verifies every read: so does the window, and what it
        # left in flight
        verified = [o for o in ops if o[3] and o[2] >= t0]
        s.attempted = len(sent)
        s.failed = sum(1 for o in sent if not o[3])
        s.end_to_end["ops_per_s"] = len(acked) / (t1 - t0)
        for q in LATENCY_QUANTILES:     # BENCHMARK.json names which it holds
            s.end_to_end["lat_p%d_ms" % q] = 1e3 * (pctl(lat, q / 100) or 0.0)
        s.end_to_end.update(
            lat_mean_ms=1e3 * statistics.fmean(lat or [0.0]),
            lat_max_ms=1e3 * max(lat, default=0.0))
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

        s.compare("read_mismatches",
                  sum(1 for o in verified if not o[4]), 0)
        s.compare("reads_compared", len(verified), 1, ">=")
        s.compare("host_fallbacks", after["host_fallbacks"], 0)
        try:        # the mon's own log: whom it marked down, ever
            down = len(await asyncio.wait_for(marked_down(c),
                                              END_TIMEOUT_S))
        except Exception as e:
            print("benchmark: the mon's log could not be read: %r" % e,
                  file=sys.stderr)
            down = None
        if failure:
            s.compare("reconstructed_reads", s.facts["reconstructed_reads"],
                      (len(acked) + 1) // 2, ">=")
            s.compare("ec_dispatches_in_window", s.facts["ec_dispatches"],
                      1, ">=")
            s.compare("victim_in_and_down", victim_in_and_down, 1, ">=")
            s.compare("osds_marked_down", down, 1)
        else:
            s.compare("reconstructed_reads",
                      s.facts["reconstructed_reads"], 0)
            s.compare("osds_marked_down", down, 0)
    finally:    # a cluster that flaps may never stop: the result counts
        try:
            await asyncio.wait_for(c.stop(), END_TIMEOUT_S)
        except TimeoutError:
            print("benchmark: the cluster did not stop", file=sys.stderr)


def run(s) -> None:
    asyncio.run(_run(s))
