"""Program spans on the profiler's clock (ceph_tpu/trace/span.py).

The table SPANS and the emit sites are held to each other; no span body
may suspend (one thread runs every coroutine, so a span across an
``await`` would nest with other requests' spans); with no profiler a
span costs nothing visible; under a profiler session on the CPU backend
one EC write leaves every rados-path span, properly nested per thread,
a read with a data holder stopped under noout leaves the read path's,
and 4 KiB overwrites of an RBD image on an EC data pool leave the
parity-delta path's.
"""

import ast
import asyncio
import glob
import os

import pytest

from ceph_tpu.trace import span as spanmod
from ceph_tpu.trace.span import PREFIX, SPANS, mark, span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what one acknowledged k2m1 write_full must leave; the rest of SPANS is
# the bulk remap's, a timer's (heartbeat, advance_pgs, gc) or a fault's
WRITE_PATH = (
    "client.calc_target", "client.submit", "client.send_op",
    "client.handle_reply", "msgr.encode", "msgr.write", "msgr.recv",
    "msgr.read_decode", "msgr.dispatch", "osd.dequeue", "osd.handle_op",
    "osd.ec.op", "osd.ec.submit", "osd.ec.sub_write", "osd.ec.sub_reply",
    "ec.prepare", "ec.stage", "ec.dispatch", "ec.deliver", "ec.collect",
    "store.apply", "op.retired")
# what a k2m1 read leaves with the OSD of a data shard stopped under
# noout, beside the client's, the messenger's and the op queue's spans;
# the first object was read once before the trace, in the same epoch, so
# its target comes from the client's table
READ_PATH = ("client.target_hit", "osd.ec.read", "osd.ec.sub_read",
             "osd.ec.sub_read_reply", "osd.ec.reconstruct",
             "ec.decode_prepare", "ec.decode_collect", "ec.dispatch",
             "op.retired")
# what a 4 KiB overwrite of an image on an EC data pool leaves (the
# parity-delta path), a block read, and one write that grows an object
DELTA_PATH = ("rbd.write", "rbd.read", "osd.ec.delta_plan",
              "osd.ec.delta_xor", "osd.ec.delta_apply",
              "osd.ec.delta_write", "osd.ec.rmw_fallback",
              "ec.delta_prepare", "ec.delta_collect", "ec.dispatch",
              "osd.ec.sub_read", "osd.ec.submit", "op.retired")
REMAP_PATH = ("crush.build", "crush.upload", "crush.launch", "crush.wait",
              "crush.lanes", "crush.readback", "crush.tables")
# the first EC write of a process compiles on the loop every daemon
# shares; at FAST_CONF's 0.6 s grace that stall gets all three OSDs
# marked down at once (ROADMAP A-first) and the write in flight can come
# back -EAGAIN.  Nothing here is about failure detection: the fixtures'
# clusters keep the shipped grace, and the one kill waits that long.
SHIPPED_GRACE = {"heartbeat_grace": 6.0}


async def warm_write(c, io, pid: int) -> None:
    """The process's first EC write, outside every case: it compiles on
    the loop every daemon shares, and on a loaded machine that can
    outlast even the shipped grace, the OSDs re-boot under it and the
    write comes back -EAGAIN (ROADMAP A0).  Asked again once the pool
    is healthy; any other answer is raised."""
    from ceph_tpu.client.rados import RadosError
    for attempt in range(5):
        try:
            return await io.write_full("warm", b"\x5a" * 8192)
        except RadosError as e:
            if e.code != -11 or attempt == 4:
                raise
            await c.wait_health(pid)


def _is_span_call(node, names=("span", "mark")) -> bool:
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in names)


def _sources():
    for path in sorted(glob.glob(os.path.join(ROOT, "ceph_tpu", "**",
                                              "*.py"), recursive=True)):
        if path.endswith(os.path.join("trace", "span.py")):
            continue
        with open(path) as f:
            src = f.read()
        if "trace.span import" in src or ".span import" in src:
            yield os.path.relpath(path, ROOT), ast.parse(src)


def _emitted() -> dict:
    """name -> [file:line] of every span(...)/mark(...) in ceph_tpu/;
    a name that is not a string literal is an error of its own."""
    out: dict = {}
    for path, tree in _sources():
        for node in ast.walk(tree):
            if not _is_span_call(node):
                continue
            arg = node.args[0] if node.args else None
            assert isinstance(arg, ast.Constant) and \
                isinstance(arg.value, str), \
                "%s:%d: span name must be a literal" % (path, node.lineno)
            out.setdefault(arg.value, []).append(
                "%s:%d" % (path, node.lineno))
    # the gc hook lives beside the primitive
    out.setdefault("gc", []).append("ceph_tpu/trace/span.py")
    return out


EMITTED = _emitted()


def test_every_emit_site_is_registered():
    unknown = {n: at for n, at in EMITTED.items() if n not in SPANS}
    assert not unknown, unknown


@pytest.mark.parametrize("name", sorted(SPANS))
def test_registered_span_is_emitted(name):
    layer, meaning = SPANS[name]
    assert layer in (spanmod.CLIENT, spanmod.HOST, spanmod.BATCHER,
                     spanmod.MAPPING) and meaning
    assert not name.startswith("bench.")
    assert name in EMITTED, "%s is in SPANS and emitted nowhere" % name


def _suspends(body) -> list:
    """Lines in `body` that give the loop away, nested defs left out."""
    found, todo = [], list(body)
    while todo:
        node = todo.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, (ast.Await, ast.Yield, ast.YieldFrom,
                             ast.AsyncWith, ast.AsyncFor)):
            found.append(node.lineno)
        todo.extend(ast.iter_child_nodes(node))
    return found


def test_no_span_body_suspends():
    bad = []
    for path, tree in _sources():
        for node in ast.walk(tree):
            if isinstance(node, (ast.With, ast.AsyncWith)) and any(
                    _is_span_call(i.context_expr, ("span",))
                    for i in node.items):
                bad += ["%s:%d" % (path, ln)
                        for ln in _suspends(node.body)]
    assert not bad, "await/yield inside a span body: %s" % bad


def test_lint_sees_a_suspending_body():
    tree = ast.parse("async def f():\n"
                     "    with span('x'):\n"
                     "        await g()\n"
                     "    with span('y'):\n"
                     "        def h():\n"
                     "            yield 1\n")
    withs = [n for n in ast.walk(tree) if isinstance(n, ast.With)]
    assert sorted(len(_suspends(w.body)) for w in withs) == [0, 1]


def test_span_without_a_profiler_is_transparent():
    def work():
        with span("osd.handle_op"):
            return 41 + 1
    assert work() == 42
    assert mark("client.resend", age_us=7, rto_us=3) is None
    with pytest.raises(KeyError):
        span("no.such.span")
    with pytest.raises(ZeroDivisionError):
        with span("store.apply", txns=1):
            1 / 0
    spanmod.watch_gc()
    spanmod.watch_gc()
    import gc
    assert gc.callbacks.count(spanmod._on_gc) == 1
    gc.collect()


# -- one traced write on the CPU backend -----------------------------------


def _host_events(log_dir: str) -> dict:
    """thread line -> [(name, start_ns, end_ns, stats)] of rados.* host
    events in the newest trace under log_dir."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(PREFIX):
                    lines.setdefault(ln.name, []).append(
                        (ev.name[len(PREFIX):], int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns),
                         dict(ev.stats)))
    return lines


@pytest.fixture(scope="module")
def traced_write(tmp_path_factory):
    import jax

    from ceph_tpu.testing import LocalCluster
    log_dir = str(tmp_path_factory.mktemp("spans"))
    old = os.environ.get("CEPH_TPU_EC_OFFLOAD")
    os.environ["CEPH_TPU_EC_OFFLOAD"] = "1"

    async def main():
        c = await LocalCluster(n_osds=3, conf=SHIPPED_GRACE).start()
        try:
            pid = await c.create_pool("spans", pg_num=4,
                                      pool_type="erasure")
            await c.wait_health(pid)
            io = c.client.io_ctx("spans")
            await warm_write(c, io, pid)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                await io.write_full("traced", b"\xa5" * 8192)
                await asyncio.sleep(0.2)    # the sub-ops retire
            finally:
                jax.profiler.stop_trace()
        finally:
            await c.stop()

    try:
        asyncio.run(asyncio.wait_for(main(), 240))
    finally:
        if old is None:
            del os.environ["CEPH_TPU_EC_OFFLOAD"]
        else:
            os.environ["CEPH_TPU_EC_OFFLOAD"] = old
    return _host_events(log_dir)


@pytest.fixture(scope="module")
def traced_degraded_reads(tmp_path_factory):
    """(events, reads): four 8 KiB objects read with the OSD that holds
    data position 0 of each stopped under noout."""
    import jax

    from ceph_tpu.testing import LocalCluster
    log_dir = str(tmp_path_factory.mktemp("reads"))
    names = []

    async def main():
        c = await LocalCluster(n_osds=3, conf=SHIPPED_GRACE).start()
        try:
            pid = await c.create_pool("reads", pg_num=4,
                                      pool_type="erasure")
            await c.wait_health(pid)
            io = c.client.io_ctx("reads")
            await warm_write(c, io, pid)
            om = c.client.osdmap
            await c.client.mon_command("osd set", key="noout")
            victim = 1

            def first_data_osd(name):
                pg = om.pools[pid].raw_pg_to_pg(
                    om.object_locator_to_pg(name, pid))
                return om.pg_to_up_acting_osds(pg)[2][0]

            i = 0
            while len(names) < 4:
                i += 1
                if first_data_osd("obj%d" % i) == victim:
                    names.append("obj%d" % i)
                    await io.write_full(names[-1], b"\x5a" * 8192)
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            assert await io.read(names[0]) == b"\x5a" * 8192   # warm
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                for name in names:
                    assert await io.read(name) == b"\x5a" * 8192
                await asyncio.sleep(0.2)
            finally:
                jax.profiler.stop_trace()
        finally:
            await c.stop()

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CEPH_TPU_EC_OFFLOAD", "1")
        asyncio.run(asyncio.wait_for(main(), 240))
    return _host_events(log_dir), len(names)


@pytest.fixture(scope="module")
def traced_remap(tmp_path_factory):
    import jax

    from chip_smoke import build_osdmap
    from ceph_tpu.parallel.mapping import OSDMapMapping
    log_dir = str(tmp_path_factory.mktemp("remap"))
    m = build_osdmap(40, 256)
    OSDMapMapping(m)                    # traces the pool's programs
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        mp = OSDMapMapping(m)
    finally:
        jax.profiler.stop_trace()
    assert mp.device_pools == 1 and mp.scalar_pools == 0
    return _host_events(log_dir), mp.pools[1]


@pytest.mark.parametrize("name", REMAP_PATH)
def test_traced_remap_leaves_span(traced_remap, name):
    names = {ev[0] for evs in traced_remap[0].values() for ev in evs}
    assert name in names, sorted(names)


def test_traced_remap_counts_lanes_and_bytes(traced_remap):
    lines, pm = traced_remap
    by = {}
    for evs in lines.values():
        for name, lo, hi, stats in evs:
            by.setdefault(name, []).append((lo, hi, stats))
    (lo, hi, build), = by["crush.build"]
    assert build == {"pools": 1}
    assert all(lo <= a and b <= hi for name, evs in by.items()
               for a, b, _s in evs), "a remap span outside crush.build"
    lanes = [s for _a, _b, s in by["crush.launch"] if "lanes" in s]
    # 256 PGs is no multiple of the Pallas tile: the XLA descent, counted
    assert [(s["lanes"], s["pallas_lanes"]) for s in lanes] == [(256, 0)]
    # a rule of one choose step in one TAKE/EMIT block, all of it on
    # the device
    assert [(s["steps"], s["blocks"]) for s in lanes] == [(1, 1)]
    # one pass counted: too small a pool for a tail, a few lanes flagged
    (_a, _b, counted), = by["crush.lanes"]
    assert counted["lanes"] == 256 and counted["tail_lanes"] == 0
    assert counted["blocks"] == 1
    assert 0 <= counted["resolve_lanes"] < 256
    # firstn: no indep round to leave a slot undefined; the holes of the
    # up table (two hosts cannot seat three replicas) are counted
    assert counted["retry_lanes"] == 0
    # nor an indep step's tail, and SPANS says what the count is
    assert counted["indep_tail_lanes"] == 0
    assert all(arg in SPANS["crush.lanes"][1] for arg in counted)
    assert counted["none_slots"] == int((pm.up == 0x7FFFFFFF).sum()) > 0
    (_a, _b, back), = by["crush.readback"]
    assert back["bytes"] == pm.up.nbytes + pm.up_primary.nbytes


@pytest.mark.parametrize("name", WRITE_PATH)
def test_traced_write_leaves_span(traced_write, name):
    assert any(ev[0] == name for evs in traced_write.values()
               for ev in evs), sorted({ev[0] for evs in
                                       traced_write.values()
                                       for ev in evs})


def test_traced_write_spans_nest_per_thread(traced_write):
    for line, evs in traced_write.items():
        stack = []
        for name, lo, hi, _stats in sorted(evs,
                                           key=lambda e: (e[1], -e[2])):
            while stack and stack[-1][1] <= lo:
                stack.pop()
            assert not stack or hi <= stack[-1][1], \
                "%s: %s [%d, %d] straddles %s" % (line, name, lo, hi,
                                                  stack[-1])
            stack.append((name, hi))


def test_traced_write_retires_the_clients_op(traced_write):
    retired = [ev[3] for evs in traced_write.values() for ev in evs
               if ev[0] == "op.retired"]
    mine = [s for s in retired if s.get("client") == 1]
    assert len(mine) == 1 and mine[0]["total_us"] > 0, retired
    staged = [s for s in retired if "subop_us" in s]
    assert staged and all(
        s["queue_us"] >= 0 and s["ec_batch_us"] > 0 and s["subop_us"] > 0
        for s in staged), retired
    sizes = [ev[3]["bytes"] for evs in traced_write.values() for ev in evs
             if ev[0] == "msgr.write"]
    assert sizes and max(sizes) > 4096     # a shard's frame


def test_traced_write_counts_what_each_read_brought(traced_write):
    """msgr.recv: bytes of one recv_into, and how many of them went
    straight into a large frame's own buffer (none of a 4 KiB shard's)."""
    recvs = [ev[3] for evs in traced_write.values() for ev in evs
             if ev[0] == "msgr.recv"]
    assert recvs and all(s["bytes"] >= s["direct"] >= 0 for s in recvs)
    assert sum(s["bytes"] for s in recvs) > 8192 * 3 // 2
    assert sum(s["direct"] for s in recvs) == 0


# -- traced reads with one data holder stopped under noout -------------------


def _named(lines: dict, name: str) -> list:
    return [ev for evs in lines.values() for ev in evs if ev[0] == name]


@pytest.mark.parametrize("name", READ_PATH)
def test_traced_degraded_read_leaves_span(traced_degraded_reads, name):
    lines, _reads = traced_degraded_reads
    assert _named(lines, name), sorted({ev[0] for evs in lines.values()
                                        for ev in evs})


def test_traced_degraded_read_marks_each_reconstruction(
        traced_degraded_reads):
    lines, reads = traced_degraded_reads
    marks = [ev[3] for ev in _named(lines, "osd.ec.reconstruct")]
    assert marks == [{"erased": 1, "bytes": 8192}] * reads


def test_traced_degraded_read_counts_shard_bytes(traced_degraded_reads):
    """k2m1 with data position 0 gone: each read fetches the one remote
    survivor's 4 KiB shard, whichever of the two the primary holds."""
    lines, reads = traced_degraded_reads
    served = [ev[3]["bytes"] for ev in _named(lines, "osd.ec.sub_read")]
    got = [ev[3]["bytes"] for ev in _named(lines, "osd.ec.sub_read_reply")]
    assert served == [4096] * reads and got == [4096] * reads


def test_traced_degraded_read_retires_with_read_stages(
        traced_degraded_reads):
    lines, reads = traced_degraded_reads
    staged = [ev[3] for ev in _named(lines, "op.retired")
              if "sub_read_us" in ev[3]]
    assert len(staged) == reads
    assert all(s["sub_read_us"] > 0 and s["decode_us"] > 0
               and s["total_us"] >= s["sub_read_us"] + s["decode_us"]
               and "subop_us" not in s for s in staged), staged


def test_traced_degraded_read_spans_nest_per_thread(traced_degraded_reads):
    test_traced_write_spans_nest_per_thread(traced_degraded_reads[0])


# -- traced overwrites of an image on an EC data pool ------------------------

OVERWRITES = 3


@pytest.fixture(scope="module")
def traced_overwrites(tmp_path_factory):
    """Three 4 KiB overwrites of a written 64 KiB object through
    services/rbd.py, one block read, and one write that grows an object."""
    import jax

    from ceph_tpu.client.striper import FileLayout
    from ceph_tpu.services.rbd import RBD
    from ceph_tpu.testing import LocalCluster
    log_dir = str(tmp_path_factory.mktemp("overwrites"))

    async def main():
        c = await LocalCluster(n_osds=3, conf=SHIPPED_GRACE).start()
        try:
            ec = await c.create_pool("ow", pg_num=4, pool_type="erasure")
            rep = await c.create_pool("rbd", pg_num=4)
            await c.allow_ec_overwrites("ow")
            await c.wait_health(ec)
            await c.wait_health(rep)
            await warm_write(c, c.client.io_ctx("ow"), ec)
            rbd = RBD(c.client.io_ctx("rbd"))
            await rbd.create("disk", 1 << 16, FileLayout(
                stripe_unit=1 << 16, stripe_count=1, object_size=1 << 16),
                data_pool="ow")
            img = await rbd.open("disk")
            await img.write(0, b"\x5a" * (1 << 16))
            await img.write(4096, b"\xa5" * 4096)        # warm
            io = c.client.io_ctx("ow")
            await io.write_full("grows", b"g" * 8192)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                for i in range(OVERWRITES):
                    await img.write(8192 * (i + 1), bytes([i + 1]) * 4096)
                assert await img.read(8192, 4096) == b"\x01" * 4096
                await io.write("grows", b"h" * 8192, 4096)
                await asyncio.sleep(0.2)
            finally:
                jax.profiler.stop_trace()
        finally:
            await c.stop()

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CEPH_TPU_EC_OFFLOAD", "1")
        asyncio.run(asyncio.wait_for(main(), 240))
    return _host_events(log_dir)


@pytest.mark.parametrize("name", DELTA_PATH)
def test_traced_overwrite_leaves_span(traced_overwrites, name):
    assert _named(traced_overwrites, name), sorted(
        {ev[0] for evs in traced_overwrites.values() for ev in evs})


def test_traced_overwrite_marks_each_delta_write_and_the_fallback(
        traced_overwrites):
    from ceph_tpu.osd.ecbackend import RMW_FALLBACK_WHY
    deltas = [ev[3] for ev in _named(traced_overwrites,
                                     "osd.ec.delta_write")]
    assert deltas == [{"bytes": 4096, "chunks": 1,
                       "intervals": 1}] * OVERWRITES
    fell = [ev[3] for ev in _named(traced_overwrites,
                                   "osd.ec.rmw_fallback")]
    assert [RMW_FALLBACK_WHY[s["why"]] for s in fell] == ["growth"]
    plans = [ev[3]["bytes"] for ev in _named(traced_overwrites,
                                             "osd.ec.delta_plan")]
    assert sorted(plans) == [4096] * OVERWRITES + [8192]
    # k2m1: one parity, then all three shards' transactions
    applied = sorted(ev[3]["shards"] for ev in _named(
        traced_overwrites, "osd.ec.delta_apply"))
    assert applied == [1] * OVERWRITES + [3] * OVERWRITES


def test_traced_overwrite_retires_with_delta_stages(traced_overwrites):
    retired = [ev[3] for ev in _named(traced_overwrites, "op.retired")]
    staged = [s for s in retired if "delta_read_us" in s]
    assert len(staged) == OVERWRITES
    assert all(s["delta_read_us"] > 0 and s["delta_lock_us"] >= 0
               and s["subop_us"] > 0
               and s["total_us"] >= s["delta_read_us"] + s["subop_us"]
               for s in staged), staged
    # the growing write waited for the lock too and read no delta
    assert len([s for s in retired if "delta_lock_us" in s]) \
        == OVERWRITES + 1
    sizes = [ev[3]["bytes"] for ev in _named(traced_overwrites,
                                             "rbd.write")]
    assert sizes == [4096] * OVERWRITES


def test_traced_overwrite_spans_nest_per_thread(traced_overwrites):
    test_traced_write_spans_nest_per_thread(traced_overwrites)
