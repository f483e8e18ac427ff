"""Cluster flight recorder: span rings, Perfetto export, per-chip
utilization accounting, digest segment folding, and the stage-name
drift lint.

The acceptance scenario rides here: a thrashed EC workload's exported
Chrome trace validates against the schema (required keys, monotonic
ts per track) and carries a COMPLETE span tree — >= 4 stages over
>= 2 daemons plus >= 1 device lane — for every acked write sampled.
"""

import asyncio
import zlib

import numpy as np

from ceph_tpu.testing import ClusterThrasher, LocalCluster, Workload
from ceph_tpu.trace import OpTracker
from ceph_tpu.trace import recorder as flight
from ceph_tpu.trace import registry
from ceph_tpu.utils.context import Context


def run(coro, timeout=300):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


# -- lint: stage/series names cannot silently drift ----------------------


def test_registry_lint_clean():
    """The tier-1 drift lint: every emitted stage literal is
    registered, every registered name is still emitted, every
    consumer reference (the trace tests) is registered
    AND literally present in its consumer — a rename anywhere fails
    here instead of silently unmatching."""
    assert registry.lint_repo() == []


def test_registry_lint_catches_unknown_stage():
    assert not registry.stage_known("ec_encod3d_typo")
    assert registry.stage_known("ec_encoded")
    assert registry.stage_known("sent_osd.2")


# -- unit: recorder ring, sampling, slow retention -----------------------


def _traces_for_sampling(n: int):
    """(kept, dropped) trace ids under 1-in-n sampling, found
    deterministically."""
    kept = dropped = None
    i = 0
    while kept is None or dropped is None:
        t = "c:%d" % i
        if zlib.crc32(t.encode()) % n == 0:
            kept = kept or t
        else:
            dropped = dropped or t
        i += 1
    return kept, dropped


def test_recorder_sampling_and_slow_retention():
    ctx = Context("osd.9", conf_overrides={
        "flight_recorder_sample": 8,
        "osd_op_complaint_time": 0.05,
    })
    tr = OpTracker(ctx, "osd.9")
    fr = ctx.flight_recorder
    assert fr is tr.recorder
    kept, dropped = _traces_for_sampling(8)
    tr.create("kept op", trace=kept).finish()
    tr.create("dropped op", trace=dropped).finish()
    assert [r["trace"] for r in fr.records] == [kept]
    assert fr.dropped == 1
    # slow ops are ALWAYS retained, sampled out or not
    op = tr.create("slow op", trace=dropped)
    op.initiated -= 1.0
    op.finish()
    assert fr.records[-1]["desc"] == "slow op"
    assert fr.records[-1]["slow"] is True
    # ring stays bounded
    ctx.conf.set("flight_recorder_sample", 1)
    ctx.conf.set("flight_recorder_ring", 4)
    for i in range(10):
        tr.create("op-%d" % i, trace="x:%d" % i).finish()
    assert len(fr.records) == 4
    # device-ticket attribution rides the record
    op = tr.create("ec op", trace="x:ec")
    op.note("device_ticket", {"seq": 9, "chip": 1, "bucket": 1024,
                              "queue_wait": 0.001, "device_s": 0.002,
                              "klass": "client-ec"})
    op.finish()
    assert fr.records[-1]["tickets"][0]["seq"] == 9
    # ...and surfaces first-class in the tracker dump (the
    # dump_historic_ops attribution satellite)
    dump = tr.dump_historic_ops()["ops"][-1]
    assert dump["device"]["chip"] == 1
    assert dump["device"]["bucket"] == 1024
    assert dump["device"]["queue_wait"] == 0.001
    assert dump["device"]["device_s"] == 0.002
    # disabled recorder records nothing
    flight.set_enabled(False)
    try:
        tr.create("ghost", trace="x:g").finish()
        assert fr.records[-1]["trace"] == "x:ec"
    finally:
        flight.set_enabled(True)


def test_background_span_and_dump():
    ctx = Context("osd.3")
    tr = OpTracker(ctx, "osd.3")
    fr = tr.recorder
    t0 = fr.now()
    fr.span("scrub", t0, meta={"pgid": "1.2"})
    d = fr.dump()
    assert d["daemon"] == "osd.3"
    assert d["records"][-1]["kind"] == "background"
    assert d["records"][-1]["name"] == "scrub"
    assert d["records"][-1]["meta"]["pgid"] == "1.2"
    assert d["records"][-1]["t1"] >= t0


# -- unit: chrome-trace export + schema validator ------------------------


def _op_rec(daemon, trace, t0, events, tickets=None):
    rec = {"kind": "op", "daemon": daemon, "trace": trace,
           "desc": "osd_op(%s)" % trace, "slow": False,
           "t0": t0, "t1": t0 + events[-1][0],
           "events": [[t0 + dt, name] for dt, name in events]}
    if tickets:
        rec["tickets"] = tickets
    return rec


def test_chrome_trace_export_and_validator():
    rings = {
        "client.0": [_op_rec("client.0", "c:1", 10.0,
                             [(0.0, "initiated"),
                              (0.001, "sent_osd.0"),
                              (0.005, "done")])],
        "osd.0": [
            _op_rec("osd.0", "c:1", 10.001,
                    [(0.0, "initiated"), (0.0002, "queued"),
                     (0.001, "ec_encode_start"),
                     (0.002, "ec_encoded"),
                     (0.003, "ec_write_done")],
                    tickets=[{"seq": 7, "chip": 0}]),
            # overlapping second op: must land on its own lane
            _op_rec("osd.0", "c:2", 10.002,
                    [(0.0, "initiated"), (0.004, "done")]),
            {"kind": "background", "daemon": "osd.0",
             "name": "deep_scrub", "t0": 10.01, "t1": 10.02,
             "meta": {"pgid": "1.0"}},
        ],
    }
    device = [{"seq": 7, "klass": "client-ec", "bucket": 1024,
               "bytes": 4096, "chip": 0, "t_enqueue": 10.0012,
               "t_admit": 10.0013, "t_launch": 10.0015,
               "t_done": 10.0018, "ok": True,
               "queue_wait": 0.0001, "device_s": 0.0003}]
    doc = flight.chrome_trace(rings, offsets={"osd.0": 0.0},
                              device=device, meta={"seed": 1})
    assert flight.validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"client.0", "osd.0", "device-mesh"}
    # the two overlapping osd.0 ops sit on distinct lanes
    op_slices = [e for e in evs if e.get("cat") == "op"
                 and e["args"].get("trace") in ("c:1", "c:2")]
    osd_ops = [e for e in op_slices if e["args"]["trace"] == "c:1"
               or e["args"]["trace"] == "c:2"]
    osd_tids = {e["tid"] for e in osd_ops
                if e["args"]["trace"] in ("c:1", "c:2")
                and e["name"].startswith("osd_op")}
    assert len(osd_tids) == 2
    # stage sub-slices carry the stage names
    stages = {e["name"] for e in evs if e.get("cat") == "stage"}
    assert {"queued", "ec_encode_start", "ec_encoded"} <= stages
    # the cross-daemon trace produced a flow start and end
    phases = [e["ph"] for e in evs if e.get("cat") == "flow"]
    assert "s" in phases and "f" in phases
    # device lane: the ticket renders on the chip's lane with its seq
    dev = [e for e in evs
           if e.get("cat") == "device" and e["ph"] == "X"]
    assert len(dev) == 1 and dev[0]["args"]["seq"] == 7
    # counter tracks: per-chip busy / queue-depth "C" events
    ctr = [e for e in evs if e["ph"] == "C"]
    assert {e["name"] for e in ctr} \
        == {"chip-0 busy", "chip-0 queue_depth"}
    # queue-depth steps up at enqueue and back down by completion
    depths = [e["args"]["queue_depth"] for e in ctr
              if e["name"] == "chip-0 queue_depth"]
    assert max(depths) >= 1 and depths[-1] == 0
    # background span rendered
    assert any(e.get("cat") == "background"
               and e["name"] == "deep_scrub" for e in evs)
    # the validator actually catches breakage
    assert flight.validate_chrome_trace({"nope": 1})
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 0, "ts": 5.0,
         "dur": 1.0},
        {"ph": "X", "name": "b", "pid": 1, "tid": 0, "ts": 1.0,
         "dur": 1.0}]}
    assert any("regresses" in e
               for e in flight.validate_chrome_trace(bad))
    missing = {"traceEvents": [{"ph": "X", "name": "a"}]}
    assert any("missing keys" in e
               for e in flight.validate_chrome_trace(missing))


# -- unit: per-chip utilization integrals --------------------------------


def test_chip_utilization_integrals():
    from ceph_tpu.device.runtime import DeviceRuntime, DispatchTicket

    rt = DeviceRuntime(chips=2)
    chip = rt.chips[0]
    now = 100.0

    def fake_ticket(t_enq, qwait, dev_s, ok=True):
        t = DispatchTicket(rt.next_seq(), "client-ec", 1024, 4096,
                           chip=0)
        t.t_enqueue = t_enq
        t.t_admit = t_enq + qwait
        t.t_launch = t.t_admit
        t.t_done = t.t_launch + dev_s
        t.ok = ok
        chip.tickets.append(t)
        return t

    # 0.2 s device time + 0.1 s queue wait inside a 1 s window
    fake_ticket(99.5, 0.1, 0.2)
    u = chip.utilization(window=1.0, now=now)
    assert abs(u["busy_frac"] - 0.2) < 1e-6
    assert abs(u["queue_wait_frac"] - 0.1) < 1e-6
    assert abs(u["idle_frac"] - 0.8) < 1e-6
    # a ticket fully before the window contributes nothing
    fake_ticket(90.0, 0.5, 0.5)
    u = chip.utilization(window=1.0, now=now)
    assert abs(u["busy_frac"] - 0.2) < 1e-6
    # a straddling ticket is clipped to its window overlap
    fake_ticket(98.8, 0.0, 0.5)     # done at 99.3, window starts 99.0
    u = chip.utilization(window=1.0, now=now)
    assert abs(u["busy_frac"] - 0.5) < 1e-6
    # failed dispatches count queue wait but not busy
    fake_ticket(99.6, 0.2, 0.3, ok=False)
    u = chip.utilization(window=1.0, now=now)
    assert abs(u["busy_frac"] - 0.5) < 1e-6
    assert abs(u["queue_wait_frac"] - 0.3) < 1e-6
    # the metrics map exports the util gauges with the chip label
    m = chip.metrics()
    for key in ("device_util_busy", "device_util_queue_wait",
                "device_util_idle"):
        assert key in m
    from ceph_tpu.utils.exporter import validate_exposition
    body = "\n".join(rt.prom_lines()) + "\n"
    assert validate_exposition(body) == []
    assert 'ceph_tpu_device_util_busy{chip="0"}' in body
    assert 'ceph_tpu_device_util_queue_wait{chip="1"}' in body
    assert 'ceph_tpu_device_util_idle{chip="0"}' in body


# -- unit: crc32 combine + segment folding (digest lane-cap lift) --------


def test_crc32_combine_parity():
    from ceph_tpu.device.digest import crc32_combine

    rng = np.random.default_rng(7)
    for la, lb in ((0, 5), (1, 1), (100, 3), (1000, 1 << 14),
                   (12345, 67890), (1 << 14, 1)):
        a = rng.integers(0, 256, la, dtype=np.uint8).tobytes()
        b = rng.integers(0, 256, lb, dtype=np.uint8).tobytes()
        assert crc32_combine(zlib.crc32(a), zlib.crc32(b), lb) \
            == zlib.crc32(a + b)
    # multi-segment fold (the device path's recombination shape)
    parts = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in (1 << 14, 1 << 14, 777, 1 << 14, 1)]
    crc = zlib.crc32(parts[0])
    for p in parts[1:]:
        crc = crc32_combine(crc, zlib.crc32(p), len(p))
    assert crc == zlib.crc32(b"".join(parts))
    # len2=0 is the identity
    assert crc32_combine(0x12345678, 0, 0) == 0x12345678


def test_digest_segment_folding_lifts_lane_cap(monkeypatch):
    """Buffers far past the old 16 KiB lane cap digest ON DEVICE by
    splitting into <= 16 KiB lanes and recombining with
    crc32_combine, bit-identical to zlib.crc32."""
    monkeypatch.setenv("CEPH_TPU_SCRUB_OFFLOAD", "1")
    from ceph_tpu.device import digest as dg
    from ceph_tpu.device.runtime import DeviceRuntime

    async def main():
        DeviceRuntime.reset()
        rng = np.random.default_rng(13)
        bufs = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                for n in (100, dg.DEVICE_MAX_BYTES,
                          dg.DEVICE_MAX_BYTES + 1,
                          5 * dg.DEVICE_MAX_BYTES + 321,
                          2 * dg.DEVICE_MAX_BYTES)]
        out, path = await dg.crc32_batch(bufs)
        assert path == "device"
        assert out == dg.crc32_host(bufs)

    run(main())


# -- cluster: status surfaces --------------------------------------------


def test_status_pgmap_unavailable_without_digest():
    """A digest-less mon (no mgr ever registered) says so explicitly
    instead of silently omitting the pgmap section."""

    async def main():
        c = await LocalCluster(n_osds=1).start()
        try:
            st = await c.client.mon_command("status")
            assert st["pgmap"] == {
                "available": False,
                "status": "unavailable (no mgr digest)",
            }, st
        finally:
            await c.stop()

    run(main())


def test_device_util_flows_to_status_and_dumps(monkeypatch):
    """Per-chip utilization integrals flow OSD -> MMgrReport -> mgr
    digest -> `status` device-utilization line; device-dispatched EC
    ops carry chip + ticket attribution in dump_historic_ops."""
    monkeypatch.setenv("CEPH_TPU_EC_OFFLOAD", "1")

    async def main():
        c = await LocalCluster(n_osds=3, with_mgr=True).start()
        try:
            pid = await c.create_pool("fru", pg_num=8,
                                      pool_type="erasure")
            await c.wait_health(pid)
            io = c.client.io_ctx("fru")
            for i in range(24):
                await io.write_full("u-%d" % i, b"\xa5" * 8192)

            def busy(d):
                rows = (d or {}).get("device_util") or {}
                return any((r.get("busy_frac") or 0) > 0
                           for r in rows.values())

            await c.wait_stats(busy, timeout=30.0,
                               what="device_util busy in digest")
            st = await c.client.mon_command("status")
            assert st["pgmap"]["available"] is True
            du = st.get("device_util") or {}
            assert du, st
            assert any((r.get("busy_frac") or 0) > 0
                       for r in du.values()), du
            for row in du.values():
                assert {"busy_frac", "queue_wait_frac",
                        "idle_frac"} <= set(row)
            # S3: historic dumps carry the op's chip + ticket
            # attribution, not just stage names
            attributed = 0
            for osd in c.live_osds:
                for rec in osd.optracker.dump_historic_ops()["ops"]:
                    dev = rec.get("device")
                    if dev is None:
                        continue
                    assert dev["chip"] is not None
                    assert dev["bucket"] > 0
                    assert dev["queue_wait"] is not None
                    assert dev["device_s"] is not None
                    attributed += 1
            assert attributed > 0
        finally:
            await c.stop()

    run(main())


# -- acceptance: thrashed EC write span trees in the exported trace ------


def test_thrashed_ec_trace_complete_span_trees(monkeypatch,
                                               tmp_path):
    """A thrashed EC workload's exported Chrome trace validates
    against the schema and carries, for EVERY acked write sampled
    (dev conf samples every trace), a complete span tree: >= 4
    distinct stages over >= 2 daemons plus >= 1 device lane."""
    monkeypatch.setenv("CEPH_TPU_EC_OFFLOAD", "1")
    flight.clear_device_ring()

    async def main():
        c = await LocalCluster(
            n_osds=4, seed=33,
            conf={"osd_op_history_size": 512,
                  "flight_recorder_ring": 16384}).start()
        try:
            pid = await c.create_pool("fr_ec", pg_num=8,
                                      pool_type="erasure")
            await c.wait_health(pid)
            wl = Workload(c.client.io_ctx("fr_ec"), seed=33).start()
            th = ClusterThrasher(c, seed=33,
                                 actions=[("kill_revive", 1)])
            await th.run(pid, wl)
            await wl.stop()
            await asyncio.sleep(0.4)        # last sub-ops retire

            out = str(tmp_path / "thrash_trace.json")
            doc = c.export_trace(path=out)
            errs = flight.validate_chrome_trace(doc)
            assert not errs, errs[:5]
            import json
            import os
            assert os.path.getsize(out) > 0
            with open(out) as f:
                assert json.load(f)["traceEvents"]

            evs = doc["traceEvents"]
            pid_name = {e["pid"]: e["args"]["name"] for e in evs
                        if e["ph"] == "M"
                        and e["name"] == "process_name"}
            op_by_trace: dict = {}
            stages_by_trace: dict = {}
            for e in evs:
                tr = (e.get("args") or {}).get("trace")
                if e.get("cat") == "op" and tr:
                    op_by_trace.setdefault(tr, []).append(e)
                elif e.get("cat") == "stage" and tr:
                    stages_by_trace.setdefault(tr, set()).add(
                        e["name"])
            device_seqs = {e["args"]["seq"] for e in evs
                           if e.get("cat") == "device"
                           and e["ph"] == "X"}
            assert device_seqs, "no device lanes in the trace"

            # map acked oids -> client write traces from the client's
            # own ring (dev conf keeps every trace)
            write_trace: dict = {}
            for r in c.client.ctx.flight_recorder.records:
                if r.get("kind") != "op" or "[writefull]" \
                        not in r["desc"]:
                    continue
                for oid in wl.acked:
                    if " %s " % oid in r["desc"]:
                        write_trace[oid] = r["trace"]
            assert len(write_trace) == len(wl.acked), \
                "client ring lost %d acked writes" \
                % (len(wl.acked) - len(write_trace))

            checked = 0
            for oid, tr in sorted(write_trace.items()):
                ops = op_by_trace.get(tr) or []
                daemons = {pid_name[e["pid"]] for e in ops}
                assert len(daemons) >= 2, (oid, tr, daemons)
                stages = stages_by_trace.get(tr) or set()
                assert len(stages) >= 4, (oid, tr, stages)
                # the exact-flush attribution stage rode the span
                assert "device_dispatched" in stages, (oid, stages)
                # >= 1 device lane: the write's own flush ticket
                # appears as a device-lane slice
                seqs = {e["args"].get("device_ticket_seq")
                        for e in ops} - {None}
                assert seqs, (oid, tr, "no device ticket on the op")
                assert seqs & device_seqs, (oid, tr, seqs)
                checked += 1
            assert checked == len(wl.acked) and checked >= 20, checked
        finally:
            await c.stop()

    run(main(), timeout=280)


def test_export_trace_includes_background_spans(monkeypatch):
    """Scrub work shows up as background spans beside the ops (the
    competing-work visibility the recorder exists for)."""

    async def main():
        c = await LocalCluster(n_osds=3).start()
        try:
            pid = await c.create_pool("fr_bg", pg_num=4, size=3)
            await c.wait_health(pid)
            io = c.client.io_ctx("fr_bg")
            for i in range(8):
                await io.write_full("b-%d" % i, b"\x5a" * 2048)
            await c.scrub_pool(pid, deep=True, recheck=False)
            doc = c.export_trace()
            assert flight.validate_chrome_trace(doc) == []
            names = {e["name"] for e in doc["traceEvents"]
                     if e.get("cat") == "background"}
            assert "deep_scrub" in names, names
        finally:
            await c.stop()

    run(main())
