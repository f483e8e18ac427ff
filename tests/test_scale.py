"""Scale plane: shell clusters, columnar PGMap, batched balancer.

Covers ISSUE 7's acceptance surface at tier-1 size:

* a ~300-shell cluster boots through the real mon/paxos/subscription
  path (boot storm folded into a handful of epochs), drives mark-out
  churn, and the misplaced rise + drain is observed through the
  external stats plane (OSD report -> mgr columnar PGMap -> mon
  digest);
* the columnar PGMap folds a 100k-row synthetic report set with
  unchanged digest/health outputs vs the original dict implementation
  (golden comparison);
* a late joiner N epochs behind converges with exactly ONE full map
  plus contiguous incrementals (MOSDMapMsg traffic asserted);
* the batched balancer scores >= 1000 candidate upmaps in one
  device-runtime dispatch (ticket asserted) and its emitted items are
  identical in effect to the calc_pg_upmaps validity rules.

A pytest-marked slow variant boots 1k here for CI-style full passes;
5k and 10k have no runner.
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.scale import ScaleCluster, batched_calc_pg_upmaps


def run(coro, timeout=420):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


QUIET = {"log_level": 0}


# -- columnar PGMap golden comparison ---------------------------------------


def _synth_reports(n_rows: int, n_pools: int = 12,
                   n_daemons: int = 64, seed: int = 7):
    """Deterministic synthetic report set: each daemon primaries a
    slice of the rows; two stamps so rates derive; a handful of rows
    change primary between passes (the rate-reset path)."""
    rng = np.random.default_rng(seed)
    rows_by_daemon: dict[str, list] = {}
    pools = rng.integers(1, 1 + n_pools, n_rows)
    daemons = rng.integers(0, n_daemons, n_rows)
    states = np.array(["active", "replica", "peering"])
    st_pick = rng.integers(0, 3, n_rows)
    for i in range(n_rows):
        d = "osd.%d" % daemons[i]
        rows_by_daemon.setdefault(d, []).append({
            "pgid": "%d.%x" % (pools[i], i),
            "pool": int(pools[i]),
            "state": str(states[st_pick[i]]),
            "num_objects": int(rng.integers(0, 100)),
            "num_bytes": int(rng.integers(0, 1 << 30)),
            "degraded": int(rng.integers(0, 5)),
            "misplaced": int(rng.integers(0, 5)),
            "unfound": int(rng.integers(0, 2)),
            "log_size": int(rng.integers(0, 50)),
            "read_ops": int(rng.integers(0, 10000)),
            "read_bytes": int(rng.integers(0, 1 << 24)),
            "write_ops": int(rng.integers(0, 10000)),
            "write_bytes": int(rng.integers(0, 1 << 24)),
            "recovery_ops": int(rng.integers(0, 1000)),
            "recovery_bytes": int(rng.integers(0, 1 << 20)),
        })
    return rows_by_daemon


def _bump(rows_by_daemon, rng):
    """Second-pass counters: monotone bumps (integer deltas over an
    integral dt, so both implementations derive identical rates)."""
    out = {}
    for d, rows in rows_by_daemon.items():
        out[d] = []
        for r in rows:
            r2 = dict(r)
            for c in ("read_ops", "write_ops", "recovery_ops"):
                r2[c] = r[c] + int(rng.integers(0, 64)) * 4
            out[d].append(r2)
    return out


def _digests_equal(a: dict, b: dict) -> None:
    assert a["num_pgs"] == b["num_pgs"]
    assert a["pg_states"] == b["pg_states"]
    assert a["inactive_pgs"] == b["inactive_pgs"]
    assert a["osd_stats"] == b["osd_stats"]
    assert a["op_size_hist_bytes_pow2"] == b["op_size_hist_bytes_pow2"]
    assert set(a["pools"]) == set(b["pools"])
    for pid in a["pools"]:
        ra, rb = a["pools"][pid], b["pools"][pid]
        assert set(ra) == set(rb)
        for k in ra:
            if isinstance(ra[k], float) or isinstance(rb[k], float):
                assert rb[k] == pytest.approx(ra[k], rel=1e-9), \
                    (pid, k)
            else:
                assert ra[k] == rb[k], (pid, k)
    for k in a["totals"]:
        assert b["totals"][k] == pytest.approx(a["totals"][k],
                                               rel=1e-9), k


def test_columnar_pgmap_golden_100k():
    """The acceptance fold: 100k synthetic rows through both
    implementations — digest, pool totals, state counts, and the
    health inputs (degraded/inactive) must agree."""
    from ceph_tpu.mgr.pgmap import DictPGMap, PGMap

    n = 100_000
    reports = _synth_reports(n)
    rng = np.random.default_rng(11)
    reports2 = _bump(reports, rng)
    col, ref = PGMap(stale_after=1e9), DictPGMap(stale_after=1e9)
    for pm in (col, ref):
        for d, rows in reports.items():
            pm.apply_report(d, rows, None, stamp=100.0)
        for d, rows in reports2.items():
            pm.apply_report(d, rows, None, stamp=104.0)
    assert col.num_rows == n
    _digests_equal(ref.digest(now=104.0), col.digest(now=104.0))
    # pool filter (deleted pool) agrees too
    keep = {1, 2, 3}
    a = ref.pool_totals(104.0, keep)
    b = col.pool_totals(104.0, keep)
    assert set(a) == set(b)
    for pid in a:
        for k in a[pid]:
            assert b[pid][k] == pytest.approx(a[pid][k], rel=1e-9)
    assert ref.pg_state_counts(104.0) == col.pg_state_counts(104.0)


def test_columnar_pgmap_rates_view_and_staleness():
    """The rates mapping view + staleness semantics the dict
    implementation exposed (pm.rates[pgid], rows aging out)."""
    from ceph_tpu.mgr.pgmap import PGMap

    pm = PGMap(stale_after=5.0)
    row = {"pgid": "3.a", "pool": 3, "state": "active",
           "num_objects": 4, "write_ops": 100}
    pm.apply_report("osd.2", [row], None, stamp=10.0)
    assert "3.a" not in pm.rates
    row2 = dict(row, write_ops=160)
    pm.apply_report("osd.2", [row2], None, stamp=12.0)
    assert pm.rates["3.a"]["write_ops_s"] == 30.0
    # primary change resets the rate base
    pm.apply_report("osd.5", [row2], None, stamp=13.0)
    assert "3.a" not in pm.rates
    # staleness: the row ages out of every fold
    assert pm.pool_totals(now=30.0) == {}
    assert pm.pg_state_counts(now=30.0) == {}


# -- batched balancer --------------------------------------------------------


def _skewed_host_map(hosts=12, per_host=4, pg_num=1024, size=3):
    from ceph_tpu.models.crushmap import (CHOOSELEAF_FIRSTN, EMIT,
                                          STRAW2, TAKE, CrushMap)
    from ceph_tpu.osd.osdmap import (OSD_EXISTS, OSD_UP, Incremental,
                                     OSDMap, PGPool)

    n_osds = hosts * per_host
    crush = CrushMap()
    host_ids = []
    for h in range(hosts):
        items = list(range(h * per_host, (h + 1) * per_host))
        b = crush.add_bucket(STRAW2, 1, items, [0x10000] * per_host,
                             id=-(h + 2))
        host_ids.append(b.id)
    crush.add_bucket(STRAW2, 2, host_ids,
                     [crush.buckets[h].weight for h in host_ids],
                     id=-1)
    crush.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1),
                    (EMIT, 0, 0)], id=0)
    m = OSDMap()
    inc = Incremental(epoch=1)
    inc.new_max_osd = n_osds
    inc.new_crush = crush
    inc.new_pools[1] = PGPool(id=1, name="p", pg_num=pg_num,
                              size=size, crush_rule=0)
    m.apply_incremental(inc)
    inc = m.new_incremental()
    for o in range(n_osds):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = 0x8000 if o % 5 == 0 else 0x10000
    m.apply_incremental(inc)
    return m, per_host


def test_batched_balancer_ticket_and_candidate_volume():
    """One balancer tick must score >= 1000 candidates in bulk
    dispatches carried by device-runtime mapping-class tickets (the
    acceptance criterion's counter), and reduce placement stddev."""
    from ceph_tpu.device.runtime import DeviceRuntime, K_MAPPING

    DeviceRuntime.reset()
    m, _per_host = _skewed_host_map()
    inc = m.new_incremental()
    res = batched_calc_pg_upmaps(m, inc, max_deviation=0.5,
                                 max_changes=64)
    assert res.changes > 0
    assert res.candidates_scored >= 1000
    assert res.device_rounds >= 1
    # the dispatch rode a runtime ticket on the mapping class: ours
    # must be in the chip's ring, successful, sized by the candidate
    # table (one ticket may cover thousands of candidates)
    assert res.tickets, "no device tickets recorded"
    ring = DeviceRuntime.get().tickets
    for t in res.tickets:
        assert t.klass == K_MAPPING and t.ok and t in ring
    biggest = max(t.nbytes for t in res.tickets)
    assert biggest >= 1000 * 4      # >= 1000 candidates in ONE batch
    assert res.stddev_after < res.stddev_before


def test_batched_balancer_effect_identical_to_reference_rules():
    """Emitted upmaps replayed through the EXISTING calc_pg_upmaps
    validity rules: every item's source is a raw member (no stacked
    no-ops), applied up sets respect failure domains and dup rules,
    and the deviation accounting the batched scorer reported is
    bit-identical to the applied map's real placement."""
    from ceph_tpu.osd.balancer import (BalancerState, _effective_up,
                                       _failure_domains)
    from ceph_tpu.osd.osdmap import OSDMap
    from ceph_tpu.scale.balancer import _stddev

    m, per_host = _skewed_host_map()
    inc = m.new_incremental()
    res = batched_calc_pg_upmaps(m, inc, max_deviation=0.5,
                                 max_changes=64)
    assert res.changes > 0 and inc.new_pg_upmap_items
    m2 = OSDMap.decode(m.encode())
    m2.apply_incremental(inc)
    domains = _failure_domains(m2, 0)
    for pg, items in m2.pg_upmap_items.items():
        pool = m2.pools[pg.pool]
        raw, _ = m2._pg_to_raw_osds(pool, pg)
        for f, _t in items:
            assert f in raw, (pg, items, raw)
        up, _, _, _ = m2.pg_to_up_acting_osds(pg)
        assert len(set(up)) == len(up)
        doms = [domains.get(o) for o in up]
        assert None not in doms and len(set(doms)) == len(doms), \
            (pg, up, doms)
        # the item list's effect via _apply_upmap replay == the map's
        # real up set (the calc_pg_upmaps bookkeeping contract)
        assert _effective_up(m2, raw, items) == up
    # deviation accounting: the scorer's reported stddev_after equals
    # the stddev recomputed from the APPLIED map's placements
    st2 = BalancerState(m2, None)
    assert abs(_stddev(st2.counts, st2.target)
               - res.stddev_after) < 1e-9


def test_batched_balancer_host_fallback_matches_device():
    """With the mesh poisoned the tick degrades to the numpy host
    scorer and still converges — same integer math, different venue."""
    from ceph_tpu.device.runtime import DeviceRuntime

    m, _ = _skewed_host_map(hosts=6, pg_num=256)
    inc_dev = m.new_incremental()
    DeviceRuntime.reset()
    res_dev = batched_calc_pg_upmaps(m, inc_dev, max_deviation=0.5)
    rt = DeviceRuntime.reset()
    rt.poison(RuntimeError("test: mesh lost"))
    inc_host = m.new_incremental()
    res_host = batched_calc_pg_upmaps(m, inc_host, max_deviation=0.5)
    DeviceRuntime.reset()
    assert res_host.device_rounds == 0 and res_host.host_rounds >= 1
    assert res_dev.device_rounds >= 1
    # identical verdicts: same items emitted either way
    assert inc_dev.new_pg_upmap_items == inc_host.new_pg_upmap_items
    assert res_host.stddev_after == pytest.approx(
        res_dev.stddev_after)


# -- shell cluster smoke (tier-1) -------------------------------------------


def test_scale_cluster_smoke_300():
    """~300 OSD shells through the real mon path: boot storm folds
    into a handful of epochs, the columnar digest carries every PG,
    mark-out churn raises misplaced through the stats plane and the
    simulated backfill drains it to exactly zero."""

    async def main():
        c = await ScaleCluster(300, conf=QUIET).start()
        try:
            mon = c.mons[0]
            # boot storm folded: 300 boots in few epochs, not 300
            assert mon.osdmap.epoch <= 20, mon.osdmap.epoch
            assert sum(1 for o in range(mon.osdmap.max_osd)
                       if mon.osdmap.is_up(o)) == 300
            await c.create_pool("scale", pg_num=1024)
            target = c.leader().osdmap.epoch
            conv = await c.wait_epoch_converged(target, timeout=60.0)
            assert conv < 60.0

            from ceph_tpu.utils.backoff import wait_for
            await wait_for(
                lambda: (c.digest() or {}).get("num_pgs") == 1024,
                45.0, what="digest carrying all 1024 shell PGs")
            victims = await c.mark_out_fraction(0.01)
            assert len(victims) == 3
            await c.wait_epoch_converged(c.leader().osdmap.epoch,
                                         timeout=60.0)
            obs = await c.wait_misplaced_drained(timeout=120.0)
            assert obs["max_misplaced"] > 0
            assert obs["max_recovery_rate"] > 0.0
            assert c.misplaced_objects() == 0
            # publication stayed incremental for the whole fleet:
            # full maps only for fresh subscribers, bounded hard
            assert mon.full_maps_sent <= 5, mon.full_maps_sent
        finally:
            await c.stop()

    run(main())


def test_late_joiner_full_map_plus_incrementals():
    """A shell booting N epochs behind (N > mon_map_catchup_max)
    converges via ONE full map + contiguous incrementals — never a
    second full map, never the whole incremental history."""

    async def main():
        conf = dict(QUIET, mon_map_catchup_max=8)
        c = await ScaleCluster(20, conf=conf).start()
        try:
            mon = c.mons[0]
            await c.create_pool("p", pg_num=64)
            # drive ~16 epochs of history (out/in toggles commit one
            # epoch each, beyond the catch-up cap)
            for i in range(8):
                await c.client.mon_command("osd out", id=i)
                await c.client.mon_command("osd in", id=i)
            assert mon.osdmap.epoch > 10
            full_before = mon.full_maps_sent
            fresh = (await c.add_shells(1))[0]
            target = mon.osdmap.epoch
            await c.wait_epoch_converged(target, timeout=30.0)
            assert fresh.osdmap.epoch >= target
            # exactly one full map crossed the wire for the joiner
            assert mon.full_maps_sent == full_before + 1, \
                (full_before, mon.full_maps_sent)
            # and it kept converging incrementally afterwards
            await c.client.mon_command("osd out", id=2)
            await c.client.mon_command("osd in", id=2)
            await c.wait_epoch_converged(mon.osdmap.epoch,
                                         timeout=30.0)
            assert mon.full_maps_sent == full_before + 1
        finally:
            await c.stop()

    run(main())


@pytest.mark.slow
def test_scale_cluster_1k():
    """A 1k-OSD fleet as a CI-style full-pass test."""

    async def main():
        c = await ScaleCluster(1000, conf=QUIET).start()
        try:
            await c.create_pool("scale", pg_num=4096)
            await c.wait_epoch_converged(c.leader().osdmap.epoch,
                                         timeout=120.0)
            from ceph_tpu.utils.backoff import wait_for
            await wait_for(
                lambda: (c.digest() or {}).get("num_pgs") == 4096,
                90.0, what="digest carrying all 4096 shell PGs")
            await c.mark_out_fraction(0.01)
            obs = await c.wait_misplaced_drained(timeout=240.0)
            assert obs["max_misplaced"] > 0
            info = await c.mgr.balancer_tick()
            assert info["candidates_scored"] >= 1000
            assert info["stddev_after"] <= info["stddev_before"]
        finally:
            await c.stop()

    run(main(), timeout=900)
