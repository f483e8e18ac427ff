"""Telemetry fabric: packed stat-row blocks + vectorized mgr ingest.

Covers ISSUE 13's acceptance surface:

* the packed columnar block format round-trips dict rows exactly and
  its encoding is byte-stable (golden sha256 pin — the wire format is
  a compatibility artifact like the dencoder corpus);
* MMgrReports without the columnar field encode byte-identically to
  the pre-columnar wire form, and legacy dict-row reports parse
  unchanged (mixed-version fleets);
* the columnar fast path is golden-identical to DictPGMap across a
  randomized fleet — rates, counter-reset clamping, primary changes,
  scrub columns, staleness, pool filters, and prune counters;
* a mixed columnar+legacy fleet converges to the digest an all-legacy
  fleet produces;
* a malformed block falls back to the row loop VISIBLY (counted),
  while well-formed blocks never fall back (1M-row smoke, slow);
* ingest observability: the mgr exporter families render lint-clean,
  the registry drift lint holds, and report freshness (max-age /
  stale-count) flows digest -> `status`;
* three steady report generations of stampless rows and a 9,000-PG
  sweep keep the columnar path golden-identical with zero fallback.
"""

import asyncio
import hashlib

import numpy as np
import pytest

from ceph_tpu.mgr.pgmap import DictPGMap, PGMap
from ceph_tpu.msg.statblock import (STAT_CTR_COLS, STAT_FLOAT_COLS,
                                    STAT_INT_COLS, block_nbytes,
                                    pack_stat_rows, unpack_stat_rows)


def run(coro, timeout=180):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


# -- fixtures ----------------------------------------------------------------


def _full_row(pgid, pool, state, base):
    return {"pgid": pgid, "pool": pool, "state": state,
            "num_objects": base + 7, "num_bytes": (base + 7) << 20,
            "degraded": base % 3, "misplaced": base % 2, "unfound": 0,
            "log_size": 5 + base, "scrub_errors": base % 4 == 3,
            "read_ops": 10 * base, "read_bytes": 4096 * base,
            "write_ops": 20 * base, "write_bytes": 8192 * base,
            "recovery_ops": 3 * base, "recovery_bytes": 300 * base,
            "last_scrub_stamp": 12.5 + base,
            "last_deep_scrub_stamp": 0.25 * base}


def _golden_rows():
    return [_full_row("1.0", 1, "active", 0),
            _full_row("1.1", 1, "peering", 1),
            _full_row("2.1f", 2, "active", 2),
            _full_row("3.ff", 3, "replica", 3)]


def _synth_fleet(n_rows, n_daemons=24, n_pools=8, seed=3):
    """Flat row list + per-row daemon assignment (regrouped by the
    caller so primary changes between passes are easy to model)."""
    rng = np.random.default_rng(seed)
    rows, owners = [], []
    for i in range(n_rows):
        pool = 1 + int(rng.integers(0, n_pools))
        st = ("active", "replica", "peering")[int(rng.integers(0, 3))]
        row = _full_row("%d.%x" % (pool, i), pool, st,
                        int(rng.integers(0, 50)))
        row["scrub_errors"] = int(rng.integers(0, 20) == 0)
        rows.append(row)
        owners.append(int(rng.integers(0, n_daemons)))
    return rows, owners, rng


def _group(rows, owners):
    by = {}
    for row, o in zip(rows, owners):
        by.setdefault("osd.%d" % o, []).append(row)
    return by


def _apply(pm, by_daemon, stamp, columnar):
    for d, rows in sorted(by_daemon.items()):
        if columnar:
            pm.apply_report(d, None, None, stamp,
                            pg_stats_cols=pack_stat_rows(rows))
        else:
            pm.apply_report(d, rows, None, stamp)


def _assert_digests_equal(a: dict, b: dict):
    assert a["num_pgs"] == b["num_pgs"]
    assert a["pg_states"] == b["pg_states"]
    assert a["inactive_pgs"] == b["inactive_pgs"]
    assert a["inconsistent_pgs"] == b["inconsistent_pgs"]
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


# -- packed block format -----------------------------------------------------


def test_statblock_roundtrip_exact():
    rows = _golden_rows()
    blk = pack_stat_rows(rows)
    assert blk["n"] == len(rows)
    back = unpack_stat_rows(blk)
    for orig, got in zip(rows, back):
        assert got["pgid"] == orig["pgid"]
        assert got["state"] == orig["state"]
        for c in STAT_INT_COLS + STAT_CTR_COLS:
            assert got[c] == int(orig[c]), c
        for c in STAT_FLOAT_COLS:
            assert got[c] == float(orig[c]), c
    assert block_nbytes(blk) > 0


def test_statblock_golden_byte_stability():
    """The packed encoding is a wire-compat artifact: its denc bytes
    are PINNED.  A layout change must bump STATBLOCK_V and regenerate
    this digest deliberately — never drift silently."""
    from ceph_tpu.utils import denc
    blob = denc.encode(pack_stat_rows(_golden_rows()))
    assert len(blob) == 848
    assert hashlib.sha256(blob).hexdigest() == (
        "0ffe1d4df3261c0b9973ed9b4915948c"
        "1a54acc9bfbfcfa1dfdee71f5ea356c0")


def test_statblock_rejects_malformed():
    blk = pack_stat_rows(_golden_rows())
    from ceph_tpu.msg.statblock import block_cols
    bad = dict(blk, v=99)
    with pytest.raises(ValueError):
        block_cols(bad)
    bad = dict(blk, pg_pool=blk["pg_pool"][:-8])
    with pytest.raises(ValueError):
        block_cols(bad)
    bad = dict(blk, state_names=[])
    with pytest.raises(ValueError):
        block_cols(bad)
    with pytest.raises(ValueError):
        pack_stat_rows([{"pgid": "not-a-pgid", "pool": 1}])


# -- wire back-compat --------------------------------------------------------


def test_mgr_report_legacy_envelope_byte_stable():
    """An MMgrReport WITHOUT the columnar field encodes byte-identically
    to the pre-columnar wire form (the pinned-corpus discipline), and
    a legacy frame parses with pg_stats intact + pg_stats_cols None."""
    from ceph_tpu.msg.message import decode_message, encode_message
    from ceph_tpu.msg.messages import MMgrReport
    from ceph_tpu.utils import denc

    rows = [{"pgid": "1.0", "pool": 1, "num_objects": 3}]
    m = MMgrReport(daemon="osd.0", epoch=3, perf={},
                   pg_states={"active": 1}, num_pgs=1, num_objects=3,
                   pg_stats=rows, osd_stats=None)
    legacy_fields = {
        "daemon": "osd.0", "epoch": 3, "perf": {},
        "pg_states": {"active": 1}, "num_pgs": 1, "num_objects": 3,
        "pg_stats": rows, "osd_stats": None}
    legacy_blob = denc.encode_versioned(
        ["mgr_report", 0, "", legacy_fields], 1, 1)
    assert encode_message(m) == legacy_blob
    got = decode_message(legacy_blob)
    assert got.pg_stats == rows
    assert got.pg_stats_cols is None
    # a columnar report round-trips its block through the envelope
    blk = pack_stat_rows(_golden_rows())
    m2 = MMgrReport(daemon="osd.1", epoch=4, perf={}, pg_states={},
                    num_pgs=4, num_objects=0, pg_stats=None,
                    osd_stats=None, pg_stats_cols=blk)
    got2 = decode_message(encode_message(m2))
    assert got2.pg_stats is None
    assert unpack_stat_rows(got2.pg_stats_cols) == \
        unpack_stat_rows(blk)


# -- columnar-vs-dict golden -------------------------------------------------


def test_columnar_golden_randomized_fleet():
    """Randomized fleet through three passes — counter bumps, counter
    RESETS (clamp at 0), primary handoffs (rate restart) — then
    staleness, pool filters and pruning: the columnar fast path, the
    legacy row loop, and DictPGMap agree on every surface."""
    n = 4000
    rows, owners, rng = _synth_fleet(n)
    col = PGMap(stale_after=1e9)
    rowwise = PGMap(stale_after=1e9)
    ref = DictPGMap(stale_after=1e9)
    pms = ((col, True), (rowwise, False), (ref, False))

    by = _group(rows, owners)
    for pm, columnar in pms:
        _apply(pm, by, 100.0, columnar)

    # pass 2: monotone bumps -> real rates
    rows2 = [dict(r, write_ops=r["write_ops"] + 40,
                  read_ops=r["read_ops"] + 12,
                  recovery_ops=r["recovery_ops"] + 4)
             for r in rows]
    by2 = _group(rows2, owners)
    for pm, columnar in pms:
        _apply(pm, by2, 104.0, columnar)

    # pass 3: ~10% counter resets on an UNCHANGED primary (clamp at
    # 0, never negative), ~20% primary handoffs (rates must restart,
    # not derive) — disjoint residues so both paths are exercised
    owners3 = list(owners)
    rows3 = []
    for i, r in enumerate(rows2):
        r = dict(r, write_ops=r["write_ops"] + 8)
        if i % 10 == 3:
            r["write_ops"] = 1          # reset: clamp, not negative
            r["read_ops"] = 0
        if i % 5 == 0:
            owners3[i] = (owners3[i] + 7) % 24
        rows3.append(r)
    by3 = _group(rows3, owners3)
    for pm, columnar in pms:
        _apply(pm, by3, 107.0, columnar)

    now = 107.0
    _assert_digests_equal(ref.digest(now=now), col.digest(now=now))
    _assert_digests_equal(ref.digest(now=now),
                          rowwise.digest(now=now))
    assert ref.pg_state_counts(now) == col.pg_state_counts(now)
    assert ref.inconsistent_pgs(now) == col.inconsistent_pgs(now)
    # per-pgid rates agree (incl. clamp-to-0 and handoff resets)
    for i in (0, 3, 5, 13, 17, 20, 100, 2003, n - 1):
        pgid = rows[i]["pgid"]
        assert col.rates.get(pgid) == ref.rates.get(pgid), pgid
        assert rowwise.rates.get(pgid) == ref.rates.get(pgid), pgid
    # pool filter (deleted pools) agrees
    keep = {1, 2, 3}
    a = ref.pool_totals(now, keep)
    b = col.pool_totals(now, keep)
    assert set(a) == set(b)
    for pid in a:
        for k in a[pid]:
            assert b[pid][k] == pytest.approx(a[pid][k], rel=1e-9)
    # no block row ever fell back to the row loop
    assert col.ingest["fallback_rows"] == 0
    assert col.ingest["rows"]["columnar"] == 3 * n

    # prune: deleted-pool rows (all still fresh) compact out with
    # identical visible counters, and the digests still agree
    for pm, _ in pms:
        got = pm.prune(now + 10.0, pools={1, 2, 3}, after=49.0)
        assert got["stale"] == 0
        assert got["pool"] > 0
    assert col.pruned_pool == ref.pruned_pool == rowwise.pruned_pool
    _assert_digests_equal(ref.digest(now=now), col.digest(now=now))
    # everything ages out -> full stale prune, counted
    before = col.num_rows
    for pm, _ in pms:
        got = pm.prune(now + 1000.0, after=100.0)
        assert got["stale"] == before
    assert col.num_rows == 0 and not ref.pg_stats
    assert col.pruned_stale == ref.pruned_stale == before


def test_mixed_fleet_identical_digest():
    """Half the fleet ships packed blocks, half legacy dict rows: the
    digest is identical to an all-legacy fleet's (mixed-version
    clusters converge during a rollout)."""
    n = 2000
    rows, owners, _rng = _synth_fleet(n, seed=11)
    by = _group(rows, owners)
    rows2 = [dict(r, write_ops=r["write_ops"] + 24) for r in rows]
    by2 = _group(rows2, owners)

    mixed = PGMap(stale_after=1e9)
    legacy = DictPGMap(stale_after=1e9)
    for stamp, rep in ((100.0, by), (104.0, by2)):
        for i, d in enumerate(sorted(rep)):
            if i % 2:
                mixed.apply_report(
                    d, None, None, stamp,
                    pg_stats_cols=pack_stat_rows(rep[d]))
            else:
                mixed.apply_report(d, rep[d], None, stamp)
            legacy.apply_report(d, rep[d], None, stamp)
    _assert_digests_equal(legacy.digest(now=104.0),
                          mixed.digest(now=104.0))
    assert mixed.ingest["reports"]["columnar"] > 0
    assert mixed.ingest["reports"]["legacy"] > 0


def test_malformed_block_falls_back_visibly():
    """A corrupt block must not lose the report OR raise: the rows
    land through the row-wise fallback and the fallback counter
    increments (never a silent drop)."""
    rows = _golden_rows()
    blk = pack_stat_rows(rows)
    pm = PGMap(stale_after=1e9)
    pm.apply_report("osd.0", None, None, 100.0, pg_stats_cols=blk)
    assert pm.ingest["fallback_rows"] == 0
    assert pm.num_rows == len(rows)
    # unknown version: even the fallback cannot decode -> 0 rows, but
    # no exception and the report is still counted
    bad = dict(blk, v=99)
    pm.apply_report("osd.0", None, None, 104.0, pg_stats_cols=bad)
    assert pm.ingest["reports"]["columnar"] == 2
    # truncated counter column: validation rejects BEFORE any scatter
    # (nothing half-applied), both paths refuse, report still counted
    rows_before = pm.num_rows
    bad = dict(blk, ctrs=[blk["ctrs"][0][:-8]] + blk["ctrs"][1:])
    pm.apply_report("osd.0", None, None, 108.0, pg_stats_cols=bad)
    assert pm.num_rows == rows_before
    assert pm.ingest["reports"]["columnar"] == 3
    # the good block still lands afterwards (the fabric self-heals on
    # the producer's next report)
    pm.apply_report("osd.0", None, None, 112.0, pg_stats_cols=blk)
    assert pm.rates["1.1"]["write_ops_s"] == 0.0  # stamps moved on


def test_prune_then_reingest_no_ghost_rates():
    """A PG landing on a slot freed by prune() compaction must read
    as FRESH: the recycled slot's leftover _from/_stamp/_ctr must
    never feed a rate derivation (the golden DictPGMap restarts
    rates after a delete-then-recreate / age-out-then-return)."""
    col = PGMap(stale_after=1e9)
    ref = DictPGMap(stale_after=1e9)
    rows = [_full_row("1.%x" % i, 1, "active", i) for i in range(8)]
    rows2 = [dict(r, write_ops=r["write_ops"] + 40) for r in rows]
    for pm in (col, ref):
        pm.apply_report("osd.0", None, None, 100.0,
                        pg_stats_cols=pack_stat_rows(rows))
        pm.apply_report("osd.0", None, None, 104.0,
                        pg_stats_cols=pack_stat_rows(rows2))
        assert pm.rates["1.0"]["write_ops_s"] == pytest.approx(10.0)
        # everything ages out and compacts away...
        pm.prune(1000.0, after=10.0)
    assert col.num_rows == 0
    # ...then the SAME daemon re-reports the same pgids much later
    # with restarted (lower) counters — onto the recycled slots
    rows3 = [dict(r, write_ops=1, read_ops=0) for r in rows]
    for pm in (col, ref):
        pm.apply_report("osd.0", None, None, 2000.0,
                        pg_stats_cols=pack_stat_rows(rows3))
    for r in rows:
        # fresh rows: no comparable base, rates must NOT derive from
        # the dead slots' counters/stamps
        assert col.rates.get(r["pgid"]) is None, r["pgid"]
        assert ref.rates.get(r["pgid"]) is None, r["pgid"]
    # and the next delta derives normally on both paths
    rows4 = [dict(r, write_ops=81, read_ops=16) for r in rows3]
    for pm in (col, ref):
        pm.apply_report("osd.0", None, None, 2004.0,
                        pg_stats_cols=pack_stat_rows(rows4))
        assert pm.rates["1.3"]["write_ops_s"] == pytest.approx(20.0)
    _assert_digests_equal(ref.digest(now=2004.0),
                          col.digest(now=2004.0))
    assert col.ingest["fallback_rows"] == 0


def test_duplicate_pgids_in_block_fall_back_rowwise():
    """Duplicate pgids inside ONE block would make the masked scatter
    last-write-wins with a single rate derivation — not the row
    loop's per-occurrence semantics — so the block is rejected into
    the visible row-wise fallback and stays golden-identical."""
    rows = [_full_row("1.1", 1, "active", 1),
            _full_row("1.1", 1, "active", 5),
            _full_row("1.2", 1, "active", 2)]
    blk = pack_stat_rows(rows)
    pm = PGMap(stale_after=1e9)
    ref = DictPGMap(stale_after=1e9)
    for p in (pm, ref):
        p.apply_report("osd.0", None, None, 100.0,
                       pg_stats_cols=blk)
    assert pm.ingest["fallback_rows"] == len(rows)
    assert pm.num_rows == 2
    _assert_digests_equal(ref.digest(now=100.0),
                          pm.digest(now=100.0))


def test_pool_id_overflow_keeps_legacy_path():
    """pool >= 2**31 would overflow the int64 ``pool << 32`` merge
    key: the packer refuses (producer keeps dict rows) and the mgr
    routes the pgid to the synthetic string-key space instead of
    raising (or silently wrapping negative) in the report handler."""
    huge = 1 << 31
    row = _full_row("%d.0" % huge, huge, "active", 2)
    with pytest.raises(ValueError):
        pack_stat_rows([row])
    pm = PGMap(stale_after=1e9)
    ref = DictPGMap(stale_after=1e9)
    for p in (pm, ref):
        p.apply_report("osd.0", [row], None, 100.0)
        p.apply_report("osd.0", [dict(row, write_ops=row["write_ops"]
                                      + 40)], None, 104.0)
    assert pm.rates[row["pgid"]]["write_ops_s"] == pytest.approx(10.0)
    _assert_digests_equal(ref.digest(now=104.0),
                          pm.digest(now=104.0))


def test_mixed_field_report_rows_split_by_format():
    """A report carrying BOTH a columnar block and legacy dict rows
    accounts each portion under its own rows format (the bytes and
    the one report count ride the dominant columnar format)."""
    for pm in (PGMap(stale_after=1e9), DictPGMap(stale_after=1e9)):
        blk = pack_stat_rows([_full_row("1.0", 1, "active", 0)])
        legacy = [_full_row("2.0", 2, "active", 1),
                  _full_row("2.1", 2, "active", 2)]
        pm.apply_report("osd.0", legacy, None, 100.0,
                        pg_stats_cols=blk)
        assert pm.ingest["rows"] == {"columnar": 1, "legacy": 2}
        assert pm.ingest["reports"] == {"columnar": 1, "legacy": 0}
        assert pm.ingest["bytes"]["columnar"] == block_nbytes(blk)


def test_duplicate_and_odd_pgids_keep_working():
    """Odd pgid strings (legacy rows outside the canonical shape)
    still land via synthetic keys, and canonical rows keep the fast
    path beside them."""
    pm = PGMap(stale_after=1e9)
    pm.apply_report("osd.0", [
        {"pgid": "weird-pg", "pool": 9, "state": "active",
         "num_objects": 2},
        {"pgid": "9.1", "pool": 9, "state": "active",
         "num_objects": 3}], None, 100.0)
    blk = pack_stat_rows([_full_row("9.2", 9, "active", 1)])
    pm.apply_report("osd.1", None, None, 100.5, pg_stats_cols=blk)
    tot = pm.pool_totals(now=101.0)
    assert tot[9]["num_pgs"] == 3
    assert tot[9]["objects"] == 2 + 3 + 8


# -- ingest observability ----------------------------------------------------


def test_ingest_exporter_families_lint_clean():
    """The mgr ingest families (ceph_tpu_mgr_report_rows_total,
    ceph_tpu_mgr_report_bytes_total, ceph_tpu_mgr_ingest_seconds,
    ceph_tpu_mgr_ingest_fallback_rows_total,
    ceph_tpu_mgr_rows_pruned_total) render exposition-lint clean and
    carry the observed counts."""
    from ceph_tpu.mgr.daemon import ingest_prom_lines
    from ceph_tpu.utils.exporter import validate_exposition

    pm = PGMap(stale_after=5.0)
    rows = _golden_rows()
    pm.apply_report("osd.0", None, None, 100.0,
                    pg_stats_cols=pack_stat_rows(rows))
    pm.apply_report("osd.1", rows, None, 100.0)
    pm.prune(200.0, after=5.0)
    text = "\n".join(ingest_prom_lines(pm))
    assert validate_exposition(text) == []
    assert 'ceph_tpu_mgr_report_rows_total{format="columnar"} 4' \
        in text
    assert 'ceph_tpu_mgr_report_rows_total{format="legacy"} 4' \
        in text
    assert 'ceph_tpu_mgr_report_bytes_total{format="columnar"}' \
        in text
    assert "ceph_tpu_mgr_ingest_seconds_bucket" in text
    assert "ceph_tpu_mgr_ingest_fallback_rows_total 0" in text
    # 4 unique pgids (the legacy report re-reported the same PGs):
    # all 4 rows prune stale, both reporting daemons expire
    assert 'ceph_tpu_mgr_rows_pruned_total{reason="stale"} 4' \
        in text
    assert 'ceph_tpu_mgr_rows_pruned_total{reason="daemon"} 2' \
        in text


def test_registry_mgr_series_lint():
    """The drift lint holds both directions for the ingest families
    (registered <-> rendered <-> consumer-referenced)."""
    from ceph_tpu.trace import registry

    assert registry.lint_mgr_plane() == []
    # a registered-but-unrendered family fails
    orig = registry.MGR_SERIES
    registry.MGR_SERIES = frozenset(orig | {"ceph_tpu_mgr_ghost"})
    try:
        errs = registry.lint_mgr_plane()
        assert any("ghost" in e for e in errs)
    finally:
        registry.MGR_SERIES = orig


def test_report_freshness_in_digest():
    pm = PGMap(stale_after=5.0)
    pm.apply_report("osd.0", [_full_row("1.0", 1, "active", 0)],
                    None, 100.0)
    pm.apply_report("osd.1", [_full_row("1.1", 1, "active", 1)],
                    None, 106.0)
    rep = pm.digest(now=108.0)["reports"]
    assert rep["daemons"] == 2
    assert rep["max_age"] == pytest.approx(8.0)
    assert rep["max_age_daemon"] == "osd.0"
    assert rep["stale"] == 1            # osd.0 is past the window
    # DictPGMap mirrors the section
    ref = DictPGMap(stale_after=5.0)
    ref.apply_report("osd.0", [_full_row("1.0", 1, "active", 0)],
                     None, 100.0)
    ref.apply_report("osd.1", [_full_row("1.1", 1, "active", 1)],
                     None, 106.0)
    assert ref.digest(now=108.0)["reports"] == rep


# -- steady generations + sweep at tier-1 size -------------------------------


def _synth_stat_rows(n_rows, n_daemons=64, seed=23):
    """Report set grouped by daemon, in the shape a shell fleet
    sends: every int and counter column, NO scrub stamps (the float
    columns `_full_row` always carries are absent here)."""
    rng = np.random.default_rng(seed)
    pools = rng.integers(1, 13, n_rows)
    daemons = rng.integers(0, n_daemons, n_rows)
    objs = rng.integers(0, 100, n_rows)
    wops = rng.integers(0, 10000, n_rows)
    by_daemon = {}
    for i in range(n_rows):
        by_daemon.setdefault("osd.%d" % daemons[i], []).append({
            "pgid": "%d.%x" % (pools[i], i), "pool": int(pools[i]),
            "state": "active" if i % 7 else "peering",
            "num_objects": int(objs[i]),
            "num_bytes": int(objs[i]) << 20, "degraded": int(i % 5),
            "misplaced": int(objs[i]) % 3, "unfound": 0,
            "log_size": 10, "scrub_errors": int(i % 97 == 0),
            "read_ops": int(wops[i]), "read_bytes": 0,
            "write_ops": int(wops[i]),
            "write_bytes": int(wops[i]) << 12,
            "recovery_ops": 0, "recovery_bytes": 0})
    return by_daemon


def _bumped(by_daemon, w, r):
    return {d: [dict(row, write_ops=row["write_ops"] + w,
                     recovery_ops=row["recovery_ops"] + r)
                for row in rows]
            for d, rows in by_daemon.items()}


def test_columnar_ingest_steady_generations_and_sweep():
    """6,000 stampless rows through three report generations (one
    that allocates, two steady) on the columnar path and on the row
    path of the same PGMap: both digests equal DictPGMap's and no
    block row falls back; then a 9,000-PG two-generation sweep, whose
    digest counts every PG and equals the row path's."""
    by = _synth_stat_rows(6000)
    gens = ((100.0, by), (104.0, _bumped(by, 32, 8)),
            (108.0, _bumped(by, 64, 24)))
    col, rowwise = PGMap(stale_after=1e9), PGMap(stale_after=1e9)
    ref = DictPGMap(stale_after=1e9)
    for stamp, gen in gens:
        _apply(col, gen, stamp, True)
        _apply(rowwise, gen, stamp, False)
        _apply(ref, gen, stamp, False)
    want = ref.digest(now=108.0)
    assert want["num_pgs"] == 6000
    _assert_digests_equal(want, col.digest(now=108.0))
    _assert_digests_equal(want, rowwise.digest(now=108.0))
    assert col.ingest["fallback_rows"] == 0
    assert col.ingest["rows"]["columnar"] == 3 * 6000

    sweep = _synth_stat_rows(9000, seed=29)
    pm, ref = PGMap(stale_after=1e9), DictPGMap(stale_after=1e9)
    for stamp, gen in ((100.0, sweep), (104.0, _bumped(sweep, 16, 0))):
        _apply(pm, gen, stamp, True)
        _apply(ref, gen, stamp, False)
    dig = pm.digest(now=104.0)
    assert dig["num_pgs"] == 9000
    _assert_digests_equal(ref.digest(now=104.0), dig)
    assert pm.ingest["fallback_rows"] == 0


# -- e2e: columnar fleet through the real pipeline ---------------------------


def test_scale_fleet_columnar_end_to_end():
    """A small shell fleet ships packed blocks through real
    messengers: the mgr ingests them on the fast path (no fallback,
    no legacy rows), the digest fills, and `status` renders the
    report-freshness line."""
    from ceph_tpu.scale import ScaleCluster

    async def main():
        c = await ScaleCluster(16, conf={"log_level": 0}).start()
        try:
            await c.create_pool("p", pg_num=64)
            from ceph_tpu.utils.backoff import wait_for
            await wait_for(
                lambda: (c.digest() or {}).get("num_pgs") == 64,
                45.0, what="digest carrying all 64 shell PGs")
            ing = c.mgr.pgmap.ingest
            assert ing["reports"]["columnar"] > 0
            assert ing["rows"]["columnar"] >= 64
            assert ing["fallback_rows"] == 0
            # PG-less shells report rowless frames; no dict ROW ever
            # takes the legacy path in a columnar fleet
            assert ing["rows"]["legacy"] == 0
            assert ing["bytes"]["columnar"] > 0
            out = await c.mon_cmd("status")
            rep = out["pgmap"]["reports"]
            assert rep["daemons"] == 16
            assert rep["stale"] == 0
            assert rep["max_age"] < 10.0
            assert rep["max_age_daemon"].startswith("osd.")
            # the mgr scrape surface carries the ingest families
            from ceph_tpu.utils.exporter import validate_exposition
            text = c.mgr.exporter.render()
            assert validate_exposition(text) == []
            assert "ceph_tpu_mgr_report_rows_total" in text
        finally:
            await c.stop()

    run(main())


# -- scale smoke -------------------------------------------------------------


@pytest.mark.slow
def test_million_row_ingest_never_falls_back():
    """1M rows (the digest-sweep scale) through the columnar path:
    every row lands on the fast path, the digest carries all of them,
    and steady-state re-ingest beats the first-sight pass."""
    import time as _t

    n_daemons, per = 8, 125_000
    gens = []
    for gen in range(2):
        by = {}
        for d in range(n_daemons):
            rows = []
            for i in range(per):
                idx = d * per + i
                rows.append({
                    "pgid": "%d.%x" % (1 + idx % 4, idx),
                    "pool": 1 + idx % 4, "state": "active",
                    "num_objects": 8, "num_bytes": 8 << 20,
                    "degraded": 0, "misplaced": idx % 3,
                    "unfound": 0, "log_size": 0, "scrub_errors": 0,
                    "read_ops": idx + gen * 64, "read_bytes": 0,
                    "write_ops": idx + gen * 128, "write_bytes": 0,
                    "recovery_ops": 0, "recovery_bytes": 0})
            by["osd.%d" % d] = rows
        gens.append({d: pack_stat_rows(rows)
                     for d, rows in by.items()})
    pm = PGMap(stale_after=1e9)
    t0 = _t.perf_counter()
    for d, blk in gens[0].items():
        pm.apply_report(d, None, None, 100.0, pg_stats_cols=blk)
    cold_s = _t.perf_counter() - t0
    t0 = _t.perf_counter()
    for d, blk in gens[1].items():
        pm.apply_report(d, None, None, 104.0, pg_stats_cols=blk)
    steady_s = _t.perf_counter() - t0
    assert pm.num_rows == n_daemons * per
    assert pm.ingest["fallback_rows"] == 0
    assert pm.ingest["rows"]["columnar"] == 2 * n_daemons * per
    dig = pm.digest(now=104.0)
    assert dig["num_pgs"] == n_daemons * per
    assert dig["reports"]["daemons"] == n_daemons
    # the steady-state pass must stay vectorized (a silent fallback
    # to per-row work would blow these bounds by orders of magnitude)
    assert steady_s < cold_s * 2
    assert steady_s < 30.0, steady_s
