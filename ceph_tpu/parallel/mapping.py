"""Bulk PG mapping: the whole cluster's PG->OSD table in one device pass.

Replaces the reference's ParallelPGMapper thread pool
(src/osd/OSDMapMapping.h:18-120, used by the mgr and by OSDMonitor to
prime pg_temp at OSDMonitor.cc:728-735,1067): instead of sharding PG
ranges over threads, all PGs of a pool become one vector batch through
one jitted program that fuses do_rule with the whole post-CRUSH
pipeline (up-filter, compaction, primary pick, primary affinity —
OSDMap.cc:2626-2802).  Results stay dense numpy arrays per pool; the
sparse exception tables (pg_upmap*, pg_temp, primary_temp) are applied
by recomputing only the excepted PGs through the host scalar pipeline.

Falls back to the scalar pipeline per-PG when the crush map is outside
the device scope (non-straw2 buckets, local retries, a rule that mixes
firstn and indep steps or has more than one TAKE/EMIT pair).

Device dispatches route through the shared device runtime
(ceph_tpu.device.runtime) onto one mesh chip — the caller's affinity
chip when given (an OSD passes its bound chip so per-chip isolation
holds for mapping too), else the first available chip: each pool pass
is admitted under the "mapping" class (weight below client/recovery
EC, so a full-cluster remap cannot starve EC writes of the
accelerator), carries a DispatchTicket for the exporter, and degrades
to the scalar host pipeline when admission pushes back (DeviceBusy)
or the chip is in device-loss fallback.  A dispatch failure poisons
only the chip it ran on and this build finishes on the host path.
"""

from __future__ import annotations

import numpy as np

from ..device.runtime import DeviceBusy, DeviceRuntime, K_MAPPING
from ..models.crushmap import ITEM_NONE
from ..ops.crush.hashes import hash32_2_v
from ..osd.osdmap import OSD_EXISTS, OSD_UP, OSDMap, PGPool, pg_t
from ..trace.span import span
from ..utils.log import global_logger

class PoolMapping:
    """Dense up/acting arrays for one pool ([pg_num, size] int32 with
    ITEM_NONE holes; compacted rows for replicated pools)."""

    __slots__ = ("pool_id", "can_shift", "up", "up_primary", "acting",
                 "acting_primary")

    def __init__(self, pool: PGPool, up: np.ndarray,
                 up_primary: np.ndarray):
        self.pool_id = pool.id
        self.can_shift = pool.can_shift_osds()
        self.up = up
        self.up_primary = up_primary
        self.acting = up.copy()
        self.acting_primary = up_primary.copy()

    def _row(self, arr: np.ndarray, ps: int) -> list[int]:
        row = arr[ps].tolist()
        if self.can_shift:
            return [v for v in row if v != ITEM_NONE]
        return row

    def get(self, ps: int) -> tuple[list[int], int, list[int], int]:
        return (self._row(self.up, ps), int(self.up_primary[ps]),
                self._row(self.acting, ps), int(self.acting_primary[ps]))


class OSDMapMapping:
    """Caches up/acting for every PG of every pool (OSDMapMapping.h:174)
    as dense arrays."""

    def __init__(self, osdmap: OSDMap, device_mapper=None,
                 runtime=None, chip: int | None = None):
        self.epoch = osdmap.epoch
        self.pools: dict[int, PoolMapping] = {}
        self.device_pools = 0      # pools mapped on device this build
        self.scalar_pools = 0      # pools that fell back to host
        # pool id -> choose steps of its rule that the device pass ran
        # (MapState.steps); a pool the host mapped has no entry
        self.rule_steps: dict[int, int] = {}
        with span("crush.build", pools=len(osdmap.pools)):
            self._build(osdmap, device_mapper, runtime, chip)

    def _build(self, osdmap: OSDMap, device_mapper, runtime,
               chip: int | None) -> None:
        state = np.asarray(osdmap.osd_state, dtype=np.int32)
        exists = (state & OSD_EXISTS) != 0
        isup = (state & OSD_UP) != 0
        aff = (np.asarray(osdmap.osd_primary_affinity, dtype=np.int32)
               if osdmap.osd_primary_affinity is not None else None)
        dm = device_mapper
        rt = runtime or DeviceRuntime.get()
        for pool in osdmap.pools.values():
            try:
                target = rt.route(chip)
                if target is None or not target.available:
                    raise ValueError("mapping chip in fallback")
                if dm is None:
                    dm = osdmap.device_mapper()
                up, prim = self._map_pool_ticketed(
                    osdmap, pool, dm, target, exists, isup, aff)
            except (ValueError, DeviceBusy) as e:
                # outside device scope, admission pushback, or
                # device-loss fallback: the scalar pipeline is the
                # always-correct degradation — and a Python loop per
                # PG, so it never starts unannounced
                global_logger().error(
                    "mapping", "pool %d (%d PGs) mapped by the scalar "
                    "host pipeline: %r" % (pool.id, pool.pg_num, e))
                up, prim = self._map_pool_scalar(osdmap, pool)
                self.scalar_pools += 1
            else:
                self.device_pools += 1
            with span("crush.tables"):
                pm = PoolMapping(pool, up, prim)
                self._apply_exceptions(osdmap, pool, pm)
                self.pools[pool.id] = pm

    def _map_pool_ticketed(self, osdmap, pool, dm, chip,
                           exists, isup, aff):
        """One pool pass under a mapping-class dispatch ticket on the
        routed chip.  Sync context (map advance runs outside any op
        coroutine), so admission is the non-blocking form — a full
        dispatch queue degrades this pass to the scalar path rather
        than queueing device work behind EC flushes."""
        ticket = chip.open_ticket(K_MAPPING,
                                  chip.rt.bucket_for(pool.pg_num),
                                  pool.pg_num * pool.size * 4)
        chip.try_admit(ticket)
        try:
            chip.launch(ticket)     # injected-fault hook
            with chip.scope():
                up, prim = self._map_pool_device(osdmap, pool, dm,
                                                 exists, isup, aff)
        except ValueError:
            # map outside device scope: a scalar-fallback condition,
            # not a device loss
            chip.finish(ticket, ok=False)
            raise
        except Exception as e:      # DeviceLost + real device faults
            chip.finish(ticket, ok=False, error=e)
            chip.poison(e)
            raise ValueError("device mapping dispatch failed") from e
        chip.finish(ticket, ok=True)
        return up, prim

    # -- vectorized pool mapping ------------------------------------------

    def _map_pool_device(self, osdmap: OSDMap, pool: PGPool, dm,
                         exists, isup, aff):
        from ..osd.osdmap import FLAG_HASHPSPOOL
        state = dm.map_pool_state(
            pool.crush_rule, pool.size, pool.pg_num, pool.pgp_num,
            pool.pgp_num_mask, pool.id,
            bool(pool.flags & FLAG_HASHPSPOOL), osdmap.osd_weight,
            exists, isup, aff, can_shift=pool.can_shift_osds())
        self.rule_steps[pool.id] = state.steps
        return dm.read_tables(state)

    # -- scalar fallback ---------------------------------------------------

    def _map_pool_scalar(self, osdmap: OSDMap, pool: PGPool):
        up = np.full((pool.pg_num, pool.size), ITEM_NONE, np.int32)
        prim = np.full((pool.pg_num,), -1, np.int32)
        for ps in range(pool.pg_num):
            pg = pg_t(pool.id, ps)
            raw, pps = osdmap._pg_to_raw_osds(pool, pg)
            row = osdmap._raw_to_up_osds(pool, raw)
            p = osdmap._pick_primary(row)
            p = osdmap._apply_primary_affinity(pps, pool, row, p)
            up[ps, :len(row)] = row
            prim[ps] = p
        return up, prim

    # -- sparse exceptions -------------------------------------------------

    def _apply_exceptions(self, osdmap: OSDMap, pool: PGPool,
                          pm: PoolMapping) -> None:
        """Recompute the (few) PGs carrying upmap/temp entries through
        the exact scalar pipeline and overwrite their rows."""
        excepted: set[int] = set()
        for table in (osdmap.pg_upmap, osdmap.pg_upmap_items,
                      osdmap.pg_upmap_primaries, osdmap.pg_temp,
                      osdmap.primary_temp):
            for pg in table:
                if pg.pool == pool.id and pg.ps < pool.pg_num:
                    excepted.add(pg.ps)
        for ps in excepted:
            pg = pg_t(pool.id, ps)
            up, upp, acting, actingp = osdmap.pg_to_up_acting_osds(pg)
            self._write_row(pm.up, ps, up)
            pm.up_primary[ps] = upp
            self._write_row(pm.acting, ps, acting)
            pm.acting_primary[ps] = actingp

    @staticmethod
    def _write_row(arr: np.ndarray, ps: int, vals: list[int]) -> None:
        n = min(len(vals), arr.shape[1])
        arr[ps, :n] = vals[:n]
        arr[ps, n:] = ITEM_NONE

    # -- lookup ------------------------------------------------------------

    def get(self, pg: pg_t) -> tuple[list[int], int, list[int], int]:
        pm = self.pools.get(pg.pool)
        if pm is None or pg.ps >= pm.up.shape[0]:
            return [], -1, [], -1
        return pm.get(pg.ps)


def pps_for_pool(pool: PGPool, ps: np.ndarray) -> np.ndarray:
    """Vectorized raw_pg_to_pps over a pool's ps range."""
    from ..ops.crush.hashes import pps_seed_v
    from ..osd.osdmap import FLAG_HASHPSPOOL
    return pps_seed_v(ps, pool.pgp_num, pool.pgp_num_mask, pool.id,
                      bool(pool.flags & FLAG_HASHPSPOOL))
