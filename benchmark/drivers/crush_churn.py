"""Reweight churn on one large replicated pool: one full-pool remap after
another for the whole window.

Every step applies an incremental that marks `osds_out_per_step` seeded
OSDs out (weight 0) and the previous step's back in, then builds
`ceph_tpu.parallel.mapping.OSDMapMapping(osdmap, runtime=rt)`; the step
ends when up and acting are numpy arrays on the host.  The window closes
at the end of the first remap that finishes past `--seconds`, so that
`remap_s` is the whole window over whole remaps.

The map is chip_smoke.py's (bench.py's before it): hosts of equal OSDs
under one root, straw2, `chooseleaf firstn 0 type host`.  The schedule of
out sets is drawn from the seed before the window and shared with the
plain reference, which rebuilds each epoch's weights from it alone.
"""

import time

import numpy as np

from ..harness import trace
from ..reference import crush_ref
from .program import host_fallbacks


def build_osdmap(hosts: int, per_host: int, pool: dict):
    from ceph_tpu.models.crushmap import (CHOOSELEAF_FIRSTN, EMIT, STRAW2,
                                          TAKE, CrushMap)
    from ceph_tpu.osd.osdmap import (OSD_EXISTS, OSD_UP, Incremental,
                                     OSDMap, PGPool)
    crush, host_ids = CrushMap(), []
    for h in range(hosts):
        b = crush.add_bucket(
            STRAW2, 1, list(range(h * per_host, (h + 1) * per_host)),
            [0x10000] * per_host, id=-(h + 2))
        host_ids.append(b.id)
    crush.add_bucket(STRAW2, 2, host_ids,
                     [crush.buckets[h].weight for h in host_ids], id=-1)
    crush.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1), (EMIT, 0, 0)],
                   id=0)
    m = OSDMap()
    inc = Incremental(epoch=1)
    inc.new_max_osd = hosts * per_host
    inc.new_crush = crush
    inc.new_pools[pool["id"]] = PGPool(
        id=pool["id"], name="bench", pg_num=pool["pg_num"],
        size=pool["size"], crush_rule=0)
    m.apply_incremental(inc)
    inc = m.new_incremental()
    for o in range(m.max_osd):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = 0x10000
    m.apply_incremental(inc)
    return m


def schedule(seed: int, n_osds: int, per_step: int, steps: int) -> list:
    """The out set of every step; no OSD is in two consecutive sets, so
    each step changes exactly 2 * per_step weights."""
    rng = np.random.default_rng([seed, n_osds])
    sets, prev = [], set()
    for _ in range(steps):
        free = np.array([o for o in range(n_osds) if o not in prev])
        prev = {int(o) for o in rng.choice(free, per_step, replace=False)}
        sets.append(sorted(prev))
    return sets


def run(s) -> None:
    from ceph_tpu.device.runtime import DeviceRuntime
    from ceph_tpu.parallel.mapping import OSDMapMapping
    cfg, mix = s.config, s.mix
    hosts, per_host = cfg["crush"]["hosts"], cfg["crush"]["osds_per_host"]
    pool, per_step = cfg["pool"], mix["osds_out_per_step"]
    rt = DeviceRuntime.get()
    outs = schedule(s.seed, hosts * per_host, per_step, 1024)
    rng = np.random.default_rng([s.seed, 1])
    sample = np.sort(rng.choice(pool["pg_num"], mix["sample_pgs"],
                                replace=False))
    m = build_osdmap(hosts, per_host, pool)
    steps = []      # (out set, sampled rows, device_pools, scalar_pools)

    def remap(step: int):
        with trace.span("remap.incremental"):
            inc = m.new_incremental()
            for o in (outs[(step - 1) % len(outs)] if step else []):
                inc.new_weight[o] = 0x10000
            for o in outs[step % len(outs)]:
                inc.new_weight[o] = 0
            m.apply_incremental(inc)
        with trace.span("remap.mapping"):
            mp = OSDMapMapping(m, runtime=rt)
        pm = mp.pools[pool["id"]]
        steps.append((outs[step % len(outs)],
                      (pm.up[sample], pm.up_primary[sample],
                       pm.acting[sample], pm.acting_primary[sample]),
                      mp.device_pools, mp.scalar_pools))

    with trace.span("setup"):
        OSDMapMapping(m, runtime=rt)        # cold: every program of the pool
        for step in range(mix["warm_steps"]):
            remap(step)
    first = len(steps)
    d0 = rt.dispatches
    t0 = s.open_window()
    while time.monotonic() - t0 < s.seconds:
        remap(len(steps))
    window_s = s.close_window()
    remaps = len(steps) - first
    s.read_memory_peak()
    s.attempted, s.failed = remaps, 0
    s.end_to_end["remap_s"] = window_s / remaps
    s.facts.update(remaps=remaps, dispatches=rt.dispatches - d0,
                   pg_num=pool["pg_num"])

    with trace.span("correctness"):
        ref = crush_ref.Map.uniform(hosts, per_host)
        n_osds, wrong = hosts * per_host, 0
        all_up = [True] * n_osds
        for out, rows, _dp, _sp in (steps[first], steps[-1]):
            weight = [0x10000] * n_osds
            for o in out:
                weight[o] = 0
            for i, ps in enumerate(sample):
                want = crush_ref.pg_to_up_acting(
                    ref, pool["id"], pool["pg_num"], pool["size"], int(ps),
                    weight, all_up)
                got = ([int(o) for o in rows[0][i] if o >= 0 and o < n_osds],
                       int(rows[1][i]),
                       [int(o) for o in rows[2][i] if o >= 0 and o < n_osds],
                       int(rows[3][i]))
                wrong += got != want
        s.compare("mismatched_pgs", wrong, 0)
        s.compare("pgs_compared", 2 * len(sample), 1, ">=")
        s.compare("scalar_pools", sum(st[3] for st in steps[first:]), 0)
        s.compare("device_pools_per_remap",
                  min(st[2] for st in steps[first:]), 1, ">=")
        s.compare("dispatches_in_window", s.facts["dispatches"], 1, ">=")
        s.compare("host_fallbacks", host_fallbacks(rt), 0)
