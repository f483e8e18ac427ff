"""Reweight churn on a stretch cluster's pool: one full-pool remap after
another for the whole window.

The map is two datacenters under one root (root -> datacenters -> hosts
-> OSDs, straw2, upstream's default type ids), written as crushtool text
together with the pool's rule and compiled by ceph_tpu.models.
crushcompiler, as an operator installs a hand-written stretch rule.  The
rule has two TAKE/EMIT blocks (`take site1; chooseleaf firstn 2 type
host; emit; take site2; chooseleaf firstn 2 type host; emit`); the pool
is replicated, size 4.  Every step of the window is crush_churn.py's: an
incremental marks `osds_out_per_step` seeded OSDs out and the previous
step's back in, then `OSDMapMapping(osdmap, runtime=rt)` is built; the
step ends when up and acting are numpy arrays on the host.

Before any pool is mapped the driver ends the run with no result when
the compiled rule is not the configuration's text or when the device
mapper does not take the rule (crush_churn_rules.probe): a 10M-PG pool
must never reach OSDMapMapping's scalar loop, hours of Python.

The process keeps the memory it frees (keep_freed_memory) before any of
it is mapped, as the tcmalloc that upstream's daemons link does.

The rows are compared with benchmark/reference/crush_stretch_ref.py; that
each row has two OSDs in each datacenter, in the rule's order, on four
distinct hosts is checked from the map alone.
"""

import ctypes
import time

import numpy as np

from ..harness import trace
from ..reference import crush_stretch_ref as ref
from .crush_churn_rules import probe, reference_of, refuse, rule_text
from .crush_churn_rules import schedule
from .program import host_fallbacks


M_TRIM_THRESHOLD, M_MMAP_MAX = -1, -4     # glibc's mallopt parameters


def keep_freed_memory() -> None:
    """Has glibc's malloc keep freed blocks for the next allocation, as
    tcmalloc, which upstream's daemons link, keeps freed spans.  Every
    remap reads back and copies [pg_num, 4] tables anew, 600 MB in all;
    by default glibc maps a block that large from the kernel and unmaps
    it at free, so each remap faults every page of them in again.  On a
    TPU v5e host a 160 MB copy takes 0.17 s into fresh memory and 0.01 s
    into memory kept, and a remap's host time 0.52 s against 0.12 s.
    Nothing is kept where the C library is not glibc."""
    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return
    mallopt(M_MMAP_MAX, 0)              # large blocks from the heap
    mallopt(M_TRIM_THRESHOLD, 1 << 30)  # and the heap's top kept


def crush_text(cfg: dict) -> str:
    """The map and the pool's rule as crushtool's text.  Datacenter s
    is bucket -(2 + s), its host h -(2 + sites + s * hosts + h) holding
    OSDs ((s * hosts + h) * osds ...); the root is bucket -1."""
    c = cfg["crush"]
    sites, hosts, osds = (c["datacenters"], c["hosts_per_datacenter"],
                          c["osds_per_host"])
    weight = c["osd_weight"] / 0x10000
    lines = ["tunable %s %d" % kv for kv in c["tunables"].items()]
    lines += ["device %d osd.%d" % (o, o)
              for o in range(len(sites) * hosts * osds)]
    lines += ["type %d %s" % (tid, name)
              for name, tid in sorted(c["types"].items(),
                                      key=lambda kv: kv[1])]

    def bucket(kind, name, bid, items):
        return (["%s %s {" % (kind, name), "id %d" % bid,
                 "alg %s" % c["bucket_alg"], "hash 0"]
                + ["item %s weight %.5f" % it for it in items] + ["}"])

    for s, site in enumerate(sites):
        names = []
        for h in range(hosts):
            first = (s * hosts + h) * osds
            names.append("%s-host%d" % (site, h))
            lines += bucket("host", names[-1],
                            -(2 + len(sites) + s * hosts + h),
                            [("osd.%d" % o, weight)
                             for o in range(first, first + osds)])
        lines += bucket("datacenter", site, -(2 + s),
                        [(n, weight * osds) for n in names])
    lines += bucket("root", c["root"], -1,
                    [(site, weight * osds * hosts) for site in sites])
    lines += ["rule %s {" % cfg["rule_name"], "id 1", "type replicated"]
    lines += ["step " + step for step in cfg["rule"]] + ["}"]
    return "\n".join(lines) + "\n"


def build_osdmap(cfg: dict):
    """(osdmap, crush rule id) with every OSD existing, up and in; ends
    the run if the compiled rule is not the configuration's."""
    from ceph_tpu.models import crushcompiler
    from ceph_tpu.osd.osdmap import (OSD_EXISTS, OSD_UP, Incremental,
                                     OSDMap, PGPool)
    crush, pool = crushcompiler.compile(crush_text(cfg)), cfg["pool"]
    ruleno = next(r.id for r in crush.rules.values()
                  if r.name == cfg["rule_name"])
    made = rule_text(crush, ruleno)
    if made != cfg["rule"]:
        refuse("the compiled rule is %r, the configuration says %r"
               % (made, cfg["rule"]))
    m = OSDMap()
    inc = Incremental(epoch=1)
    inc.new_max_osd = crush.max_devices
    inc.new_crush = crush
    inc.new_pools[pool["id"]] = PGPool(
        id=pool["id"], name="bench", pg_num=pool["pg_num"],
        size=pool["size"], min_size=pool["min_size"], crush_rule=ruleno)
    m.apply_incremental(inc)
    inc = m.new_incremental()
    for o in range(m.max_osd):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = cfg["crush"]["osd_weight"]
    m.apply_incremental(inc)
    return m, ruleno


def site_violations(crush, rows: np.ndarray, sites: list) -> int:
    """Rows that break what the rule promises, read from the map alone:
    each datacenter's share of the row in the rule's order (positions
    0-1 under the first, 2-3 under the second), every OSD on a host of
    its own."""
    parent = {i: b.id for b in crush.buckets.values() for i in b.items}
    site_id = {b.name: b.id for b in crush.buckets.values()}
    want = [site_id[s] for s in sites
            for _ in range(len(rows[0]) // len(sites))]
    bad = 0
    for row in rows:
        osds = [int(o) for o in row]
        if any(o not in parent for o in osds):
            bad += 1
            continue
        hosts = [parent[o] for o in osds]
        bad += (len(set(hosts)) != len(hosts)
                or [parent[h] for h in hosts] != want)
    return bad


def run(s) -> None:
    from ceph_tpu.device.runtime import DeviceRuntime
    from ceph_tpu.osd.osdmap import OSD_UP
    cfg, mix = s.config, s.mix
    pool, per_step = cfg["pool"], mix["osds_out_per_step"]
    keep_freed_memory()
    t_map = time.monotonic()
    m, ruleno = build_osdmap(cfg)
    t_probe = time.monotonic()
    probe(m, ruleno, pool["size"])
    t_cold = time.monotonic()
    from ceph_tpu.parallel.mapping import OSDMapMapping
    rt = DeviceRuntime.get()
    n_osds = m.max_osd
    outs = schedule(s.seed, n_osds, per_step, 256)    # wraps, if ever
    rng = np.random.default_rng([s.seed, 1])
    sample = np.sort(rng.choice(pool["pg_num"], mix["sample_pgs"],
                                replace=False))
    steps = []  # (out set, sampled rows, device_pools, scalar_pools, blocks)

    def remap(step: int):
        with trace.span("remap.incremental"):
            inc = m.new_incremental()
            for o in (outs[(step - 1) % len(outs)] if step else []):
                inc.new_weight[o] = cfg["crush"]["osd_weight"]
            for o in outs[step % len(outs)]:
                inc.new_weight[o] = 0
            m.apply_incremental(inc)
        with trace.span("remap.mapping"):
            mp = OSDMapMapping(m, runtime=rt)
        pm = mp.pools[pool["id"]]
        steps.append((outs[step % len(outs)],
                      (pm.up[sample], pm.up_primary[sample],
                       pm.acting[sample], pm.acting_primary[sample]),
                      mp.device_pools, mp.scalar_pools,
                      mp.rule_blocks.get(pool["id"], 0)))

    with trace.span("setup"):
        OSDMapMapping(m, runtime=rt)        # cold: every program of the pool
        t_warm = time.monotonic()
        for step in range(mix["warm_steps"]):
            remap(step)
    # where set-up's seconds go: before the driver (imports, the chip),
    # the map and its rule, the probe's two programs, the pool's two
    s.facts["setup_parts_s"] = {
        "before_driver": round(t_map - s.t_start, 3),
        "map_and_rule": round(t_probe - t_map, 3),
        "probe": round(t_cold - t_probe, 3),
        "first_pass": round(t_warm - t_cold, 3),
        "warm_steps": round(time.monotonic() - t_warm, 3)}
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
        crush_ref, rule = reference_of(m.crush, ruleno)
        osd_up = [bool(st & OSD_UP) for st in m.osd_state]
        wrong = sites = 0
        for out, rows, _dp, _sp, _bl in (steps[first], steps[-1]):
            weight = [cfg["crush"]["osd_weight"]] * n_osds
            for o in out:
                weight[o] = 0
            for i, ps in enumerate(sample):
                want = ref.pg_to_up_acting(
                    crush_ref, rule, pool["id"], pool["pg_num"],
                    pool["size"], int(ps), weight, osd_up)
                got = ([int(o) for o in rows[0][i]], int(rows[1][i]),
                       [int(o) for o in rows[2][i]], int(rows[3][i]))
                wrong += got != want
            sites += site_violations(m.crush, rows[0],
                                     cfg["crush"]["datacenters"])
        s.compare("mismatched_pgs", wrong, 0)
        s.compare("pgs_compared", 2 * len(sample), 2 * mix["sample_pgs"],
                  ">=")
        s.compare("site_violations", sites, 0)
        s.compare("scalar_pools", sum(st[3] for st in steps[first:]), 0)
        s.compare("device_pools_per_remap",
                  min(st[2] for st in steps[first:]), 1, ">=")
        s.compare("rule_blocks_on_device",
                  min(st[4] for st in steps[first:]), 2, ">=")
        s.compare("dispatches_in_window", s.facts["dispatches"], 1, ">=")
        s.compare("host_fallbacks", host_fallbacks(rt), 0)
