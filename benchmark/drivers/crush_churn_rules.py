"""Reweight churn on one large erasure pool whose rule has two steps: one
full-pool remap after another for the whole window.

The map has three levels (root -> racks -> hosts -> OSDs, straw2, named
types), the pool's rule is the one its codec makes from the profile
(`create_rule`: for LRC with crush-locality=rack, `choose indep 2 type
rack; chooseleaf indep 4 type host`), and every step of the window is
crush_churn.py's: an incremental marks `osds_out_per_step` seeded OSDs out
and the previous step's back in, then `OSDMapMapping(osdmap, runtime=rt)`
is built; the step ends when up and acting are numpy arrays on the host.

Before any pool is mapped the driver ends the run with no result when the
codec has no `create_rule`, when the rule it makes is not the
configuration's text, or when the device mapper does not take the rule: a
4M-PG pool must never reach OSDMapMapping's scalar loop, hours of Python.

The rows are compared with benchmark/reference/crush_rules_ref.py position
by position, holes included; locality (distinct hosts, each half of a row
in one rack, the halves in two) is checked from the map alone.
"""

import sys
import time

import numpy as np

from ..harness import trace
from ..reference import crush_rules_ref as ref
from .crush_churn import schedule
from .program import host_fallbacks

PROBE_LANES = 4096


def refuse(why: str):
    print("benchmark: crush_churn_rules: %s" % why, file=sys.stderr)
    raise SystemExit(3)


def build_crush(cfg: dict):
    """The three-level map through CrushMap.add_bucket, types named.
    Host h of rack r holds OSDs ((r * hosts + h) * osds ...); the root is
    bucket -1, rack r is -(2 + r), its host h -(2 + racks + r * hosts + h)."""
    from ceph_tpu.models.crushmap import STRAW2, CrushMap
    c = cfg["crush"]
    racks, hosts, osds = c["racks"], c["hosts_per_rack"], c["osds_per_host"]
    w, types = c["osd_weight"], c["types"]
    crush = CrushMap()
    crush.types = {tid: name for name, tid in types.items()}
    rack_ids = []
    for r in range(racks):
        host_ids = []
        for h in range(hosts):
            first = (r * hosts + h) * osds
            b = crush.add_bucket(
                STRAW2, types["host"], list(range(first, first + osds)),
                [w] * osds, id=-(2 + racks + r * hosts + h),
                name="host%d-%d" % (r, h))
            host_ids.append(b.id)
        b = crush.add_bucket(
            STRAW2, types["rack"], host_ids,
            [crush.buckets[h].weight for h in host_ids], id=-(2 + r),
            name="rack%d" % r)
        rack_ids.append(b.id)
    crush.add_bucket(STRAW2, types["root"], rack_ids,
                     [crush.buckets[r].weight for r in rack_ids], id=-1,
                     name=c["root"])
    return crush


def rule_text(crush, ruleno: int) -> list:
    """The rule as crushtool would print its steps."""
    from ceph_tpu.models import crushmap as cm
    sets = {cm.SET_CHOOSE_TRIES: "set_choose_tries",
            cm.SET_CHOOSELEAF_TRIES: "set_chooseleaf_tries",
            cm.SET_CHOOSELEAF_VARY_R: "set_chooseleaf_vary_r",
            cm.SET_CHOOSELEAF_STABLE: "set_chooseleaf_stable"}
    chooses = {cm.CHOOSE_FIRSTN: "choose firstn",
               cm.CHOOSE_INDEP: "choose indep",
               cm.CHOOSELEAF_FIRSTN: "chooseleaf firstn",
               cm.CHOOSELEAF_INDEP: "chooseleaf indep"}
    out = []
    for op, a1, a2 in crush.rules[ruleno].steps:
        if op == cm.TAKE:
            out.append("take %s" % crush.buckets[a1].name)
        elif op == cm.EMIT:
            out.append("emit")
        elif op in sets:
            out.append("%s %d" % (sets[op], a1))
        elif op in chooses:
            out.append("%s %d type %s" % (chooses[op], a1, crush.types[a2]))
        else:
            out.append("op %d %d %d" % (op, a1, a2))
    return out


def make_rule(cfg: dict, crush) -> int:
    """The pool's rule, made by its codec from the profile."""
    from ceph_tpu.ec.plugin import ErasureCodePluginRegistry
    profile = dict(cfg["profile"])
    codec = ErasureCodePluginRegistry.instance().factory(
        profile["plugin"], profile)
    if not callable(getattr(codec, "create_rule", None)):
        refuse("the %s codec has no create_rule" % profile["plugin"])
    if codec.get_chunk_count() != cfg["pool"]["size"]:
        refuse("the codec has %d chunks, the pool's size is %d"
               % (codec.get_chunk_count(), cfg["pool"]["size"]))
    ruleno = codec.create_rule("bench", crush)
    made = rule_text(crush, ruleno)
    if made != cfg["rule"]:
        refuse("create_rule made %r, the configuration says %r"
               % (made, cfg["rule"]))
    return ruleno


def build_osdmap(cfg: dict):
    """(osdmap, crush rule id) with every OSD existing, up and in."""
    from ceph_tpu.osd.osdmap import (OSD_EXISTS, OSD_UP, POOL_TYPE_ERASURE,
                                     Incremental, OSDMap, PGPool)
    crush, pool = build_crush(cfg), cfg["pool"]
    ruleno = make_rule(cfg, crush)
    m = OSDMap()
    inc = Incremental(epoch=1)
    inc.new_max_osd = crush.max_devices
    inc.new_crush = crush
    inc.new_pools[pool["id"]] = PGPool(
        id=pool["id"], name="bench", type=POOL_TYPE_ERASURE,
        pg_num=pool["pg_num"], size=pool["size"],
        min_size=int(cfg["profile"]["k"]) + 1, crush_rule=ruleno)
    m.apply_incremental(inc)
    inc = m.new_incremental()
    for o in range(m.max_osd):
        inc.new_state[o] = OSD_EXISTS | OSD_UP
        inc.new_weight[o] = cfg["crush"]["osd_weight"]
    m.apply_incremental(inc)
    return m, ruleno


def probe(m, ruleno: int, size: int) -> None:
    """Ends the run unless the device mapper takes the rule: a small
    batch through do_rule_batch, before OSDMapMapping is ever built."""
    try:
        dm = m.device_mapper()
        rows = dm.do_rule_batch(ruleno, np.arange(PROBE_LANES), size,
                                np.asarray(m.osd_weight, dtype=np.int32))
    except ValueError as e:
        refuse("the device mapper does not take the pool's rule: %s" % e)
    if rows.shape != (PROBE_LANES, size):
        refuse("the device mapper's rows are %r" % (rows.shape,))


def reference_of(crush, ruleno: int) -> tuple:
    """The program's map and rule as the plain reference takes them:
    integers and names, nothing else of the program."""
    from ceph_tpu.models import crushmap as cm
    names = {cm.TAKE: ref.TAKE, cm.EMIT: ref.EMIT,
             cm.CHOOSE_FIRSTN: ref.CHOOSE_FIRSTN,
             cm.CHOOSE_INDEP: ref.CHOOSE_INDEP,
             cm.CHOOSELEAF_FIRSTN: ref.CHOOSELEAF_FIRSTN,
             cm.CHOOSELEAF_INDEP: ref.CHOOSELEAF_INDEP,
             cm.SET_CHOOSE_TRIES: ref.SET_CHOOSE_TRIES,
             cm.SET_CHOOSELEAF_TRIES: ref.SET_CHOOSELEAF_TRIES}
    buckets = {b.id: (b.type, list(b.items), list(b.item_weights))
               for b in crush.buckets.values()}
    steps = [(names[op], a1, a2) for op, a1, a2 in crush.rules[ruleno].steps]
    return ref.Map(buckets, crush.max_devices), steps


def locality_violations(crush, rows: np.ndarray, types: dict) -> int:
    """Rows that break what the profile promises, read from the map
    alone: the OSDs present on distinct hosts, positions 0-3 in one rack,
    positions 4-7 in one other."""
    parent = {i: b.id for b in crush.buckets.values() for i in b.items}
    bad = 0
    for row in rows:
        osds = [int(o) for o in row]
        there = [o for o in osds if o != ref.NONE]
        if any(o not in parent for o in there):
            bad += 1
            continue
        hosts = [parent[o] for o in there]
        half = len(osds) // 2
        racks = [{parent[parent[o]] for o in osds[lo:lo + half]
                  if o != ref.NONE} for lo in (0, half)]
        bad += (len(set(hosts)) != len(hosts)
                or any(crush.buckets[h].type != types["host"] for h in hosts)
                or any(len(r) > 1 for r in racks)
                or bool(racks[0] & racks[1]))
    return bad


def run(s) -> None:
    from ceph_tpu.device.runtime import DeviceRuntime
    from ceph_tpu.osd.osdmap import OSD_UP
    cfg, mix = s.config, s.mix
    pool, per_step = cfg["pool"], mix["osds_out_per_step"]
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
    steps = []  # (out set, sampled rows, device_pools, scalar_pools, steps)

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
                      mp.rule_steps.get(pool["id"], 0)))

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
        wrong = local = holes = 0
        for out, rows, _dp, _sp, _st in (steps[first], steps[-1]):
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
            local += locality_violations(m.crush, rows[0],
                                         cfg["crush"]["types"])
            holes += int((rows[0] == ref.NONE).sum())
        s.facts["none_slots_sampled"] = holes
        s.compare("mismatched_pgs", wrong, 0)
        s.compare("pgs_compared", 2 * len(sample), 2 * mix["sample_pgs"],
                  ">=")
        s.compare("locality_violations", local, 0)
        s.compare("scalar_pools", sum(st[3] for st in steps[first:]), 0)
        s.compare("device_pools_per_remap",
                  min(st[2] for st in steps[first:]), 1, ">=")
        s.compare("rule_steps_on_device",
                  min(st[4] for st in steps[first:]), 2, ">=")
        s.compare("dispatches_in_window", s.facts["dispatches"], 1, ">=")
        s.compare("host_fallbacks", host_fallbacks(rt), 0)
