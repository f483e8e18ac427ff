"""Plain reference for the crush cells: one PG at a time, Python integers.

It maps a PG of a replicated pool on a two-level straw2 map (root ->
hosts -> OSDs) under the rule `take root; chooseleaf firstn 0 type host;
emit` with the optimal tunables (choose_total_tries 50,
chooseleaf_descend_once 1, chooseleaf_vary_r 1, chooseleaf_stable 1, no
local retries), then runs the OSDMap pipeline for a pool with no upmap,
temp or primary-affinity entries: up is the raw mapping less the OSDs that
are down, acting is up, the primary is the first.

Semantics from src/crush/mapper.c (crush_hash32_2/3, crush_ln,
bucket_straw2_choose, is_out, crush_choose_firstn) and src/osd/OSDMap.cc
(_pg_to_raw_osds, _raw_to_up_osds), written for this one rule shape with
the retry loop as the C code runs it under those tunables.  The log
tables are data beside this file.  Imports nothing of the program.

Its witness is upstream's C, not the program: benchmark/tests/
test_reference.py holds the hashes, crush_ln and do_rule to the outputs of
the C oracle kept in benchmark/tests/data/upstream_crush_golden.json, and
the tables to their closed forms.
"""

import json
import os

M32 = 0xFFFFFFFF
SEED = 1315423911
S64_MIN = -(1 << 63)

with open(os.path.join(os.path.dirname(__file__),
                       "crush_ln_tables.json")) as _f:
    _T = json.load(_f)
RH_LH_TBL, LL_TBL = _T["RH_LH_TBL"], _T["LL_TBL"]


def _mix(a, b, c):
    a = (a - b) & M32; a = (a - c) & M32; a ^= c >> 13
    b = (b - c) & M32; b = (b - a) & M32; b = (b ^ (a << 8)) & M32
    c = (c - a) & M32; c = (c - b) & M32; c ^= b >> 13
    a = (a - b) & M32; a = (a - c) & M32; a ^= c >> 12
    b = (b - c) & M32; b = (b - a) & M32; b = (b ^ (a << 16)) & M32
    c = (c - a) & M32; c = (c - b) & M32; c ^= b >> 5
    a = (a - b) & M32; a = (a - c) & M32; a ^= c >> 3
    b = (b - c) & M32; b = (b - a) & M32; b = (b ^ (a << 10)) & M32
    c = (c - a) & M32; c = (c - b) & M32; c ^= b >> 15
    return a, b, c


def hash32_2(a, b):
    a &= M32; b &= M32
    h = (SEED ^ a ^ b) & M32
    x, y = 231232, 1232
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a, b, c):
    a &= M32; b &= M32; c &= M32
    h = (SEED ^ a ^ b ^ c) & M32
    x, y = 231232, 1232
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def crush_ln(xin):
    """2^44 * log2(xin + 1) in fixed point."""
    x = xin + 1
    iexpon = 15
    if not (x & 0x18000):
        bits = 16 - x.bit_length()
        x <<= bits
        iexpon = 15 - bits
    index1 = (x >> 8) << 1
    rh = RH_LH_TBL[index1 - 256]
    lh = RH_LH_TBL[index1 + 1 - 256]
    xl64 = ((x * rh) & ((1 << 64) - 1)) >> 48
    lh = (lh + LL_TBL[xl64 & 0xFF]) >> 4
    return (iexpon << 44) + lh


def straw2(items, weights, x, r):
    """The item with the highest ln(u)/weight draw; C division truncates."""
    high, high_draw = 0, 0
    for i, (item, w) in enumerate(zip(items, weights)):
        if w:
            ln = crush_ln(hash32_3(x, item, r) & 0xFFFF) - 0x1000000000000
            q = abs(ln) // w
            draw = -q if ln < 0 else q
        else:
            draw = S64_MIN
        if i == 0 or draw > high_draw:
            high, high_draw = i, draw
    return items[high]


def is_out(osd_weight, item, x):
    w = osd_weight[item]
    if w >= 0x10000:
        return False
    if w == 0:
        return True
    return (hash32_2(x, item) & 0xFFFF) >= w


class Map:
    """Two levels of straw2 buckets: `root` is (items, weights) over host
    bucket ids, `hosts` maps each id to (OSDs, weights); weights are
    16.16 fixed point."""

    TRIES = 51      # choose_total_tries + 1, the historical off-by-one

    def __init__(self, root: tuple, hosts: dict):
        self.root, self.root_w = root
        self.hosts = hosts

    @classmethod
    def uniform(cls, hosts: int, osds_per_host: int) -> "Map":
        """The benchmark's map: every OSD of crush weight 1.0; host h is
        bucket -(h + 2), the root is bucket -1."""
        ids = [-(h + 2) for h in range(hosts)]
        return cls((ids, [0x10000 * osds_per_host] * hosts),
                   {-(h + 2): (list(range(h * osds_per_host,
                                          (h + 1) * osds_per_host)),
                               [0x10000] * osds_per_host)
                    for h in range(hosts)})

    def do_rule(self, x: int, numrep: int, osd_weight: list) -> list:
        out, leaves = [], []
        for rep in range(numrep):
            for ftotal in range(self.TRIES):
                r = rep + ftotal
                host = straw2(self.root, self.root_w, x, r)
                if host in out:
                    continue        # collision: descend again
                # chooseleaf, descend once, vary_r 1, stable: one draw
                # in the host at the same r
                leaf = straw2(*self.hosts[host], x, r)
                if leaf in leaves or is_out(osd_weight, leaf, x):
                    continue
                out.append(host)
                leaves.append(leaf)
                break
        return leaves


def stable_mod(x, b, bmask):
    return x & bmask if (x & bmask) < b else x & (bmask >> 1)


def pg_to_up_acting(crush: Map, pool_id: int, pg_num: int, size: int,
                    ps: int, osd_weight: list, osd_up: list) -> tuple:
    """(up, up_primary, acting, acting_primary) of pg pool_id.ps; the pool
    has pgp_num == pg_num and the hashpspool flag."""
    mask = (1 << (pg_num - 1).bit_length()) - 1
    pps = hash32_2(stable_mod(ps, pg_num, mask), pool_id)
    raw = crush.do_rule(pps, size, osd_weight)
    up = [o for o in raw if osd_up[o]]
    primary = up[0] if up else -1
    return up, primary, list(up), primary
