"""The plain references: what the rados cells write, and the CRUSH of the
crush cells.  The CRUSH reference's witness is upstream's C (the outputs
of the oracle that tests/golden/gen_crush_golden.py builds from
src/crush/*.c, copied to data/upstream_crush_golden.json); the program's
scalar engine is a second one, for the OSDMap step above do_rule."""

import json
import os
from decimal import Decimal, getcontext

import numpy as np
import pytest

from benchmark.drivers.crush_churn import build_osdmap, schedule
from benchmark.reference import crush_ref
from benchmark.reference.rados_payload import BLOCK, Payloads


def test_every_seed_writes_the_same_names_in_another_order():
    a, b = Payloads(7, 4096, 4), Payloads(2 ** 31 + 11, 4096, 4)
    na = [a.number(i) for i in range(3 * BLOCK)]
    nb = [b.number(i) for i in range(3 * BLOCK)]
    assert na != nb
    for blk in range(3):
        want = list(range(blk * BLOCK, (blk + 1) * BLOCK))
        assert sorted(na[blk * BLOCK:(blk + 1) * BLOCK]) == want
        assert sorted(nb[blk * BLOCK:(blk + 1) * BLOCK]) == want
    assert a.name(5) == b.name(5)
    assert a.data(5) != b.data(5) and len(a.data(5)) == 4096
    assert a.data(5) == Payloads(7, 4096, 4).data(5)      # from the seed
    assert a.data(5) != a.data(5 + 4)       # same ring buffer, other stamp


def test_schedule_changes_twice_per_step_osds():
    outs = schedule(3, 1000, 10, 50)
    assert all(len(s) == 10 for s in outs)
    assert all(not set(x) & set(y) for x, y in zip(outs, outs[1:]))
    assert outs == schedule(3, 1000, 10, 50) != schedule(4, 1000, 10, 50)


with open(os.path.join(os.path.dirname(__file__), "data",
                       "upstream_crush_golden.json")) as _f:
    GOLDEN = json.load(_f)


@pytest.mark.parametrize("fn, key", [(crush_ref.hash32_2, "hash2"),
                                     (crush_ref.hash32_3, "hash3")])
def test_hashes_agree_with_upstream_c(fn, key):
    p = GOLDEN["primitives"]
    assert len(p[key + "_in"]) == 200
    assert [fn(*i) for i in p[key + "_in"]] == p[key + "_out"]


def _table_entries(xin: int) -> tuple:
    """The RH/LH pair and the LL entry that crush_ln(xin) reads."""
    x = xin + 1
    if not x & 0x18000:
        x <<= 16 - x.bit_length()
    pair = (x >> 8) - 128
    xl64 = ((x * crush_ref.RH_LH_TBL[2 * pair]) & ((1 << 64) - 1)) >> 48
    return pair, xl64 & 0xFF


def test_crush_ln_agrees_with_upstream_c_over_most_of_the_tables():
    p = GOLDEN["primitives"]
    assert [crush_ref.crush_ln(u) for u in p["ln_in"]] == p["ln_out"]
    pairs, lls = zip(*map(_table_entries, p["ln_in"]))
    # what the C outputs pin directly; the rest stands on the closed forms
    assert len(set(pairs)) == 128 and len(set(lls)) == 186


def test_ln_tables_are_upstreams_closed_forms():
    """__RH_LH_tbl is exact: RH = ceil(2^56 / (256 + 2k)), LH = trunc(2^48
    log2(1 + k/128)) with upstream's 0xffff00000000 at k = 128.  __LL_tbl
    is upstream's own residue (its generator is lost): 2^48 log2(1 +
    j/2^15) plus an offset between 0 and 5,493,489,664, under 2e-5 of
    2^48.  A corrupted entry stands out by more."""
    getcontext().prec = 50
    log2 = lambda x: Decimal(x).ln() / Decimal(2).ln()  # noqa: E731
    rh_lh, ll = crush_ref.RH_LH_TBL, crush_ref.LL_TBL
    assert len(rh_lh) == 258 and len(ll) == 256
    for k in range(129):
        assert rh_lh[2 * k] == -(-(1 << 56) // (256 + 2 * k))
        want = int(2 ** 48 * log2(1 + Decimal(k) / 128))
        assert rh_lh[2 * k + 1] == (0xFFFF00000000 if k == 128 else want)
    for j in range(256):
        off = ll[j] - int(2 ** 48 * log2(1 + Decimal(j) / 2 ** 15))
        assert 0 <= off <= 5_493_489_664


@pytest.mark.parametrize("name", sorted(GOLDEN["mappings"]))
def test_do_rule_agrees_with_upstream_c(name):
    """`take root; chooseleaf firstn 0 type host; emit` under the optimal
    tunables on maps of uneven weights with OSDs out and partly
    reweighted; in the 4x4 map every query asks for more replicas than
    there are hosts, so the retry loop runs to its end."""
    g = GOLDEN["mappings"][name]
    assert g["tunables"] == {
        "choose_local_tries": 0, "choose_local_fallback_tries": 0,
        "choose_total_tries": 50, "chooseleaf_descend_once": 1,
        "chooseleaf_vary_r": 1, "chooseleaf_stable": 1,
        "straw_calc_version": 1}
    ref = crush_ref.Map(tuple(g["root"]), {int(h): tuple(v)
                                           for h, v in g["hosts"].items()})
    assert len(g["queries"]) == 67
    for (x, numrep), want in zip(g["queries"], g["results"]):
        assert ref.do_rule(x, numrep, g["reweights"]) == want


def test_crush_reference_agrees_with_the_host_engine():
    """The step above do_rule (pps from the pg, up from raw), which the C
    oracle's vectors do not reach; the program's scalar engine is the
    witness for it, and for the benchmark's uniform map."""
    from ceph_tpu.osd.osdmap import pg_t
    pool = {"id": 1, "size": 3, "pg_num": 5000}
    m = build_osdmap(8, 5, pool)
    out = schedule(11, 40, 6, 1)[0]
    inc = m.new_incremental()
    for o in out:
        inc.new_weight[o] = 0
    inc.new_weight[(out[0] + 1) % 40] = 0x8000      # a partial reweight
    m.apply_incremental(inc)
    weight = list(m.osd_weight)
    ref = crush_ref.Map.uniform(8, 5)
    rng = np.random.default_rng(5)
    for ps in rng.choice(5000, 200, replace=False):
        want = tuple(m.pg_to_up_acting_osds(pg_t(1, int(ps))))
        got = crush_ref.pg_to_up_acting(ref, 1, 5000, 3, int(ps), weight,
                                        [True] * 40)
        assert got == want
