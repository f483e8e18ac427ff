"""Device (vectorized JAX) CRUSH engine parity against the host engine.

The host engine is itself pinned to reference golden vectors
(test_crush_host.py), so host equality here implies reference
bit-exactness for the device path too."""

import json
import os
import random

import numpy as np
import pytest

from ceph_tpu.models.crushmap import (
    CHOOSE_FIRSTN,
    CHOOSE_INDEP,
    CHOOSELEAF_FIRSTN,
    CHOOSELEAF_INDEP,
    EMIT,
    STRAW2,
    TAKE,
    CrushMap,
    Tunables,
    WeightSet,
)
from ceph_tpu.ops.crush.device import DeviceMapper
from ceph_tpu.ops.crush.host import Mapper

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


def _flat_map(n=12, seed=0):
    rng = random.Random(seed)
    m = CrushMap()
    weights = [rng.choice([0x8000, 0x10000, 0x20000, 0x30000])
               for _ in range(n)]
    m.add_bucket(STRAW2, 1, list(range(n)), weights, id=-1)
    m.add_rule([(TAKE, -1, 0), (CHOOSE_FIRSTN, 0, 0), (EMIT, 0, 0)], id=0)
    m.add_rule([(TAKE, -1, 0), (CHOOSE_INDEP, 0, 0), (EMIT, 0, 0)], id=1)
    return m


def _two_level_map(hosts=6, per_host=4, seed=1):
    rng = random.Random(seed)
    m = CrushMap()
    host_ids = []
    dev = 0
    for h in range(hosts):
        items = list(range(dev, dev + per_host))
        dev += per_host
        w = [rng.choice([0x10000, 0x18000, 0x20000]) for _ in items]
        b = m.add_bucket(STRAW2, 1, items, w, id=-(h + 2))
        host_ids.append(b.id)
    m.add_bucket(STRAW2, 2, host_ids,
                 [m.buckets[h].weight for h in host_ids], id=-1)
    m.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1), (EMIT, 0, 0)],
               id=0)
    m.add_rule([(TAKE, -1, 0), (CHOOSELEAF_INDEP, 0, 1), (EMIT, 0, 0)],
               id=1)
    m.add_rule([(TAKE, -1, 0), (CHOOSE_FIRSTN, 0, 1), (EMIT, 0, 0)], id=2)
    return m


def _compare(m, ruleno, result_max, xs, dev_weights):
    host = Mapper(m)
    dm = DeviceMapper(m)
    got = dm.do_rule_batch(ruleno, xs, result_max, dev_weights)
    for i, x in enumerate(xs):
        expect = host.do_rule(ruleno, int(x), result_max, list(dev_weights))
        row = [v for v in got[i].tolist()]
        # host returns a compacted/padded list; pad to result_max
        expect = expect + [0x7FFFFFFF] * (result_max - len(expect))
        assert row == expect, (
            "x=%d rule=%d: device %s != host %s" % (x, ruleno, row, expect))


class TestFlatStraw2:
    @pytest.mark.parametrize("ruleno", [0, 1])
    def test_all_in(self, ruleno):
        m = _flat_map()
        xs = np.arange(96, dtype=np.int64)
        _compare(m, ruleno, 3, xs, [0x10000] * 12)

    @pytest.mark.parametrize("ruleno", [0, 1])
    def test_reweight_and_out(self, ruleno):
        m = _flat_map(seed=3)
        w = [0x10000] * 12
        w[2] = 0          # out
        w[5] = 0x8000     # half reweight
        w[7] = 0
        xs = np.arange(160, dtype=np.int64)
        _compare(m, ruleno, 4, xs, w)


class TestTwoLevel:
    @pytest.mark.parametrize("ruleno", [0, 1, 2])
    def test_chooseleaf(self, ruleno):
        m = _two_level_map()
        xs = np.arange(96, dtype=np.int64)
        _compare(m, ruleno, 3, xs, [0x10000] * 24)

    @pytest.mark.parametrize("ruleno", [0, 1])
    def test_chooseleaf_with_failures(self, ruleno):
        m = _two_level_map(seed=7)
        w = [0x10000] * 24
        for d in (0, 1, 2, 3, 9, 17):   # one whole host + some others
            w[d] = 0
        w[12] = 0x4000
        xs = np.arange(160, dtype=np.int64)
        _compare(m, ruleno, 3, xs, w)

    @pytest.mark.parametrize("stable,vary_r", [(0, 0), (0, 1), (1, 1),
                                               (1, 2)])
    def test_tunable_variants(self, stable, vary_r):
        m = _two_level_map(seed=9)
        m.tunables = Tunables(chooseleaf_stable=stable,
                              chooseleaf_vary_r=vary_r)
        w = [0x10000] * 24
        w[4] = 0
        xs = np.arange(96, dtype=np.int64)
        _compare(m, 0, 3, xs, w)

    def test_choose_args_weight_set(self):
        m = _two_level_map(seed=11)
        per_pos = []
        rng = random.Random(5)
        for pos in range(3):
            per_pos.append(None)
        cargs = {}
        for bid, b in m.buckets.items():
            wsets = [[rng.choice([0x8000, 0x10000, 0x20000])
                      for _ in b.items] for _ in range(3)]
            cargs[bid] = WeightSet(bucket_id=bid, weight_sets=wsets)
        m.choose_args["opt"] = cargs
        host = Mapper(m)
        dm = DeviceMapper(m, choose_args_name="opt")
        xs = np.arange(64, dtype=np.int64)
        w = [0x10000] * 24
        got = dm.do_rule_batch(0, xs, 3, w)
        for i, x in enumerate(xs):
            expect = host.do_rule(0, int(x), 3, w, choose_args=cargs)
            expect = expect + [0x7FFFFFFF] * (3 - len(expect))
            assert got[i].tolist() == expect, "x=%d" % x


class TestOverlappingHosts:
    """A device reachable under more than one host bucket: the firstn
    chooseleaf recursion must reject leaves already placed (mapper.c:
    535-541 with out=out2), or the device path emits duplicate OSDs."""

    def _overlap_map(self):
        m = CrushMap()
        # osd.0 is a member of both hosts
        m.add_bucket(STRAW2, 1, [0, 1], [0x10000, 0x10000], id=-2)
        m.add_bucket(STRAW2, 1, [0, 2], [0x10000, 0x10000], id=-3)
        m.add_bucket(STRAW2, 2, [-2, -3], [0x20000, 0x20000], id=-1)
        m.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1), (EMIT, 0, 0)],
                   id=0)
        return m

    def test_no_duplicate_leaves(self):
        m = self._overlap_map()
        xs = np.arange(256, dtype=np.int64)
        dm = DeviceMapper(m)
        got = dm.do_rule_batch(0, xs, 2, [0x10000] * 3)
        for row in got.tolist():
            placed = [v for v in row if v != 0x7FFFFFFF]
            assert len(placed) == len(set(placed)), row

    def test_matches_host(self):
        m = self._overlap_map()
        xs = np.arange(256, dtype=np.int64)
        _compare(m, 0, 2, xs, [0x10000] * 3)


class TestGoldenMaps:
    """Replay the reference-generated golden vectors on the device engine
    for every straw2-only map in the corpus."""

    @pytest.mark.slow
    def test_golden_straw2_maps(self):
        with open(os.path.join(GOLDEN, "crush_mappings.json")) as f:
            cases = json.load(f)
        ran = 0
        for name, case in cases.items():
            m = CrushMap.from_dict(case["map"])
            if any(b.alg != STRAW2 for b in m.buckets.values()):
                continue
            try:
                dm = DeviceMapper(m, case.get("choose_args_name"))
            except ValueError:
                continue
            # group queries by (rule, result_max) into batches
            groups: dict[tuple, list[tuple[int, int]]] = {}
            for qi, (ruleno, x, rmax) in enumerate(case["queries"]):
                groups.setdefault((ruleno, rmax), []).append((qi, x))
            for (ruleno, rmax), pairs in groups.items():
                rule = m.rules[ruleno]
                n_choose = sum(1 for s in rule.steps if s[0] in (
                    CHOOSE_FIRSTN, CHOOSE_INDEP, CHOOSELEAF_FIRSTN,
                    CHOOSELEAF_INDEP))
                if n_choose != 1:
                    continue
                xs = np.asarray([x for _, x in pairs], dtype=np.int64)
                try:
                    got = dm.do_rule_batch(ruleno, xs, rmax,
                                           case["reweights"])
                except ValueError:
                    continue
                for row, (qi, x) in zip(got, pairs):
                    exp = case["results"][qi]
                    exp = exp + [0x7FFFFFFF] * (rmax - len(exp))
                    assert row.tolist() == exp, (
                        "%s rule %d x=%d: %s != %s"
                        % (name, ruleno, x, row.tolist(), exp))
                ran += 1
        assert ran > 0, "no straw2 golden cases matched the device scope"


class TestF32Draw:
    """The f32 certainty draw's soundness contract (device.py module
    docstring): g_f32 must stay within _G_DELTA/2 of the exact
    2^48-crush_ln over the whole 16-bit domain, and the exact division
    used by the top-2 resolution must be exact."""

    def test_poly_bound_exhaustive(self):
        import jax.numpy as jnp
        from ceph_tpu.ops.crush import device as D
        from ceph_tpu.ops.crush.host import crush_ln

        us = np.arange(65536, dtype=np.int64)
        g = np.asarray(D._g_f32(jnp.asarray(us)), dtype=np.float64)
        exact = np.array([(1 << 48) - crush_ln(int(u)) for u in us],
                         dtype=np.float64)
        err = np.abs(g - exact).max()
        # margin: DELTA carries 2x headroom over the numpy-simulated fit
        assert err <= D._G_DELTA * 0.75, err

    def test_exact_floordiv(self):
        import jax.numpy as jnp
        from ceph_tpu.ops.crush.device import _exact_floordiv

        rng = np.random.default_rng(11)
        neg = rng.integers(0, 1 << 49, size=4096, dtype=np.int64)
        neg[:8] = [0, 1, (1 << 49) - 1, 1 << 48, 12345, 65535, 2, 3]
        w = rng.integers(1, 1 << 32, size=4096, dtype=np.int64)
        w[:6] = [1, 2, 3, 0x10000, (1 << 32) - 1, 7]
        recip = (1.0 / w).astype(np.float32)
        q = np.asarray(_exact_floordiv(
            jnp.asarray(neg), jnp.asarray(w), jnp.asarray(recip)))
        assert np.array_equal(q, neg // w)

    def test_exact2_matches_host_draw(self):
        """Random u/w pairs through the top-2 resolver vs the host
        engine's exponential draw comparison."""
        import jax.numpy as jnp
        from ceph_tpu.ops.crush import device as D
        from ceph_tpu.ops.crush.host import crush_ln, _div_s64

        rng = np.random.default_rng(12)
        n = 2048
        u1 = rng.integers(0, 65536, size=n).astype(np.int64)
        u2 = rng.integers(0, 65536, size=n).astype(np.int64)
        w1 = rng.integers(0, 1 << 20, size=n).astype(np.int64)
        w2 = rng.integers(0, 1 << 20, size=n).astype(np.int64)
        s1 = np.zeros(n, np.int32)
        s2 = np.ones(n, np.int32)
        # third candidate: zero weight, never wins
        u3 = np.zeros(n, np.int64)
        w3 = np.zeros(n, np.int64)
        s3 = np.full(n, 2, np.int32)
        win = np.asarray(D._exact3_winner(
            None,
            (jnp.asarray(u1), jnp.asarray(u2), jnp.asarray(u3)),
            (jnp.asarray(w1), jnp.asarray(w2), jnp.asarray(w3)),
            (jnp.asarray(s1), jnp.asarray(s2), jnp.asarray(s3))))
        for i in range(n):
            # host: maximize trunc((ln-2^48)/w), first index on ties
            d1 = (_div_s64(crush_ln(int(u1[i])) - (1 << 48), int(w1[i]))
                  if w1[i] else -(1 << 63))
            d2 = (_div_s64(crush_ln(int(u2[i])) - (1 << 48), int(w2[i]))
                  if w2[i] else -(1 << 63))
            expect = 1 if d2 > d1 else 0
            assert win[i] == expect, (i, u1[i], u2[i], w1[i], w2[i],
                                      d1, d2, win[i])


class TestLargeBatch:
    """Exercises the attempt structure (L >= _ATTEMPT_MIN_L: a fixed
    number of optimistic rounds per replica, every one over all lanes)
    and the pass-2 resolve flow, sampled against the host engine.  The
    dense pass's compacted tail is TestDenseTail's."""

    @pytest.mark.parametrize("ruleno", [0, 1])
    def test_attempt_path_parity(self, ruleno):
        m = _two_level_map(hosts=8, per_host=4, seed=5)
        w = [0x10000] * 32
        w[3] = 0
        w[11] = 0x6000
        L = 20000  # > _ATTEMPT_MIN_L
        from ceph_tpu.ops.crush import device as D
        old = D._ATTEMPT_MIN_L
        D._ATTEMPT_MIN_L = 4096
        try:
            dm = DeviceMapper(m)
            xs = np.arange(L, dtype=np.int64) * 2654435761 % (1 << 32)
            got = dm.do_rule_batch(ruleno, xs, 3, w)
            host = Mapper(m)
            rng = random.Random(9)
            lanes = rng.sample(range(L), 800)
            for i in lanes:
                expect = host.do_rule(ruleno, int(xs[i]), 3, list(w))
                expect = expect + [0x7FFFFFFF] * (3 - len(expect))
                assert got[i].tolist() == expect, (i, int(xs[i]))
        finally:
            D._ATTEMPT_MIN_L = old


# the staticmethod as shipped: TestDenseTail patches it for the cases
# that are not about stage F
_CHAIN_START = DeviceMapper.__dict__["_chain_start"].__func__


class TestDenseTail:
    """A whole pool's dense pass (map_pool_state) with the tail: only
    the first optimistic round of each replica over all lanes, the
    lanes it leaves unplaced compacted (Pallas rowcompact, interpret
    mode here), replayed at that width and scattered back.  Sampled
    lanes bit-equal to the host engine whatever share of the OSDs is
    out or reweighted, and the counters say which way a pass went."""

    HOSTS, PER_HOST, PG_NUM = 40, 5, 16384
    # rule 0: chooseleaf firstn 0 type host; rule 1: choose firstn 0
    # type osd (a two-level descent, reweights reject at the leaf);
    # rule 2: chooseleaf indep 0 type host, whose tail is the step's own
    RULES = {0: (CHOOSELEAF_FIRSTN, 1), 1: (CHOOSE_FIRSTN, 0),
             2: (CHOOSELEAF_INDEP, 1)}
    _dm: dict = {}

    @classmethod
    def _mapper(cls):
        """One DeviceMapper for every case: reweights are inputs of the
        programs, so the cases share what interpret mode compiles."""
        if "dm" not in cls._dm:
            m = CrushMap()
            host_ids = []
            for h in range(cls.HOSTS):
                items = list(range(h * cls.PER_HOST,
                                   (h + 1) * cls.PER_HOST))
                b = m.add_bucket(STRAW2, 1, items,
                                 [0x10000] * cls.PER_HOST, id=-(h + 2))
                host_ids.append(b.id)
            m.add_bucket(STRAW2, 2, host_ids,
                         [m.buckets[h].weight for h in host_ids], id=-1)
            for ruleno, (op, want) in cls.RULES.items():
                m.add_rule([(TAKE, -1, 0), (op, 0, want), (EMIT, 0, 0)],
                           id=ruleno)
            cls._dm["dm"] = DeviceMapper(m)
            cls._dm["map"] = m
        return cls._dm["dm"], cls._dm["map"]

    @classmethod
    def _weights(cls, share):
        """`share` of the OSDs touched, whole hosts first: two of three
        out, the third reweighted to 0.375."""
        n = cls.HOSTS * cls.PER_HOST
        w = np.full(n, 0x10000, np.int32)
        for i, o in enumerate(range(int(round(share * n)))):
            w[o] = 0x6000 if i % 3 == 2 else 0
        return w

    def _pass(self, dm, ruleno, result_max, w):
        n = len(w)
        return dm.map_pool_state(
            ruleno, result_max, self.PG_NUM, self.PG_NUM,
            self.PG_NUM - 1, 1, True, w, np.ones(n, bool), w > 0, None,
            True)

    def _assert_rows(self, m, ruleno, result_max, w, raw, lanes):
        from ceph_tpu.ops.crush.hashes import hash32_2
        host = Mapper(m)
        weights = [int(v) for v in w]
        for pg in lanes:
            expect = host.do_rule(ruleno, hash32_2(pg, 1), result_max,
                                  weights)
            expect = expect + [0x7FFFFFFF] * (result_max - len(expect))
            assert raw[pg].tolist() == expect, (pg, ruleno)

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        """Interpret mode, and the resolve chain without stage F: the
        cases here are about the dense pass, and F's full loops in
        interpret mode are a compile of their own in every resolve
        program; the cases about F take `stage_f`."""
        from ceph_tpu.ops.crush import device as D
        monkeypatch.setenv("CEPH_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(D, "_ATTEMPT_MIN_L", 4096)
        monkeypatch.setattr(DeviceMapper, "_chain_start", staticmethod(
            lambda K1, counts, widest: _CHAIN_START(K1, counts, 0)))

    @pytest.fixture
    def stage_f(self, monkeypatch):
        """The chain as shipped, sized afresh for the case (the passes
        before it kept their capacities without F)."""
        monkeypatch.setattr(DeviceMapper, "_chain_start",
                            staticmethod(_CHAIN_START))
        monkeypatch.setattr(self._mapper()[0], "_chain_want", {})

    @pytest.mark.parametrize("share", [0.0, 0.01, 0.10, 0.40])
    @pytest.mark.parametrize("ruleno", [0, 1])
    def test_tail_parity_and_counters(self, ruleno, share):
        dm, m = self._mapper()
        dm._tail_want.clear()
        w = self._weights(share)
        slots = dm._tail_slots(ruleno, 2, self.PG_NUM, dm.TAIL_KT)
        assert slots and (self.PG_NUM // dm.RC_ROW * slots
                          ) % 4096 == 0, "the tail must stay in Pallas"
        overflows = dm.tail_overflows
        st = self._pass(dm, ruleno, 2, w)
        raw = np.asarray(st.raw)
        lanes = random.Random(ruleno * 7 + 1).sample(
            range(self.PG_NUM), 300)
        self._assert_rows(m, ruleno, 2, w, raw, lanes)
        assert st.lanes == self.PG_NUM
        assert dm.fm.descent_in_pallas[self.PG_NUM]
        if share < 0.40:
            # collisions alone (1 host in 40) leave lanes unplaced
            assert dm.tail_overflows == overflows
            assert 0 < st.tail_lanes < self.PG_NUM // 4
            assert st.resolve_lanes < st.tail_lanes
            return
        # more unplaced lanes than slots: the pass was thrown away and
        # the pool went back to dense rounds, with no tail
        assert dm.tail_overflows == overflows + 1
        assert st.tail_lanes == 0
        assert dm._tail_slots(ruleno, 2, self.PG_NUM, dm._tail_want[
            (ruleno, 2, self.PG_NUM, 0, 0)]) == 0
        # and the overflowing program itself flagged what it could not
        # seat, rather than cut it off: every lane it leaves unflagged
        # is exact
        import jax.numpy as jnp
        fn = dm._compiled_pool(ruleno, 2, True, False, self.PG_NUM,
                               self.PG_NUM - 1, 1, True, self.PG_NUM,
                               1, (slots,))
        raw1, _up, _prim, flag, tail = fn(
            jnp.asarray(w), jnp.ones(len(w), bool), jnp.asarray(w > 0),
            jnp.zeros(len(w), jnp.int32))
        kind = np.asarray(flag)
        flag = kind != 0      # the kinds: 0 is settled
        seated, unseated, largest, retry, uncertain, short = (
            int(v) for v in np.asarray(tail))
        assert retry == 0      # indep's count; a firstn pass has none
        assert [uncertain, short] == np.bincount(kind, minlength=3)[
            1:].tolist()
        assert largest > slots
        assert seated == self.PG_NUM // dm.RC_ROW * slots
        assert unseated * 16 > seated and flag.sum() >= unseated
        self._assert_rows(m, ruleno, 2, w, np.asarray(raw1),
                          [pg for pg in lanes if not flag[pg]])

    def test_short_lanes_settle_in_the_fast_form(self, stage_f):
        """A tenth of the OSDs touched: lanes whose replica is still
        unplaced after the optimistic rounds reach stage F, which runs
        the full loops for them in the fast form and settles them; rows
        equal the host engine's and the plain reference's."""
        from benchmark.drivers.crush_churn_rules import reference_of
        from ceph_tpu.ops.crush.hashes import hash32_2
        ruleno = 0
        dm, m = self._mapper()
        dm._tail_want.clear()
        w = self._weights(0.10)
        st = self._pass(dm, ruleno, 2, w)
        assert 0 < st.fast_settled <= st.fast_lanes <= st.resolve_lanes
        assert st.b_lanes <= st.resolve_lanes - st.fast_settled
        raw = np.asarray(st.raw)
        lanes = random.Random(ruleno * 7 + 3).sample(range(self.PG_NUM),
                                                     300)
        self._assert_rows(m, ruleno, 2, w, raw, lanes)
        ref, rule = reference_of(m, ruleno)
        weights = [int(v) for v in w]
        for pg in lanes[:60]:
            want = ref.do_rule(rule, hash32_2(pg, 1), 2, weights)
            assert raw[pg].tolist()[:len(want)] == want, pg

    def test_a_fast_stage_that_trusts_nothing_changes_no_answer(
            self, monkeypatch, stage_f):
        """Stage F patched to flag every lane it draws and to report the
        lanes it is handed, on the dense pass of the case above: it gets
        the short lanes and no uncertain one, settles none, and what it
        flags goes on to stage A and beyond, so every row is the
        unpatched pass's (stages A and B as wide as the chain: a short
        lane fails A's rounds again)."""
        import jax
        import jax.numpy as jnp
        from ceph_tpu.ops.crush.device import _SHORT, _UNCERTAIN
        from ceph_tpu.ops.crush.hashes import pps_seed_v
        dm, m = self._mapper()
        dm._tail_want.clear()
        w = self._weights(0.10)
        st = self._pass(dm, 0, 2, w)
        fn, _shapes = self._tail_program(dm)
        inputs = (jnp.asarray(w), jnp.ones(len(w), bool),
                  jnp.asarray(w > 0), jnp.zeros(len(w), jnp.int32))
        dense = fn(*inputs)
        kind = np.asarray(dense[3])
        seen, real = [], DeviceMapper._compile

        def compile_(self, ruleno, result_max, resolve, full=True, *a,
                     **kw):
            core = real(self, ruleno, result_max, resolve, full, *a, **kw)
            if resolve is not False or not full:
                return core

            def trusting_nothing(xs, w):
                jax.debug.callback(lambda v: seen.append(np.asarray(v)),
                                   xs)
                rows, flag = core(xs, w)
                return rows, jnp.ones_like(flag)
            return trusting_nothing

        monkeypatch.setattr(DeviceMapper, "_compile", compile_)
        K1, _K2, K3, KF, _KA = dm._chain_want[(0, 2, self.PG_NUM)]
        short, unsure = kind == _SHORT, kind == _UNCERTAIN
        assert KF, "the pass had no stage F"
        assert 0 < short.sum() <= 64 and unsure.any()
        n = self.PG_NUM
        # 64 slots hold every short lane and keep F's loops in XLA's
        # form (a width off the Pallas tile): the patch flags whatever
        # the form draws
        res = DeviceMapper(m)._compiled_device_resolve(
            0, 2, True, False, n, n - 1, 1, True, K1, K1, K3, n, n,
            dm.RC_KT, 64, K1)
        raw, _up, _prim, counts = res(*dense, *inputs)
        nflag, _n2, _n3, _rowmax, n_fast, settled, _n_a = (
            int(v) for v in np.asarray(counts)[:7])
        assert settled == 0 and n_fast == short.sum()
        assert nflag == short.sum() + unsure.sum()
        assert np.array_equal(np.asarray(raw), np.asarray(st.raw))
        pps = pps_seed_v(np.arange(n), n, n - 1, 1, True)
        (handed,) = seen
        assert set(handed.tolist()) == set(pps[short].tolist())
        assert not set(handed.tolist()) & set(pps[unsure].tolist())

    def _tail_program(self, dm):
        """The firstn pool program with a tail that the passes above
        ran, and the shapes they gave it."""
        import jax
        import jax.numpy as jnp
        n = self.HOSTS * self.PER_HOST
        slots = dm._tail_slots(0, 2, self.PG_NUM, dm.TAIL_KT)
        fn = dm._compiled_pool(0, 2, True, False, self.PG_NUM,
                               self.PG_NUM - 1, 1, True, self.PG_NUM, 1,
                               (slots,))
        return fn, [jax.ShapeDtypeStruct((n,), dt) for dt in
                    (jnp.int32, bool, bool, jnp.int32)]

    def test_the_tail_program_carries_its_scopes(self):
        """The compiled module the passes above ran (no second compile):
        every stage of a firstn pass with a tail is named, a descent
        stands under a first round or under the tail's rounds, and the
        chunk loop's own instructions are all that has no scope."""
        from tests.test_scopes import (POOL_SCOPES, scope_paths,
                                       scoped_share)
        fn, shapes = self._tail_program(self._mapper()[0])
        assert fn._cache_size() >= 1, "run after the passes above"
        paths = scope_paths(fn.lower(*shapes).compile().as_text())
        assert {name for p in paths for name in p} == POOL_SCOPES
        assert scoped_share(paths) >= 95.0, scoped_share(paths)
        assert ("crush.first", "crush.descend") in paths
        assert ("crush.tail.rounds", "crush.descend") in paths
        assert ("crush.tail.move", "crush.seeds") in paths
        assert all(p[0] in ("crush.first", "crush.tail.rounds")
                   for p in paths if "crush.descend" in p), paths

    def test_a_scope_adds_no_operation_to_the_tail_program(
            self, monkeypatch):
        """The same program lowered again from a mapper of its own with
        `scope` a null context: the same StableHLO."""
        from tests.test_scopes import assert_same_without_scopes
        (dm, m), made = self._mapper(), {}
        fn, shapes = self._tail_program(dm)

        def build():
            made["dm"] = DeviceMapper(m)
            return self._tail_program(made["dm"])[0]

        assert_same_without_scopes(monkeypatch, fn.lower(*shapes), shapes,
                                   build)
        assert made["dm"] is not dm

    @pytest.mark.parametrize("share", [0.0, 0.10, 0.13, 0.40])
    def test_indep_tail_parity_and_counters(self, share):
        """The indep twin: the step's first round over all lanes, its
        later rounds on the takes that still had an undefined slot."""
        dm, m = self._mapper()
        dm._tail_want.clear()
        w = self._weights(share)
        start = dm._tail_start(2, 2, 0)
        slots = dm._tail_slots(2, 2, self.PG_NUM, start)
        # one collision in forty: 51 hits a row group expected, and a
        # width that fills a Pallas tile
        assert 51 < start < 128 and slots == 512
        overflows = dm.tail_overflows
        st = self._pass(dm, 2, 2, w)
        lanes = random.Random(23).sample(range(self.PG_NUM), 300)
        self._assert_rows(m, 2, 2, w, np.asarray(st.raw), lanes)
        assert st.tail_lanes == 0      # firstn's count
        assert dm.fm.descent_in_pallas[self.PG_NUM // dm.RC_ROW * slots]
        if share < 0.40:
            assert dm.tail_overflows == overflows
            assert 0 < st.indep_tail_lanes <= st.retry_lanes
            # every hit lane sat in the tail, or (0.13: row groups of
            # over 512 hits) a few were left to the resolve chain
            left = st.retry_lanes - st.indep_tail_lanes
            assert (left > 0) == (share == 0.13)
            assert left * 16 <= st.indep_tail_lanes
            assert left <= st.resolve_lanes < st.retry_lanes
            return
        # more hits than slots: the pass was thrown away, the step's
        # want is past what a tail may have, its rounds are dense again
        assert dm.tail_overflows == overflows + 1
        assert st.indep_tail_lanes == 0 and st.retry_lanes > 0
        assert dm._tail_want[(2, 2, self.PG_NUM, 0, 0)] > \
            dm.INDEP_TAIL_KT_MAX
        assert dm._tail_slots(2, 2, self.PG_NUM, dm._tail_want[
            (2, 2, self.PG_NUM, 0, 0)]) == 0
        # and the program that ran over flagged what it could not seat:
        # every lane it leaves unflagged is exact
        import jax.numpy as jnp
        fn = dm._compiled_pool(2, 2, True, False, self.PG_NUM,
                               self.PG_NUM - 1, 1, True, self.PG_NUM,
                               1, (slots,))
        raw1, _up, _prim, flag, counts = fn(
            jnp.asarray(w), jnp.ones(len(w), bool), jnp.asarray(w > 0),
            jnp.zeros(len(w), jnp.int32))
        kind = np.asarray(flag)
        flag = kind != 0      # the kinds: 0 is settled
        *firstn, retry, seated, unseated, largest, uncertain, short = (
            int(v) for v in np.asarray(counts))
        assert firstn == [0, 0, 0]
        assert [uncertain, short] == np.bincount(kind, minlength=3)[
            1:].tolist()
        assert largest > slots and retry == seated + unseated
        assert seated == self.PG_NUM // dm.RC_ROW * slots
        assert unseated * 16 > seated and flag.sum() >= unseated
        self._assert_rows(m, 2, 2, w, np.asarray(raw1),
                          [pg for pg in lanes if not flag[pg]])

    @pytest.mark.parametrize("lanes,want,slots", [
        (1 << 20, 256, 256),    # DeviceMapper.CHUNK: 512 groups
        (1 << 20, 300, 384),    # widened by a pass that ran over
        (1 << 20, 513, 0),      # past TAIL_KT_MAX: dense rounds
        (32768, 256, 256),      # 16 groups x 256 = one Pallas tile
        (16384, 256, 512),      # 8 groups need 512 to fill a tile
        (49152, 256, 512),      # 24 groups: gcd with the tile is 8
        (20000, 256, 0),        # no multiple of 8 row groups
        (8192, 256, 0),         # under _ATTEMPT_MIN_L (16384 here)
    ])
    def test_tail_slots(self, monkeypatch, lanes, want, slots):
        """The tail's width follows from what the code can see: the
        chunk's lanes, rowcompact's alignment, the Pallas tile and the
        slots a pass was found to need."""
        from ceph_tpu.ops.crush import device as D
        monkeypatch.setattr(D, "_ATTEMPT_MIN_L", 16384)
        dm, _m = self._mapper()
        assert dm._tail_slots(0, 2, lanes, want) == slots
        if slots:
            assert (lanes // dm.RC_ROW * slots) % 4096 == 0

    def test_no_tail_for_indep_or_without_pallas(self, monkeypatch):
        """(The name is PR 34's: since PR 36 an indep step has a tail
        of its own, sized from the step's geometry.)"""
        m = _two_level_map()
        dm = DeviceMapper(m)
        assert dm._tail_slots(0, 3, 1 << 20, dm.TAIL_KT) == 256
        # indep: three draws among the map's hosts collide in
        # collide * 2048 lanes of a row group
        (step,) = dm._plan(1, 3).steps
        assert 0 < step.collide < 1
        start = dm._tail_start(1, 3, 0)
        assert 2048 * step.collide < start < 2048
        assert dm._tail_slots(1, 3, 1 << 20, start) == 128 * -(-start // 128)
        assert dm._tail_slots(1, 3, 1 << 20, 1025) == 0  # past its most
        assert dm._tail_slots(1, 3, 8192, start) == 0    # the full loops
        monkeypatch.delenv("CEPH_TPU_PALLAS_INTERPRET")
        assert dm._tail_slots(0, 3, 1 << 20, dm.TAIL_KT) == 0
        assert dm._tail_slots(1, 3, 1 << 20, start) == 0

    @pytest.mark.parametrize("ruleno", [1, 2])
    def test_no_tail_lanes_when_nothing_can_fail(self, ruleno):
        """One replica and nothing out: no collision, no rejection, so
        the first rounds place every lane and the tail seats none."""
        dm, m = self._mapper()
        dm._tail_want.clear()
        w = self._weights(0.0)
        assert dm._tail_slots(ruleno, 1, self.PG_NUM,
                              dm._tail_start(ruleno, 1, 0))
        st = self._pass(dm, ruleno, 1, w)
        assert st.tail_lanes == 0 and dm.tail_overflows >= 0
        assert st.indep_tail_lanes == 0 and st.retry_lanes == 0
        assert st.lanes == self.PG_NUM
        self._assert_rows(m, ruleno, 1, w, np.asarray(st.raw),
                          random.Random(3).sample(range(self.PG_NUM),
                                                  100))


class TestMapStateRemap:
    """map_pool_state + MapState.remap: the incremental path must be
    bit-identical to a full pass for qualifying changes (reweight
    decreases, up/down flips) and must fall back for increases."""

    def _mk(self, hosts=6, per_host=5, pg_num=4096):
        from ceph_tpu.models.crushmap import (CHOOSELEAF_FIRSTN, EMIT,
                                              STRAW2, TAKE, CrushMap)
        from ceph_tpu.ops.crush.device import DeviceMapper

        m = CrushMap()
        host_ids = []
        for h in range(hosts):
            items = list(range(h * per_host, (h + 1) * per_host))
            b = m.add_bucket(STRAW2, 1, items, [0x10000] * per_host,
                             id=-(h + 2))
            host_ids.append(b.id)
        m.add_bucket(STRAW2, 2, host_ids,
                     [m.buckets[h].weight for h in host_ids], id=-1)
        m.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1),
                    (EMIT, 0, 0)], id=0)
        return DeviceMapper(m), hosts * per_host, pg_num

    def _state(self, dm, pg_num, w, ex, iu):
        return dm.map_pool_state(0, 3, pg_num, pg_num, pg_num - 1, 1,
                                 True, w, ex, iu, None, True)

    def test_incremental_matches_full(self):
        import numpy as np

        dm, n_osds, pg_num = self._mk()
        w0 = np.full(n_osds, 0x10000, np.int32)
        ex = np.ones(n_osds, bool)
        iu0 = np.ones(n_osds, bool)
        st0 = self._state(dm, pg_num, w0, ex, iu0)
        w1 = w0.copy()
        iu1 = iu0.copy()
        for o in (2, 11, 23):
            w1[o] = 0
            iu1[o] = False
        w1[17] = 0x8000          # partial decrease
        st1 = st0.remap(w1, ex, iu1, None)
        stf = self._state(dm, pg_num, w1, ex, iu1)
        np.testing.assert_array_equal(np.asarray(st1.up),
                                      np.asarray(stf.up))
        np.testing.assert_array_equal(np.asarray(st1.prim),
                                      np.asarray(stf.prim))
        np.testing.assert_array_equal(np.asarray(st1.raw),
                                      np.asarray(stf.raw))
        # chained incremental stays exact
        w2 = w1.copy()
        w2[5] = 0
        st2 = st1.remap(w2, ex, iu1, None)
        stf2 = self._state(dm, pg_num, w2, ex, iu1)
        np.testing.assert_array_equal(np.asarray(st2.up),
                                      np.asarray(stf2.up))
        # reweight increase falls back to a full pass, still exact
        w3 = w2.copy()
        w3[2] = 0x10000
        iu2 = iu1.copy()
        iu2[2] = True
        st3 = st2.remap(w3, ex, iu2, None)
        stf3 = self._state(dm, pg_num, w3, ex, iu2)
        np.testing.assert_array_equal(np.asarray(st3.up),
                                      np.asarray(stf3.up))
