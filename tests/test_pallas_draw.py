"""Parity of the fused Pallas descent kernel (interpret mode) against
the XLA `_descend` fast path.

The kernel only runs compiled on a real TPU; these tests force
interpret mode so its *logic* is covered on the CPU mesh.  f32 values
are computed identically on one backend, so item/status must match the
XLA formulation bit-for-bit here (on TPU hardware only flag-soundness
is required, which the certainty bound provides)."""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ceph_tpu.models.crushmap import (  # noqa: E402
    CHOOSELEAF_FIRSTN,
    EMIT,
    STRAW2,
    TAKE,
    CrushMap,
)
import ceph_tpu.ops.crush.device as dev  # noqa: E402
import ceph_tpu.ops.crush.pallas_draw as pd  # noqa: E402


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_PALLAS_INTERPRET", "1")


def _two_level_map(rng, hosts=11, per_host=7, uniform=False):
    m = CrushMap()
    host_ids = []
    for h in range(hosts):
        items = list(range(h * per_host, (h + 1) * per_host))
        ws = ([0x10000] * per_host if uniform else
              [int(rng.integers(0x8000, 0x30000)) for _ in items])
        b = m.add_bucket(STRAW2, 1, items, ws, id=-(h + 2))
        host_ids.append(b.id)
    m.add_bucket(STRAW2, 2, host_ids,
                 [m.buckets[h].weight for h in host_ids], id=-1)
    m.add_rule([(TAKE, -1, 0), (CHOOSELEAF_FIRSTN, 0, 1), (EMIT, 0, 0)],
               id=0)
    return m


def _xla_descend(fm, bid, x, r, want_type, pos, ds):
    os.environ["CEPH_TPU_NO_PALLAS_CRUSH"] = "1"
    try:
        return dev._descend(fm, bid, x, r, want_type, pos, ds, False)
    finally:
        del os.environ["CEPH_TPU_NO_PALLAS_CRUSH"]


def test_descend_parity_outer_and_inner():
    rng = np.random.default_rng(7)
    m = _two_level_map(rng)
    fm = dev.FlatMap(m)
    L = pd.TL * 2
    x = jnp.asarray(rng.integers(0, 1 << 32, L, dtype=np.uint32))
    r = jnp.asarray(rng.integers(0, 3, L, dtype=np.int64)).astype(
        jnp.int32)
    pos = jnp.zeros((L,), jnp.int32)
    # outer: root bucket -> host type
    bid = jnp.zeros((L,), jnp.int32)
    it_x, ok_x, pm_x, fl_x = _xla_descend(fm, bid, x, r, 1, pos, (11,))
    fn = pd.make_descend_kernel(fm, (11,), 1)
    it_p, st = fn(x.astype(jnp.int32), r, bid, pos)
    fl_p = np.asarray((st & 4) != 0)
    # the kernel's table-refined top-3 pass settles most draws the
    # poly-only XLA path flags: kernel flags must be a subset, and
    # items must agree wherever neither side is uncertain
    fl_x = np.asarray(fl_x)
    assert not (fl_p & ~fl_x).any()
    agree = ~(fl_x | fl_p)
    np.testing.assert_array_equal(np.asarray(it_x)[agree],
                                  np.asarray(it_p)[agree])
    np.testing.assert_array_equal(np.asarray(ok_x)[agree],
                                  np.asarray((st & 1) != 0)[agree])
    # inner: per-lane host bucket -> device (want_type 0)
    bid2 = jnp.asarray(rng.integers(1, 12, L, dtype=np.int64)).astype(
        jnp.int32)
    it_x, ok_x, pm_x, fl_x = _xla_descend(fm, bid2, x, r, 0, pos, (7,))
    fn2 = pd.make_descend_kernel(fm, (7,), 0)
    it_p, st2 = fn2(x.astype(jnp.int32), r, bid2, pos)
    fl_x = np.asarray(fl_x)
    fl_p = np.asarray((st2 & 4) != 0)
    assert not (fl_p & ~fl_x).any()
    agree = ~(fl_x | fl_p)
    np.testing.assert_array_equal(np.asarray(it_x)[agree],
                                  np.asarray(it_p)[agree])
    np.testing.assert_array_equal(np.asarray(pm_x)[agree],
                                  np.asarray((st2 & 2) != 0)[agree])


def test_descend_parity_multi_level():
    """Three-level map (root -> rack -> host -> osd), full descent to
    devices in one kernel."""
    rng = np.random.default_rng(3)
    m = CrushMap()
    host_ids = []
    for h in range(6):
        items = list(range(h * 4, (h + 1) * 4))
        b = m.add_bucket(STRAW2, 1, items, [0x10000] * 4, id=-(h + 10))
        host_ids.append(b.id)
    rack_ids = []
    for rk in range(2):
        hs = host_ids[rk * 3:(rk + 1) * 3]
        b = m.add_bucket(STRAW2, 2, hs,
                         [m.buckets[h].weight for h in hs], id=-(rk + 2))
        rack_ids.append(b.id)
    m.add_bucket(STRAW2, 3, rack_ids,
                 [m.buckets[r].weight for r in rack_ids], id=-1)
    fm = dev.FlatMap(m)
    L = pd.TL
    x = jnp.asarray(rng.integers(0, 1 << 32, L, dtype=np.uint32))
    r = jnp.zeros((L,), jnp.int32)
    bid = jnp.zeros((L,), jnp.int32)
    pos = jnp.zeros((L,), jnp.int32)
    ds = (2, 3, 4)   # root(2 racks) -> rack(3 hosts) -> host(4 osds)
    it_x, ok_x, pm_x, fl_x = _xla_descend(fm, bid, x, r, 0, pos, ds)
    fn = pd.make_descend_kernel(fm, ds, 0)
    it_p, st = fn(x.astype(jnp.int32), r, bid, pos)
    agree = ~(np.asarray(fl_x) | np.asarray((st & 4) != 0))
    np.testing.assert_array_equal(np.asarray(it_x)[agree],
                                  np.asarray(it_p)[agree])
    np.testing.assert_array_equal(np.asarray(ok_x)[agree],
                                  np.asarray((st & 1) != 0)[agree])


def test_do_rule_batch_uses_kernel_and_matches_host():
    """End-to-end through DeviceMapper.do_rule_batch with the kernel
    active (interpret): results bit-identical to the host engine."""
    from ceph_tpu.ops.crush.host import Mapper
    from ceph_tpu.models.crushmap import ITEM_NONE

    rng = np.random.default_rng(11)
    m = _two_level_map(rng, hosts=5, per_host=4)
    dm = dev.DeviceMapper(m)
    weights = [0x10000] * m.max_devices
    weights[3] = 0      # one device out
    xs = rng.integers(0, 1 << 32, pd.TL, dtype=np.uint32)
    res = dm.do_rule_batch(0, xs, 3, np.asarray(weights, np.int32))
    host = Mapper(m)
    for i in range(0, pd.TL, 97):
        raw = host.do_rule(0, int(xs[i]), 3, weights)
        row = np.full(3, ITEM_NONE, np.int32)
        row[:len(raw)] = raw[:3]
        np.testing.assert_array_equal(row, res[i], err_msg=str(i))


@pytest.mark.slow
def test_rowcompact_remap_parity():
    """The rowcompact-compacted incremental remap must be bit-equal to
    a fresh full pass computed with pallas disabled (the XLA nonzero
    reference path)."""
    rng = np.random.default_rng(13)
    m = _two_level_map(rng, hosts=11, per_host=7, uniform=True)
    dm = dev.DeviceMapper(m)
    n_osds = 77
    pg_num = 16384            # npg % (8*RC_ROW) == 0: rc path engages
    w = np.full((n_osds,), 0x10000, np.int32)
    ex = np.ones((n_osds,), bool)
    iu = np.ones((n_osds,), bool)
    st = dm.map_pool_state(0, 3, pg_num, pg_num, pg_num - 1, 5, True,
                           w, ex, iu, None, True)
    assert dm._rc_ok(st.npg), "test setup must exercise rowcompact"
    # churn: 6 osds out+down -> incremental remap
    w2 = w.copy()
    iu2 = iu.copy()
    for o in (3, 11, 29, 41, 55, 70):
        w2[o] = 0
        iu2[o] = False
    st2 = st.remap(w2, ex, iu2, None)
    # reference: fresh full pass on the XLA-only path
    os.environ["CEPH_TPU_NO_PALLAS_CRUSH"] = "1"
    try:
        dm_ref = dev.DeviceMapper(m)
        ref = dm_ref.map_pool_state(0, 3, pg_num, pg_num, pg_num - 1,
                                    5, True, w2, ex, iu2, None, True)
    finally:
        del os.environ["CEPH_TPU_NO_PALLAS_CRUSH"]
    np.testing.assert_array_equal(np.asarray(st2.up),
                                  np.asarray(ref.up))
    np.testing.assert_array_equal(np.asarray(st2.prim),
                                  np.asarray(ref.prim))


@pytest.mark.slow
def test_rowcompact_remap_parity_padded_pgnum():
    """pg_num < npg: churn hits in the padded lane region must not
    consume compaction slots or corrupt counts (kernel-side glane
    mask), and the remap stays bit-equal to the XLA reference."""
    rng = np.random.default_rng(17)
    m = _two_level_map(rng, hosts=11, per_host=7, uniform=True)
    dm = dev.DeviceMapper(m)
    n_osds = 77
    pg_num = 16380            # npg rounds up to 16384
    w = np.full((n_osds,), 0x10000, np.int32)
    ex = np.ones((n_osds,), bool)
    iu = np.ones((n_osds,), bool)
    st = dm.map_pool_state(0, 3, pg_num, pg_num, 16383, 9, True,
                           w, ex, iu, None, True)
    assert st.npg > pg_num and dm._rc_ok(st.npg)
    w2 = w.copy()
    iu2 = iu.copy()
    for o in (2, 17, 33, 48, 61):
        w2[o] = 0
        iu2[o] = False
    st2 = st.remap(w2, ex, iu2, None)
    os.environ["CEPH_TPU_NO_PALLAS_CRUSH"] = "1"
    try:
        dm_ref = dev.DeviceMapper(m)
        ref = dm_ref.map_pool_state(0, 3, pg_num, pg_num, 16383, 9,
                                    True, w2, ex, iu2, None, True)
    finally:
        del os.environ["CEPH_TPU_NO_PALLAS_CRUSH"]
    np.testing.assert_array_equal(np.asarray(st2.up),
                                  np.asarray(ref.up))
    np.testing.assert_array_equal(np.asarray(st2.prim),
                                  np.asarray(ref.prim))


@pytest.mark.parametrize("density", [0.0, 0.087, 0.2])
def test_rowcompact_tail_geometry(density):
    """rowcompact as the dense pass's tail calls it: one chunk's own
    index space (no pg_num cut), 2048-lane row groups, 256 slots.  Each
    group's hits land in its first slots in lane order, pad slots carry
    the group's first lane, and a group with more hits than slots seats
    the first 256 and says so in its count."""
    n, row, kt = 32768, dev.DeviceMapper.RC_ROW, dev.DeviceMapper.TAIL_KT
    rng = np.random.default_rng(int(density * 1000) + 5)
    hit = rng.random(n) < density
    rc = pd.make_rowcompact_kernel(n, row, kt, n)
    idx, valid, cnt = (np.asarray(a) for a in rc(jnp.asarray(hit)))
    idx, valid = idx.reshape(n // row, kt), valid.reshape(n // row, kt)
    overflowed = 0
    for g in range(n // row):
        lanes = np.nonzero(hit[g * row:(g + 1) * row])[0] + g * row
        assert cnt[g] == len(lanes)
        seated = min(len(lanes), kt)
        overflowed += len(lanes) > kt
        np.testing.assert_array_equal(idx[g, :seated], lanes[:seated])
        assert valid[g, :seated].all() and not valid[g, seated:].any()
        assert (idx[g, seated:] == g * row).all()
    assert overflowed == (n // row if density == 0.2 else 0)


@pytest.mark.parametrize("density", [0.0, 0.087, 0.2])
def test_rowexpand_puts_compacted_rows_back(density):
    """rowexpand after rowcompact, at the tail's geometry: a seated
    lane gets the row computed at its slot (negative ids and ITEM_NONE
    survive the 8-bit limbs), every other lane keeps its old row, a
    lane its full group could not seat among them, and nothing is read
    from a pad slot."""
    n, row, kt, words = 32768, dev.DeviceMapper.RC_ROW, 256, 4
    rng = np.random.default_rng(int(density * 1000) + 11)
    hit = rng.random(n) < density
    old = rng.integers(-2 ** 31, 2 ** 31, (n, words)).astype(np.int32)
    new = rng.integers(-2 ** 31, 2 ** 31,
                       (n // row * kt, words)).astype(np.int32)
    new[::7, 1] = 0x7FFFFFFF
    idx, valid, _cnt = (np.asarray(a) for a in pd.make_rowcompact_kernel(
        n, row, kt, n)(jnp.asarray(hit)))
    got = np.asarray(pd.make_rowexpand_kernel(n, row, kt, words)(
        jnp.asarray(hit), jnp.asarray(old), jnp.asarray(new)))
    want = old.copy()
    want[idx[valid]] = new[valid]
    np.testing.assert_array_equal(got, want)
    assert (got[~hit] == old[~hit]).all()
    assert int(valid.sum()) == (n // row * kt if density == 0.2
                                else int(hit.sum()))


@pytest.mark.parametrize("density", [0.0, 0.087, 0.3])
def test_rowgather_fetches_the_seated_lanes_words(density):
    """rowgather beside rowcompact, at an indep tail's geometry: slot j
    of a row group holds the words of the group's j-th hit lane
    (negative bucket ids, ITEM_UNDEF and ITEM_NONE survive the 8-bit
    limbs), a slot past the group's hits reads 0, and a group with more
    hits than slots fetches its first 512."""
    n, row, kt, words = 32768, dev.DeviceMapper.RC_ROW, 512, 9
    rng = np.random.default_rng(int(density * 1000) + 17)
    hit = rng.random(n) < density
    vals = rng.integers(-2 ** 31, 2 ** 31, (n, words)).astype(np.int32)
    vals[::5, 2], vals[::7, 3] = 0x7FFFFFFE, 0x7FFFFFFF
    idx, valid, cnt = (np.asarray(a) for a in pd.make_rowcompact_kernel(
        n, row, kt, n)(jnp.asarray(hit)))
    got = np.asarray(pd.make_rowgather_kernel(n, row, kt, words)(
        jnp.asarray(hit), jnp.asarray(vals)))
    np.testing.assert_array_equal(
        got, np.where(valid[:, None], vals[idx], 0))
    assert (cnt > kt).all() == (density == 0.3)
    assert int(valid.sum()) == (n // row * kt if density == 0.3
                                else int(hit.sum()))
