"""Fused Pallas TPU kernel for the CRUSH bucket descent.

The XLA formulation of the f32 certainty draw (device.py `_straw2_choose`
/ `_descend`) materialises ~15 [L, S]-shaped f32/i32 temporaries per
draw in HBM — measured ~37 KB of HBM traffic per PG for the bulk-map
fast pass, which makes the 10M-PG remap bandwidth-bound (XLA cost
analysis: ~37 GB written per 1M-lane chunk).  This kernel runs the whole
multi-level descent — rjenkins hash, the f32 log approximation, the
per-item certainty intervals, winner select, child-bucket walk
(mapper.c:438-520 descent structure) — inside VMEM, so HBM traffic per
descend drops to the lane vectors themselves (~20 B/lane).

Layout: lanes ride the 128-wide lane axis in tiles of TL; bucket items
ride the sublane axis ([S_d, TL] per level).  Per-lane bucket rows are
fetched with one int8 one-hot MXU matmul per level from transposed limb
tables ([R_d, n_pos*B] int8, the same 8-bit-limb packing as
device.FlatMap) — gathers run at scalar rate on TPU, one-hot matmuls at
MXU rate, and integer matmuls are exact.

Semantics match device._descend with resolve=False bit-for-bit at the
*logic* level; the f32 draw values may differ across backends by FMA /
reassociation, which the doubled _G_DELTA headroom in the certainty
bound absorbs — an uncertain winner is flagged either way and settled
by the exact resolve pass, so end results stay bit-identical to the
host engine (verified by tests/test_crush_device.py on golden vectors).
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp

GW = 512           # lanes per sublane group (128-multiple)
TL = 8 * GW        # lanes per tile: 8 sublane rows of GW
_MAX_TABLE_BYTES = 6 << 20   # VMEM budget for the per-level limb tables
_S_BIG = 0x7FFF              # > any slot index; argmin-tiebreak sentinel


def pallas_enabled() -> bool:
    """Mosaic lowering needs a real TPU; tests force interpret mode via
    CEPH_TPU_PALLAS_INTERPRET=1 to cover the kernel logic on CPU."""
    if os.environ.get("CEPH_TPU_NO_PALLAS_CRUSH"):
        return False
    if os.environ.get("CEPH_TPU_PALLAS_INTERPRET"):
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _limb_planes(vals: np.ndarray, n_limbs: int, offset: int = 0
                 ) -> np.ndarray:
    """[B, S] int -> [n_limbs*S, B] int8 limb planes (limb-major blocks,
    biased by -128), transposed for the [R, B] @ [B, TL] fetch."""
    v = vals.astype(np.int64) - offset
    assert (v >= 0).all() and (v < (1 << (8 * n_limbs))).all()
    planes = [(((v >> (8 * j)) & 0xFF) - 128).astype(np.int8)
              for j in range(n_limbs)]
    return np.concatenate([p.T for p in planes], axis=0)


def _unpack_rows(f, S: int, n_limbs: int, base: int, offset: int = 0):
    """[R, TL] i32 matmul result -> [S, TL] i32 from limb-plane rows
    starting at `base`."""
    acc = f[base:base + S, :] + 128
    for j in range(1, n_limbs):
        acc = acc + ((f[base + j * S:base + (j + 1) * S, :] + 128)
                     << (8 * j))
    if offset:
        acc = acc + offset
    return acc


class _LevelTables:
    """Static per-level fetch tables for one (fm, depth_sizes) pair."""

    def __init__(self, fm, depth_sizes):
        self.nl = nl = fm.nl_id
        self.dup = dup = 0 if fm.ids_equal_items else nl
        self.n_pos = n_pos = fm.n_pos
        self.B = B = fm.B
        self.tables = []
        nbytes = 0
        for S_d in depth_sizes:
            blocks = []
            ids = np.tile(fm._ids_np[:, :S_d], (n_pos, 1))
            blocks.append(_limb_planes(ids, nl, fm.id_offset))
            if dup:
                items = np.tile(fm._items_np[:, :S_d], (n_pos, 1))
                blocks.append(_limb_planes(items, nl, fm.id_offset))
            rb = fm._recipbits_np.reshape(n_pos * B, -1)[:, :S_d]
            blocks.append(_limb_planes(rb, 4))
            size = np.tile(fm._size_np[:, None], (n_pos, 1))
            blocks.append(_limb_planes(size, 2))
            tbl = np.concatenate(blocks, axis=0)
            nbytes += tbl.nbytes
            self.tables.append(tbl)
        # [4, B]: rows = [size limb0, size limb1, btype limb0, limb1]
        self.meta = np.concatenate(
            [_limb_planes(fm._size_np[:, None], 2),
             _limb_planes(fm._btype_np[:, None], 2)], axis=0)
        self.nbytes = nbytes + self.meta.nbytes

    def row_count(self, S_d: int) -> int:
        return (self.nl + self.dup + 4) * S_d + 2


def _hash_mix(a, b, c):
    u = np.uint32
    a = a - b; a = a - c; a = a ^ (c >> u(13))
    b = b - c; b = b - a; b = b ^ (a << u(8))
    c = c - a; c = c - b; c = c ^ (b >> u(13))
    a = a - b; a = a - c; a = a ^ (c >> u(12))
    b = b - c; b = b - a; b = b ^ (a << u(16))
    c = c - a; c = c - b; c = c ^ (b >> u(5))
    a = a - b; a = a - c; a = a ^ (c >> u(3))
    b = b - c; b = b - a; b = b ^ (a << u(10))
    c = c - a; c = c - b; c = c ^ (b >> u(15))
    return a, b, c


def _hash32_3(a, b, c, seed):
    u = np.uint32
    h = u(seed) ^ a ^ b ^ c
    x, y = u(231232), u(1232)
    a, b, h = _hash_mix(a, b, h)
    c, x, h = _hash_mix(c, x, h)
    y, a, h = _hash_mix(y, a, h)
    b, x, h = _hash_mix(b, x, h)
    y, c, h = _hash_mix(y, c, h)
    return h


def _g_poly(u, coef):
    """f32 approximation of 2^48 - crush_ln(u); mirrors device._g_f32."""
    x = (u + 1).astype(jnp.int32)
    xf = x.astype(jnp.float32)
    b = jax.lax.bitcast_convert_type(xf, jnp.int32)
    e = ((b >> 23) - 127).astype(jnp.float32)
    mm = jax.lax.bitcast_convert_type(
        (b & 0x7FFFFF) | 0x3F800000, jnp.float32) - np.float32(1.0)
    acc = jnp.full_like(mm, np.float32(coef[-1]))
    for c in coef[-2::-1]:
        acc = acc * mm + np.float32(c)
    return np.float32(2.0 ** 44) * ((np.float32(16.0) - e) - acc)


def make_descend_kernel(fm, depth_sizes: tuple, want_type: int):
    """Compiled fused descent: fn(x, r, bid, pos) -> (item, status) with
    x/r/bid/pos int32 [L] (L % TL == 0) and status bits
    ok=1 | perm=2 | flag=4.  Returns None when the map doesn't fit the
    kernel's budget (caller falls back to the XLA path)."""
    from jax.experimental import pallas as pl
    from . import device as dev
    from ...models.crushmap import ITEM_NONE

    lt = _LevelTables(fm, depth_sizes)
    if lt.nbytes > _MAX_TABLE_BYTES or lt.n_pos * lt.B > 4096:
        return None
    nl, dup, n_pos, B = lt.nl, lt.dup, lt.n_pos, lt.B
    max_devices = int(fm.max_devices)
    coef = dev._LOG2_COEF
    g_delta = float(dev._G_DELTA)
    eps_q = float(dev._EPS_Q)
    e_const = float(dev._E_CONST)
    big = float(3.0e38)
    seed = dev.HASH_SEED
    i8, i32, f32, u32 = jnp.int8, jnp.int32, jnp.float32, jnp.uint32
    c32, cf32, cu32 = np.int32, np.float32, np.uint32
    # keep tables as host numpy: make_descend_kernel is lazily reached
    # inside jit traces, where jnp.asarray would bind the constant to
    # the live trace and leak it into later traces (cf. FlatMap row
    # cache) — numpy inputs become ordinary jit constants instead
    tbls = [np.asarray(t) for t in lt.tables]
    meta_t = np.asarray(lt.meta)
    n_lvl = len(depth_sizes)

    # -- refine tables: crush_ln's own RH/LH/LL tables (mapper.c:226-268)
    # RH as three 16-bit limbs (for the exact 64-bit x2*rh product) and
    # f32(LH)/f32(LL) as bit-limbs.  The poly error bound is dominated
    # by the ln table's quantization noise (~2^30); evaluating the real
    # table in f32 brings the bound down to REF_DELTA ~ 2^25, settling
    # ~95% of poly-uncertain draws in-kernel instead of in the resolve
    # pass.
    rh_np = dev._RH_NP.astype(np.int64)                    # [129] <2^48
    lh_np = dev._LH_NP.astype(np.int64)
    ll_np = dev._LL_NP.astype(np.int64)
    rh16 = np.stack([(rh_np >> (16 * k)) & 0xFFFF
                     for k in range(3)], axis=0)           # [3, 129]
    lh_bits = lh_np.astype(np.float32).view(np.uint32).astype(np.int64)
    ll_bits = ll_np.astype(np.float32).view(np.uint32).astype(np.int64)
    refp_t = np.concatenate(
        [_limb_planes(rh16.T, 2),                          # rows 0..5
         _limb_planes(lh_bits[:, None], 4)], axis=0)       # rows 6..9
    refl_t = _limb_planes(ll_bits[:, None], 4)             # [4, 256]
    # error budget: f32 rounding of LH, LL (2^24 each at 2^48 scale),
    # their sum, and the final subtraction, plus floor slack — ~2^26;
    # doubled for margin
    REF_DELTA = float(2 ** 27)
    REF_EPS = float(2.0 ** -21)

    def refine(u, rf, refp_ref, refl_ref):
        """f32 evaluation of the EXACT crush_ln tables for one
        candidate: u [1,GW] i32 hash, rf [1,GW] f32 reciprocal.
        Returns q_ref with |q_ref - q_exact| <= REF_DELTA*rf +
        q*REF_EPS + const (mirrors neg_ln_mxu's structure,
        mapper.c:226-268)."""
        x = u + c32(1)
        bl = jnp.full(x.shape, c32(1), i32)
        for kbit in range(1, 17):
            bl = bl + (x >= c32(1 << kbit)).astype(i32)
        need = (x & c32(0x18000)) == 0
        bits = jnp.maximum(c32(16) - bl, c32(0))
        x2 = jnp.where(need, x << bits, x)
        iexp = jnp.where(need, c32(15) - bits, c32(15))
        p = (x2 >> 8) - c32(128)                     # [0, 128]
        iota_p = jax.lax.broadcasted_iota(i32, (129, GW), 0)
        ohp = (iota_p == p).astype(i8)
        fr = jax.lax.dot_general(
            refp_ref[...], ohp, (((1,), (0,)), ((), ())),
            preferred_element_type=i32)              # [10, GW]
        rh = _unpack_rows(fr, 3, 2, 0)               # [3, GW] 16b limbs
        lhf = jax.lax.bitcast_convert_type(
            _unpack_rows(fr, 1, 4, 6), f32)
        # exact bits 48..55 of x2*rh via 16-bit limb products (each
        # < 2^32: x2 <= 2^16, limbs <= 2^16-1)
        x2u = x2.astype(u32)
        t0 = x2u * rh[0:1, :].astype(u32)
        t1 = x2u * rh[1:2, :].astype(u32)
        t2 = x2u * rh[2:3, :].astype(u32)
        s1 = (t0 >> cu32(16)) + t1
        c1 = (s1 < t1).astype(u32)
        s2 = (s1 >> cu32(16)) + (c1 << cu32(16)) + t2
        i2x = ((s2 >> cu32(16)) & cu32(0xFF)).astype(i32)
        iota_l = jax.lax.broadcasted_iota(i32, (256, GW), 0)
        ohl = (iota_l == i2x).astype(i8)
        fl = jax.lax.dot_general(
            refl_ref[...], ohl, (((1,), (0,)), ((), ())),
            preferred_element_type=i32)              # [4, GW]
        llf = jax.lax.bitcast_convert_type(
            _unpack_rows(fl, 1, 4, 0), f32)
        neg = ((cf32(float(1 << 48))
                - iexp.astype(f32) * cf32(float(1 << 44)))
               - (lhf + llf) * cf32(1.0 / 16.0))
        return neg * rf

    def group(d, S_d, tbl_ref, meta_ref, refp_ref, refl_ref, xg, rg,
              posg, st):
        """One level advance for one GW-lane sublane group.
        xg/rg/posg [1, GW]; st = (cur, done, ok, perm, flag, item)."""
        cur, done, ok, perm, flag, item = st
        col = cur if n_pos == 1 else posg * c32(B) + cur
        iota_b = jax.lax.broadcasted_iota(i32, (n_pos * B, GW), 0)
        oh = (iota_b == col).astype(i8)
        f = jax.lax.dot_general(
            tbl_ref[...], oh, (((1,), (0,)), ((), ())),
            preferred_element_type=i32)            # [R_d, GW]
        ids = _unpack_rows(f, S_d, nl, 0, fm.id_offset)
        if dup:
            items_a = _unpack_rows(f, S_d, nl, nl * S_d, fm.id_offset)
        else:
            items_a = ids
        rbits = _unpack_rows(f, S_d, 4, (nl + dup) * S_d)
        recipf = jax.lax.bitcast_convert_type(rbits, f32)
        size = _unpack_rows(f, 1, 2, (nl + dup + 4) * S_d)   # [1, GW]
        iota_s = jax.lax.broadcasted_iota(i32, (S_d, GW), 0)
        valid = (iota_s < size) & (recipf > 0)
        u = (_hash32_3(xg, ids.astype(u32), rg, seed)
             & cu32(0xFFFF)).astype(i32)
        g = _g_poly(u, coef)
        q = jnp.where(valid, g * recipf, cf32(big))
        E = cf32(g_delta) * recipf + q * cf32(eps_q) + cf32(e_const)
        hi = jnp.where(valid, q + E, cf32(big))
        low = jnp.where(valid, q - E, cf32(big))
        min_hi = jnp.min(hi, axis=0, keepdims=True)
        contend = valid & (low <= min_hi)
        ncont = jnp.sum(contend.astype(i32), axis=0, keepdims=True,
                        dtype=i32)
        certain = ncont <= 1
        minq = jnp.min(q, axis=0, keepdims=True)
        i1 = jnp.min(jnp.where(q == minq, iota_s, c32(_S_BIG)),
                     axis=0, keepdims=True)
        winc = jnp.min(jnp.where(contend, iota_s, c32(_S_BIG)),
                       axis=0, keepdims=True)
        # refined top-3 resolution for uncertain draws: pick the three
        # smallest poly draws, re-evaluate them against the exact ln
        # tables (f32, REF_DELTA error), and accept when one candidate's
        # upper bound beats both others' lower bounds and no contender
        # lies outside the top-3.  Floor ties stay flagged (the exact
        # resolve pass settles slot tie-breaks).
        sel1 = iota_s == i1
        qm = jnp.where(sel1, cf32(big), q)
        minq2 = jnp.min(qm, axis=0, keepdims=True)
        i2 = jnp.min(jnp.where(qm == minq2, iota_s, c32(_S_BIG)),
                     axis=0, keepdims=True)
        sel2 = iota_s == i2
        qm2 = jnp.where(sel2, cf32(big), qm)
        minq3 = jnp.min(qm2, axis=0, keepdims=True)
        i3 = jnp.min(jnp.where(qm2 == minq3, iota_s, c32(_S_BIG)),
                     axis=0, keepdims=True)
        sel3 = iota_s == i3

        def pick_i(a, sel):
            return jnp.sum(jnp.where(sel, a, c32(0)), axis=0,
                           keepdims=True, dtype=i32)

        def pick_f(a, sel):
            return jnp.sum(jnp.where(sel, a, cf32(0.0)), axis=0,
                           keepdims=True)

        v2 = minq2 < cf32(big)
        v3 = minq3 < cf32(big)
        qr1 = refine(pick_i(u, sel1), pick_f(recipf, sel1),
                     refp_ref, refl_ref)
        qr2 = refine(pick_i(u, sel2), pick_f(recipf, sel2),
                     refp_ref, refl_ref)
        qr3 = refine(pick_i(u, sel3), pick_f(recipf, sel3),
                     refp_ref, refl_ref)

        def bounds(qr, rfk, vk):
            Ek = (cf32(REF_DELTA) * rfk + qr * cf32(REF_EPS)
                  + cf32(e_const))
            return (jnp.where(vk, qr + Ek, cf32(big)),
                    jnp.where(vk, qr - Ek, cf32(big)))

        ub1, lb1 = bounds(qr1, pick_f(recipf, sel1),
                          jnp.ones_like(v2))
        ub2, lb2 = bounds(qr2, pick_f(recipf, sel2), v2)
        ub3, lb3 = bounds(qr3, pick_f(recipf, sel3), v3)
        w1 = (ub1 < lb2) & (ub1 < lb3)
        w2 = (ub2 < lb1) & (ub2 < lb3)
        w3 = (ub3 < lb1) & (ub3 < lb2)
        outside = contend & ~(sel1 | sel2 | sel3)
        n_out = jnp.sum(outside.astype(i32), axis=0, keepdims=True,
                        dtype=i32)
        ref_ok = (w1 | w2 | w3) & (n_out == 0)
        ref_win = jnp.where(w1, i1, jnp.where(w2, i2, i3))
        win = jnp.where(ncont == 1, winc,
                        jnp.where(ref_ok, ref_win, i1))
        chosen = jnp.sum(jnp.where(iota_s == win, items_a, c32(0)),
                         axis=0, keepdims=True, dtype=i32)
        if d == 0:
            done = size == 0            # empty start bucket: retryable
        flag = flag | ((~done) & (~certain) & (~ref_ok))
        is_bucket = chosen < 0
        cbid = jnp.where(is_bucket, c32(-1) - chosen, c32(0))
        iota_mb = jax.lax.broadcasted_iota(i32, (B, GW), 0)
        ohc = (iota_mb == cbid).astype(i8)
        fm2 = jax.lax.dot_general(
            meta_ref[...], ohc, (((1,), (0,)), ((), ())),
            preferred_element_type=i32)            # [4, GW]
        csize = _unpack_rows(fm2, 1, 2, 0)
        cbtype = _unpack_rows(fm2, 1, 2, 2)
        ctype = jnp.where(is_bucket, cbtype, c32(0))
        oob = (~is_bucket) & (chosen >= c32(max_devices))
        reach = (~done) & (ctype == c32(want_type)) & (~oob)
        wrongdev = (~done) & (~reach) & ((~is_bucket) | oob)
        empty_next = (~done) & (~reach) & is_bucket & (csize == 0)
        item = jnp.where(reach, chosen, item)
        ok = ok | reach
        perm = perm | wrongdev
        done = done | reach | wrongdev | empty_next
        cur = jnp.where((~done) & is_bucket, cbid, cur)
        return cur, done, ok, perm, flag, item

    def kern(x_ref, r_ref, bid_ref, pos_ref, *refs):
        tbl_refs = refs[:n_lvl]
        meta_ref = refs[n_lvl]
        refp_ref, refl_ref = refs[n_lvl + 1], refs[n_lvl + 2]
        item_ref, status_ref = refs[n_lvl + 3], refs[n_lvl + 4]
        x = x_ref[...].astype(u32)                  # [8, GW]
        r = r_ref[...].astype(u32)
        bid = bid_ref[...]
        pos = (jnp.minimum(pos_ref[...], c32(n_pos - 1))
               if n_pos > 1 else bid)
        z = jnp.zeros((1, GW), jnp.bool_)
        states = [
            (bid[s:s + 1, :], z, z, z, z,
             jnp.full((1, GW), ITEM_NONE, i32))
            for s in range(8)
        ]
        for d, S_d in enumerate(depth_sizes):
            for s in range(8):
                states[s] = group(d, S_d, tbl_refs[d], meta_ref,
                                  refp_ref, refl_ref,
                                  x[s:s + 1, :], r[s:s + 1, :],
                                  pos[s:s + 1, :], states[s])
        item_ref[...] = jnp.concatenate([st[5] for st in states],
                                        axis=0)
        status_ref[...] = jnp.concatenate(
            [st[2].astype(i32) | (st[3].astype(i32) << 1)
             | (st[4].astype(i32) << 2) for st in states], axis=0)

    interp = _interpret()

    @jax.jit
    def run(x, r, bid, pos):
        L = x.shape[0]
        G = L // TL
        W = L // 8
        # index maps must yield int32 — under x64 plain ints trace as
        # i64, which mosaic cannot legalize (cf. ec/kernels.py)
        z2 = lambda i: (jnp.int32(0), jnp.int32(0))  # noqa: E731
        shp = jax.ShapeDtypeStruct((8, W), jnp.int32)
        lane = pl.BlockSpec((8, GW),
                            lambda i: (jnp.int32(0), jnp.int32(i)))
        full = [pl.BlockSpec(t.shape, z2) for t in tbls]
        mspec = pl.BlockSpec(meta_t.shape, z2)
        rpspec = pl.BlockSpec(refp_t.shape, z2)
        rlspec = pl.BlockSpec(refl_t.shape, z2)
        item, status = pl.pallas_call(
            kern,
            grid=(G,),
            in_specs=[lane, lane, lane, lane] + full
                     + [mspec, rpspec, rlspec],
            out_specs=(lane, lane),
            out_shape=(shp, shp),
            interpret=interp,
            name="crush_straw2_descend",
        )(x.reshape(8, W).astype(jnp.int32),
          r.reshape(8, W).astype(jnp.int32),
          bid.reshape(8, W).astype(jnp.int32),
          pos.reshape(8, W).astype(jnp.int32),
          *tbls, meta_t, refp_t, refl_t)
        return item.reshape(L), status.reshape(L)

    return run


def make_post_kernel(D: int, S: int, can_shift: bool):
    """Fused post-CRUSH pass (no primary-affinity form): up-filter
    against the exists&up bit per device + stable compaction + primary
    pick (OSDMap.cc:2626-2744) as one kernel over [L] lanes.

    Returns fn(raw [L, S] i32, keep [D] bool) -> (up [L, S] i32,
    prim [L] i32); the affinity path stays on the XLA `_post_process`.
    """
    from jax.experimental import pallas as pl
    from ...models.crushmap import ITEM_NONE

    HI = -(-D // 16)
    i8, i32 = jnp.int8, jnp.int32
    c32 = np.int32
    interp = _interpret()

    def kern(kp_ref, *refs):
        raw_refs = refs[:S]
        up_refs = refs[S:2 * S]
        prim_ref = refs[2 * S]
        iota_hi = jax.lax.broadcasted_iota(i32, (HI, GW), 0)
        iota_16 = jax.lax.broadcasted_iota(i32, (16, GW), 0)
        for s in range(8):
            rows = [r_ref[s:s + 1, :] for r_ref in raw_refs]
            keeps = []
            for rj in rows:
                idx = jnp.clip(rj, c32(0), c32(D - 1))
                oh = (iota_hi == (idx >> 4)).astype(i8)
                kf = jax.lax.dot_general(
                    kp_ref[...], oh, (((1,), (0,)), ((), ())),
                    preferred_element_type=i32)       # [16, GW]
                klo = jnp.sum(
                    jnp.where(iota_16 == (idx & 15), kf, c32(0)),
                    axis=0, keepdims=True, dtype=i32) + 128
                keeps.append((rj != c32(ITEM_NONE)) & (rj < c32(D))
                             & (klo > 0))
            if can_shift:
                ups = [jnp.full((1, GW), ITEM_NONE, i32)
                       for _ in range(S)]
                cnt = jnp.zeros((1, GW), i32)
                for j in range(S):
                    for t in range(j + 1):
                        put = keeps[j] & (cnt == c32(t))
                        ups[t] = jnp.where(put, rows[j], ups[t])
                    cnt = cnt + keeps[j].astype(i32)
            else:
                ups = [jnp.where(keeps[j], rows[j], c32(ITEM_NONE))
                       for j in range(S)]
            prim = jnp.full((1, GW), c32(-1), i32)
            for j in range(S - 1, -1, -1):
                prim = jnp.where(ups[j] != c32(ITEM_NONE), ups[j], prim)
            for j in range(S):
                up_refs[j][s:s + 1, :] = ups[j]
            prim_ref[s:s + 1, :] = prim

    @jax.jit
    def run(raw, keep):
        L = raw.shape[0]
        G = L // TL
        W = L // 8
        kp = ((keep.astype(jnp.int32) - 128).astype(jnp.int8))
        kp = jnp.pad(kp, (0, HI * 16 - D)).reshape(HI, 16).T
        z2 = lambda i: (jnp.int32(0), jnp.int32(0))  # noqa: E731
        lane = pl.BlockSpec((8, GW),
                            lambda i: (jnp.int32(0), jnp.int32(i)))
        shp = jax.ShapeDtypeStruct((8, W), jnp.int32)
        cols = [raw[:, j].reshape(8, W) for j in range(S)]
        outs = pl.pallas_call(
            kern,
            grid=(G,),
            in_specs=[pl.BlockSpec((16, HI), z2)] + [lane] * S,
            out_specs=tuple([lane] * S + [lane]),
            out_shape=tuple([shp] * S + [shp]),
            interpret=interp,
            name="crush_post_up",
        )(kp, *cols)
        up = jnp.stack([o.reshape(L) for o in outs[:S]], axis=1)
        return up, outs[S].reshape(L)

    return run


def make_hitscan_kernel(D: int, S: int):
    """hit[l] = any slot of raw[l] holds an OSD in the changed set —
    the incremental-remap affected-lane scan, as one fused pass over
    the stored raw rows.  Returns fn(raw [L,S] i32, changed [D] bool)
    -> hit [L] bool."""
    from jax.experimental import pallas as pl
    from ...models.crushmap import ITEM_NONE

    HI = -(-D // 16)
    i8, i32 = jnp.int8, jnp.int32
    c32 = np.int32
    interp = _interpret()

    def kern(cp_ref, *refs):
        raw_refs = refs[:S]
        hit_ref = refs[S]
        iota_hi = jax.lax.broadcasted_iota(i32, (HI, GW), 0)
        iota_16 = jax.lax.broadcasted_iota(i32, (16, GW), 0)
        for s in range(8):
            acc = jnp.zeros((1, GW), jnp.bool_)
            for r_ref in raw_refs:
                rj = r_ref[s:s + 1, :]
                idx = jnp.clip(rj, c32(0), c32(D - 1))
                oh = (iota_hi == (idx >> 4)).astype(i8)
                kf = jax.lax.dot_general(
                    cp_ref[...], oh, (((1,), (0,)), ((), ())),
                    preferred_element_type=i32)       # [16, GW]
                klo = jnp.sum(
                    jnp.where(iota_16 == (idx & 15), kf, c32(0)),
                    axis=0, keepdims=True, dtype=i32) + 128
                acc = acc | ((rj != c32(ITEM_NONE)) & (rj < c32(D))
                             & (klo > 0))
            hit_ref[s:s + 1, :] = acc.astype(i32)

    @jax.jit
    def run(raw, changed):
        L = raw.shape[0]
        G = L // TL
        W = L // 8
        cp = ((changed.astype(jnp.int32) - 128).astype(jnp.int8))
        cp = jnp.pad(cp, (0, HI * 16 - D)).reshape(HI, 16).T
        z2 = lambda i: (jnp.int32(0), jnp.int32(0))  # noqa: E731
        lane = pl.BlockSpec((8, GW),
                            lambda i: (jnp.int32(0), jnp.int32(i)))
        cols = [raw[:, j].reshape(8, W) for j in range(S)]
        out = pl.pallas_call(
            kern,
            grid=(G,),
            in_specs=[pl.BlockSpec((16, HI), z2)] + [lane] * S,
            out_specs=lane,
            out_shape=jax.ShapeDtypeStruct((8, W), jnp.int32),
            interpret=interp,
            name="crush_hitscan",
        )(cp, *cols)
        return out.reshape(L) != 0

    return run


def make_rowcompact_kernel(n_lanes: int, row: int, kt: int,
                           pg_num: int):
    """Stream compaction of a sparse boolean mask without cumsum or
    dynamic stores — the jnp.nonzero replacement for the incremental
    remap's affected-lane gather (XLA's 10M-lane nonzero costs ~0.9s
    on this platform; see BENCH notes).

    The mask is viewed as NR = n_lanes/row row groups, 8 groups per
    grid step.  Per group, an MXU triangular-matmul computes hit
    positions (a block-diagonal strict-lower matrix keeps the prefix
    inside each group), a one-hot selection matrix compacts the hit
    lane indices into KT fixed slots (two bf16 limb matmuls reassemble
    indices exactly — single-term sums, so bf16 is lossless), and a
    group-membership matmul folds sublane partials per group.  All
    reads and writes are static blocks: out[g, j] = index of the j-th
    hit in group g, valid[g, j] = j < count(g) and index < pg_num.
    Pad slots carry the group base lane (a real, harmless duplicate
    for the resolve gather/scatter downstream).  Rows with count > KT
    overflow — detected via the cnt output's max, never silent.

    Returns fn(hit [n_lanes] bool) ->
      (idx [NR*kt] int32, valid [NR*kt] bool, cnt [NR] int32).
    """
    from jax.experimental import pallas as pl

    if n_lanes % (8 * row) or row % 128 or kt % 128:
        raise ValueError("rowcompact: n_lanes %d / row %d / kt %d "
                         "misaligned" % (n_lanes, row, kt))
    r2 = row // 128          # sublane rows per group
    s8 = 8 * r2              # sublane rows per grid step (8 groups)
    nr = n_lanes // row
    interp = _interpret()
    i32 = jnp.int32
    f32 = jnp.float32
    bf16 = jnp.bfloat16

    # U[j, i] = 1 for j <= i: h @ U = inclusive prefix along lanes
    U128 = np.triu(np.ones((128, 128), np.float32))
    # block-diagonal strict-lower: exclusive prefix over sublane rows
    # WITHIN each group of r2 rows
    LxB = np.zeros((s8, s8), np.float32)
    for g in range(8):
        LxB[g * r2:(g + 1) * r2, g * r2:(g + 1) * r2] = \
            np.tril(np.ones((r2, r2)), k=-1)
    # group membership: G[g, q] = 1 iff sublane row q is in group g
    Gm = np.zeros((8, s8), np.float32)
    for g in range(8):
        Gm[g, g * r2:(g + 1) * r2] = 1.0

    def kern(h_ref, u_ref, lx_ref, gm_ref, idx_ref, val_ref,
             cnt_ref):
        step = pl.program_id(0)
        h = h_ref[...].astype(f32)                       # (s8, 128)
        # hits in the padded lane region [pg_num, n_lanes) must not
        # occupy slots or counts (they would inflate rowmax and waste
        # settle work); mask them at the source
        glane = (jax.lax.broadcasted_iota(i32, (s8, 128), 0)
                 + step * np.int32(s8)) * np.int32(128) \
            + jax.lax.broadcasted_iota(i32, (s8, 128), 1)
        h = jnp.where(glane < np.int32(pg_num), h, 0.0)
        hb = h > 0.0
        p1 = jax.lax.dot_general(
            h, u_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                  # (s8, 128)
        rsum = jnp.broadcast_to(p1[:, 127:128], (s8, 128))
        roff = jax.lax.dot_general(
            lx_ref[...], rsum, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                  # (s8, 128)
        roffv = roff[:, 0:1]                             # (s8, 1)
        rsumv = p1[:, 127:128]                           # (s8, 1)
        totals = jax.lax.dot_general(
            gm_ref[...], rsum, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)[:, 0:1]          # (8, 1)
        # D[r, jr] = lane of the (jr+1)-th hit in sublane row r (a row
        # of 128 lanes holds at most 128 hits, so 128 columns always
        # suffice); built as 128 masked lane-reductions — single-term
        # sums, exact in f32
        lane_f = jax.lax.broadcasted_iota(
            i32, (s8, 128), 1).astype(f32)
        cols = [jnp.sum(jnp.where((p1 - 1.0 == np.float32(jr)) & hb,
                                  lane_f, 0.0),
                        axis=1, keepdims=True)
                for jr in range(128)]
        D = jnp.concatenate(cols, axis=1)                # (s8, 128)
        if kt > 128:
            D = jnp.concatenate(
                [D, jnp.zeros((s8, kt - 128), f32)], axis=1)
        # place row r's hits at group slots [roff[r], roff[r]+rsum[r]):
        # a per-row roll by roff[r], decomposed into static
        # conditional rolls (Mosaic has no per-row dynamic shift);
        # wrapped-around junk lands outside the row's slot interval
        # and is masked by rowsel (capacity overflow is caught via
        # cnt > kt, never silent)
        roffi = roffv.astype(i32)
        sh = D
        b = 1
        while b < kt:
            cond = ((roffi // np.int32(b)) % np.int32(2)) == 1
            sh = jnp.where(cond, jnp.roll(sh, b, axis=1), sh)
            b *= 2
        slot_f = jax.lax.broadcasted_iota(
            i32, (s8, kt), 1).astype(f32)
        rowsel = (slot_f >= roffv) & (slot_f < roffv + rsumv)
        sub_f = (jax.lax.broadcasted_iota(i32, (s8, kt), 0)
                 % np.int32(r2)).astype(f32)
        # fold sublane and lane components through SEPARATE matmuls:
        # the MXU's default precision multiplies in bf16, which is
        # only exact below 256 — sub (< r2) and lane (< 128) each
        # qualify, their 128-scaled sum would not
        sub_m = jnp.where(rowsel, sub_f, 0.0)
        lane_m = jnp.where(rowsel, sh, 0.0)
        fold = lambda x: jax.lax.dot_general(  # noqa: E731
            gm_ref[...], x, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        gbase = (step * np.int32(8)
                 + jax.lax.broadcasted_iota(i32, (8, kt), 0)) \
            * np.int32(row)
        idx = (fold(sub_m).astype(i32) * np.int32(128)
               + fold(lane_m).astype(i32) + gbase)       # (8, kt)
        slot8 = jax.lax.broadcasted_iota(
            i32, (8, kt), 1).astype(f32)
        valid = ((slot8 < totals)
                 & (idx < np.int32(pg_num))).astype(i32)
        idx_ref[...] = idx
        val_ref[...] = valid
        cnt_ref[...] = jnp.broadcast_to(totals.astype(i32), (8, 128))

    @jax.jit
    def run(hit):
        h2 = hit.astype(i32).reshape(n_lanes // 128, 128)
        z2 = lambda i: (i32(0), i32(0))  # noqa: E731
        o8 = lambda i: (i32(i), i32(0))  # noqa: E731
        idx, val, cnt = pl.pallas_call(
            kern,
            grid=(nr // 8,),
            in_specs=[
                pl.BlockSpec((s8, 128), o8),
                pl.BlockSpec((128, 128), z2),
                pl.BlockSpec((s8, s8), z2),
                pl.BlockSpec((8, s8), z2),
            ],
            out_specs=[
                pl.BlockSpec((8, kt), o8),
                pl.BlockSpec((8, kt), o8),
                pl.BlockSpec((8, 128), o8),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((nr, kt), jnp.int32),
                jax.ShapeDtypeStruct((nr, kt), jnp.int32),
                jax.ShapeDtypeStruct((nr, 128), jnp.int32),
            ],
            interpret=interp,
            name="crush_rowcompact",
        )(h2, jnp.asarray(U128), jnp.asarray(LxB), jnp.asarray(Gm))
        return (idx.reshape(-1), val.reshape(-1) != 0, cnt[:, 0])

    return run


def _group_limbs(vals, nr: int):
    """[nr * per, words] i32 rows of nr row groups -> [nr, 4*words, per]
    int8 limb planes for a one-hot matmul over a group's rows:
    word-major, then limb, biased by -128."""
    u32 = jnp.uint32
    per, words = vals.shape[0] // nr, vals.shape[1]
    vu = vals.astype(jnp.int32).astype(u32).reshape(nr, per, words)
    vu = jnp.transpose(vu, (0, 2, 1))[:, :, None, :]
    shifts = (jnp.arange(4, dtype=u32) * u32(8))[None, None, :, None]
    limbs = ((vu >> shifts) & u32(0xFF)).astype(jnp.int32) - 128
    return limbs.astype(jnp.int8).reshape(nr, 4 * words, per)


def make_rowgather_kernel(n_lanes: int, row: int, kt: int, words: int):
    """rowcompact with the lanes' data: slot j of row group g takes the
    `words` int32 of the group's j-th hit lane, for j below the group's
    count and kt; a slot past that reads 0.  The seats are rowcompact's
    and rowexpand's (slot = rank, the hits before the lane in its
    group).

    No gather (scalar rate on TPU): per group the int8 one-hot
    [kt, row] of slot against rank that rowexpand builds, contracted
    over the lanes with the values' 8-bit limb planes [4*words, row] on
    the MXU; one lane is hot per seated slot, so each product IS a limb.

    Returns fn(hit [n_lanes] bool, vals [n_lanes, words] i32)
      -> [n_lanes/row*kt, words] i32.
    """
    from jax.experimental import pallas as pl

    if n_lanes % (8 * row) or row % 128 or kt % 128:
        raise ValueError("rowgather: n_lanes %d / row %d / kt %d "
                         "misaligned" % (n_lanes, row, kt))
    nr = n_lanes // row
    interp = _interpret()
    i8, i32 = jnp.int8, jnp.int32
    c32 = np.int32
    kb = 128     # slots per matmul: bounds the one-hot held in VMEM

    def kern(hit_ref, rank_ref, val_ref, *out_refs):
        iota_k = jax.lax.broadcasted_iota(i32, (kb, row), 0)
        slot = jax.lax.broadcasted_iota(i32, (1, kb), 1)
        for s in range(8):
            rk = rank_ref[s:s + 1, :]                    # [1, row]
            hit = hit_ref[s:s + 1, :] != 0
            total = rk[:, row - 1:row] + c32(1)          # [1, 1]
            for k0 in range(0, kt, kb):
                oh = ((iota_k + c32(k0) == rk) & hit).astype(i8)
                f = jax.lax.dot_general(
                    val_ref[s], oh, (((1,), (1,)), ((), ())),
                    preferred_element_type=i32)          # [4*words, kb]
                filled = slot + c32(k0) < total
                for w in range(words):
                    out_refs[w][s:s + 1, k0:k0 + kb] = jnp.where(
                        filled, _unpack_rows(f, 1, 4, 4 * w), c32(0))

    @jax.jit
    def run(hit, vals):
        h2 = hit.astype(i32).reshape(nr, row)
        rank = jnp.cumsum(h2, axis=1, dtype=i32) - 1
        limbs = _group_limbs(vals, nr)
        lane = pl.BlockSpec((8, row), lambda i: (i32(i), i32(0)))
        slots = pl.BlockSpec((8, kt), lambda i: (i32(i), i32(0)))
        outs = pl.pallas_call(
            kern,
            grid=(nr // 8,),
            in_specs=[lane, lane,
                      pl.BlockSpec((8, 4 * words, row),
                                   lambda i: (i32(i), i32(0), i32(0)))],
            out_specs=tuple([slots] * words),
            out_shape=tuple(
                [jax.ShapeDtypeStruct((nr, kt), i32)] * words),
            interpret=interp,
            name="crush_rowgather",
        )(h2, rank, limbs)
        return jnp.stack([o.reshape(nr * kt) for o in outs], axis=1)

    return run


def make_rowexpand_kernel(n_lanes: int, row: int, kt: int, words: int):
    """rowcompact's inverse, for the rows computed on its compacted
    lanes: a hit lane takes the row at its group's slot number rank(l),
    the count of hits before it in its row group, which is where
    rowcompact seated it; a lane that is no hit, or whose group had no
    slot left for it (rank >= kt), keeps its old row.  Pad slots are
    never read, so nothing depends on what was computed there.

    No gather and no scatter (both run at scalar rate on TPU): per
    group one int8 one-hot [kt, row] of slot against rank, and one MXU
    matmul of the new rows' 8-bit limb planes [4*words, kt] with it;
    one slot is hot per seated lane, so each product IS a limb.

    Returns fn(hit [n_lanes] bool, old [n_lanes, words] i32,
               new [n_lanes/row*kt, words] i32) -> [n_lanes, words] i32.
    """
    from jax.experimental import pallas as pl

    if n_lanes % (8 * row) or row % 128 or kt % 128:
        raise ValueError("rowexpand: n_lanes %d / row %d / kt %d "
                         "misaligned" % (n_lanes, row, kt))
    nr = n_lanes // row
    interp = _interpret()
    i8, i32 = jnp.int8, jnp.int32
    c32 = np.int32

    def kern(hit_ref, rank_ref, new_ref, *refs):
        old_refs, out_refs = refs[:words], refs[words:]
        iota_k = jax.lax.broadcasted_iota(i32, (kt, row), 0)
        for s in range(8):
            rk = rank_ref[s:s + 1, :]                    # [1, row]
            seated = (hit_ref[s:s + 1, :] != 0) & (rk < c32(kt))
            oh = ((iota_k == rk) & seated).astype(i8)    # [kt, row]
            f = jax.lax.dot_general(
                new_ref[s], oh, (((1,), (0,)), ((), ())),
                preferred_element_type=i32)              # [4*words, row]
            for w in range(words):
                val = _unpack_rows(f, 1, 4, 4 * w)
                out_refs[w][s:s + 1, :] = jnp.where(
                    seated, val, old_refs[w][s:s + 1, :])

    @jax.jit
    def run(hit, old, new):
        h2 = hit.astype(i32).reshape(nr, row)
        rank = jnp.cumsum(h2, axis=1, dtype=i32) - 1
        limbs = _group_limbs(new, nr)
        lane = pl.BlockSpec((8, row), lambda i: (i32(i), i32(0)))
        shp = jax.ShapeDtypeStruct((nr, row), i32)
        outs = pl.pallas_call(
            kern,
            grid=(nr // 8,),
            in_specs=[lane, lane,
                      pl.BlockSpec((8, 4 * words, kt),
                                   lambda i: (i32(i), i32(0), i32(0)))]
                     + [lane] * words,
            out_specs=tuple([lane] * words),
            out_shape=tuple([shp] * words),
            interpret=interp,
            name="crush_rowexpand",
        )(h2, rank, limbs,
          *[old[:, w].astype(i32).reshape(nr, row) for w in range(words)])
        return jnp.stack([o.reshape(n_lanes) for o in outs], axis=1)

    return run
