"""Vectorized CRUSH mapping on device: one jitted program maps millions
of PGs at once.

This is the TPU replacement for the reference's threaded bulk mapper
(src/osd/OSDMapMapping.h:18-120 ParallelPGMapper) and the inner loops it
shards (crush_do_rule / crush_choose_firstn / crush_choose_indep,
src/crush/mapper.c:438-821): the PG axis becomes the vector lane axis
and the straw2 exponential draw (mapper.c:316-345) runs vectorized with
results bit-identical to the host engine (ceph_tpu.ops.crush.host) and
the reference golden vectors.

Bit-exactness strategy (the straw2 winner is argmax of
trunc((crush_ln(u)-2^48)/w), equivalently argmin of
q = floor((2^48-crush_ln(u))//w) with first-index tie-break):

* **f32 fast path**: q is approximated as g_f32(u) * (1/w) where g_f32
  is a degree-7 polynomial in the mantissa of u+1 fitted to the exact
  crush_ln table (max abs deviation DELTA, measured exhaustively over
  all 65536 inputs).  A per-item error bound
  E_i = DELTA/w_i + |q_i|*2^-14 + 4 makes the winner *provably* exact
  whenever the f32 gap between best and second-best exceeds E_1 + E_2.
  That covers ~99.4% of draws; no int64, no table lookups, fuses into
  a single XLA elementwise+reduce pass.
* **exact top-2 resolution**: in resolve mode the remaining draws are
  settled by computing the exact integer q for only the top-2
  candidates — crush_ln via one-hot MXU table fetches on an [L,2]
  slice (neg_ln_mxu) and an exact base-2^13 schoolbook division.
  Sound because any item outside the top-2 is > E away from the
  minimum (checked against the third-best).
* **host dust**: lanes where even the top-3 are inside the bound
  (~1e-5 of visits) fall back to the scalar host engine.

Retry control flow (collision/rejection retries, mapper.c:475-626) is
restructured for SIMD.  Large batches run the "attempt" structure: per
replica a fixed number of optimistic rounds (ftotal = 0, 1, ...) with no
data-dependent loop; a lane still unplaced after them, or whose f32
draw was uncertain, is flagged, and the device-resident resolve chain
recomputes the flagged lanes exactly (top-3 resolution, then the full
retry loops, then the all-integer draw).  In the dense pass of a whole
pool (_compiled_pool) only the first round of each replica runs at full
width: the lanes it leaves unplaced are compacted per chunk (the Pallas
rowcompact kernel), replayed from scratch through the attempt structure
at the compacted width, and their rows put back (the rowexpand kernel).
Small batches and the resolve chain's later stages run the full retry
loops.

Device scope (the modern "optimal" tunables profile): straw2 buckets at
every level, choose_local_tries == choose_local_fallback_tries == 0,
rules of shape TAKE -> one or more CHOOSE/CHOOSELEAF steps -> EMIT, the
steps all firstn or all indep (root -> rack -> host: `choose indep 2
type rack; chooseleaf indep 4 type host`).  A later step takes per lane
from the step before it, as crush_do_rule chains its working vector
(_chain_step).  Anything else -- a rule that mixes firstn and indep
steps, a second TAKE/EMIT pair, a step below a chooseleaf, a non-straw2
bucket, local retries -- raises ValueError and falls back to the host
interpreter, which remains the general spec.
"""

from __future__ import annotations

import collections
import functools
import math
from contextlib import nullcontext

import numpy as np

import jax
import jax.numpy as jnp

from ...models.crushmap import (
    CHOOSE_FIRSTN,
    CHOOSE_INDEP,
    CHOOSELEAF_FIRSTN,
    CHOOSELEAF_INDEP,
    EMIT,
    ITEM_NONE,
    ITEM_UNDEF,
    SET_CHOOSE_TRIES,
    SET_CHOOSELEAF_TRIES,
    SET_CHOOSELEAF_STABLE,
    SET_CHOOSELEAF_VARY_R,
    STRAW2,
    TAKE,
    CrushMap,
)
from ...trace.span import mark, scope, span
from ._ln_tables import LL_TBL, RH_LH_TBL

S64_MAX = (1 << 63) - 1
LN_ONE = 1 << 48  # 2^48: crush_ln scale at u=0xFFFF+1

HASH_SEED = 1315423911


# ---------------------------------------------------------------------------
# jnp primitives (bit-for-bit mirrors of hashes.py / host.crush_ln)
# ---------------------------------------------------------------------------

def _u32(v):
    return jnp.asarray(v, jnp.uint32)


def _mix(a, b, c):
    a = a - b; a = a - c; a = a ^ (c >> _u32(13))
    b = b - c; b = b - a; b = b ^ (a << _u32(8))
    c = c - a; c = c - b; c = c ^ (b >> _u32(13))
    a = a - b; a = a - c; a = a ^ (c >> _u32(12))
    b = b - c; b = b - a; b = b ^ (a << _u32(16))
    c = c - a; c = c - b; c = c ^ (b >> _u32(5))
    a = a - b; a = a - c; a = a ^ (c >> _u32(3))
    b = b - c; b = b - a; b = b ^ (a << _u32(10))
    c = c - a; c = c - b; c = c ^ (b >> _u32(15))
    return a, b, c


def hash32_3_j(a, b, c):
    a, b, c = _u32(a), _u32(b), _u32(c)
    h = _u32(HASH_SEED) ^ a ^ b ^ c
    x, y = _u32(231232), _u32(1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def hash32_2_j(a, b):
    a, b = _u32(a), _u32(b)
    h = _u32(HASH_SEED) ^ a ^ b
    x, y = _u32(231232), _u32(1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


# ---------------------------------------------------------------------------
# f32 certainty draw
#
# g_f32(u) ~ 2^48 - crush_ln(u): exponent via f32 bit tricks (u+1 <= 2^16
# is f32-exact), mantissa log via a degree-7 polynomial least-squares
# fitted to the exact table values (which themselves deviate from smooth
# log2 by ~2^29.6 — the table's own 16-bit-mantissa quantization noise,
# so a closer smooth fit is impossible).  _G_DELTA is the exhaustively
# measured max |g_f32(u) - (2^48-crush_ln(u))| over all 65536 inputs
# (f32-simulated Horner), doubled for device reassociation/FMA headroom.
# Regenerated + verified by tests/test_crush_device.py::TestF32Draw.
# ---------------------------------------------------------------------------

_LOG2_COEF = (
    5.405197953223251e-06, 1.4423911571502686, -0.7177810668945312,
    0.46077853441238403, -0.2956102788448334, 0.15550757944583893,
    -0.05415186285972595, 0.00885970052331686,
)
_G_DELTA = 825135650.0 * 2.0
_EPS_Q = 2.0 ** -21      # q = g*recipf relative error: recipf is the
                         # correctly-rounded f32 of 1/w (2^-24) plus one
                         # product rounding (2^-24), with 4x margin
_E_CONST = 4.0           # floor slack + crumbs
_BIG = jnp.float32(3.0e38)


def _g_f32(u):
    """f32 approximation of 2^48 - crush_ln(u), u int in [0, 0xFFFF]."""
    x = (u + 1).astype(jnp.int32)
    xf = x.astype(jnp.float32)
    b = jax.lax.bitcast_convert_type(xf, jnp.int32)
    e = ((b >> 23) - 127).astype(jnp.float32)
    mm = jax.lax.bitcast_convert_type(
        (b & 0x7FFFFF) | 0x3F800000, jnp.float32) - jnp.float32(1.0)
    acc = jnp.float32(_LOG2_COEF[-1])
    for c in _LOG2_COEF[-2::-1]:
        acc = acc * mm + jnp.float32(c)
    return jnp.float32(2.0 ** 44) * ((jnp.float32(16.0) - e) - acc)


# ---------------------------------------------------------------------------
# gather-free table lookups (for the exact top-2 resolution)
#
# TPU gathers are scalar-rate while the one-hot int8 matmul rides the
# MXU; table values are split into 8-bit limbs offset by -128, the index
# becomes a one-hot row, and a single [N, K] @ [K, n_limbs] int8->int32
# matmul fetches all limbs.  One row is hot, so each output element IS a
# limb value (no summation error).
# ---------------------------------------------------------------------------


def pack_limbs(table: np.ndarray, n_limbs: int,
               offset: int = 0) -> np.ndarray:
    """[K] int -> [K, n_limbs] int8 of 8-bit limbs of (v - offset),
    biased by -128 into signed range."""
    t = table.astype(object) - offset
    out = np.zeros((len(t), n_limbs), dtype=np.int8)
    for i, v in enumerate(t):
        v = int(v)
        assert 0 <= v < (1 << (8 * n_limbs)), (v, n_limbs)
        for j in range(n_limbs):
            out[i, j] = ((v >> (8 * j)) & 0xFF) - 128
    return out


def unpack_limbs(l32, n_limbs: int, offset: int = 0,
                 dtype=jnp.int64):
    """[.., n_limbs] int32 (from the one-hot matmul) -> [..] dtype."""
    acc = jnp.zeros(l32.shape[:-1], jnp.int64)
    for j in range(n_limbs):
        limb = (l32[..., j] + 128).astype(jnp.int64)
        acc = acc + (limb << (8 * j))
    return (acc + offset).astype(dtype)


def unpack_limbs32(l32, n_limbs: int, offset: int = 0):
    """int32 fast-path unpack for values that fit 31 bits (ids, recip
    bit patterns, sizes): int64 vector math halves TPU throughput and
    doubles HBM traffic, so the hot path avoids it."""
    acc = l32[..., 0] + 128
    for j in range(1, n_limbs):
        acc = acc + ((l32[..., j] + 128) << (8 * j))
    if offset:
        acc = acc + offset
    return acc


def onehot_fetch(idx, limb_table):
    """idx [..] int32 in [0, K); limb_table [K, C] int8.
    Returns [.., C] int32 via one MXU matmul."""
    K = limb_table.shape[0]
    shape = idx.shape
    flat = idx.reshape(-1)
    oh = (flat[:, None] == jnp.arange(K, dtype=jnp.int32)[None, :]
          ).astype(jnp.int8)
    out = jnp.matmul(oh, limb_table, preferred_element_type=jnp.int32)
    return out.reshape(*shape, limb_table.shape[1])


_RH_NP = np.array(RH_LH_TBL[0::2], dtype=np.uint64)   # 129 reciprocals
_LH_NP = np.array(RH_LH_TBL[1::2], dtype=np.uint64)
_LL_NP = np.array(LL_TBL, dtype=np.uint64)
_LN_NLIMB = 7  # values < 2^56
_RHLH_LIMBS_NP = np.concatenate(
    [pack_limbs(_RH_NP, _LN_NLIMB), pack_limbs(_LH_NP, _LN_NLIMB)], axis=1)
_LL_LIMBS_NP = pack_limbs(_LL_NP, _LN_NLIMB)


def neg_ln_mxu(u, rhlh_limbs, ll_limbs):
    """2^48 - crush_ln(u) for u int64 in [0, 0xFFFF], no gathers:
    the iexpon/normalisation arithmetic stays on the VPU and the three
    table fetches (RH, LH, LL — crush_ln's own structure, mapper.c:
    226-268) ride the MXU as one-hot matmuls."""
    x = u.astype(jnp.int64) + 1            # [1, 0x10000]
    bl = jnp.ones_like(x)
    for kbit in range(1, 17):
        bl = bl + (x >= (1 << kbit)).astype(jnp.int64)
    need = (x & 0x18000) == 0
    bits = jnp.maximum(16 - bl, 0)
    x2 = jnp.where(need, x << bits, x)
    iexpon = jnp.where(need, 15 - bits, 15)
    p = ((x2 >> 8) - 128).astype(jnp.int32)          # [0, 128]
    rl = onehot_fetch(p, rhlh_limbs)
    rh = unpack_limbs(rl[..., :_LN_NLIMB], _LN_NLIMB)
    lh = unpack_limbs(rl[..., _LN_NLIMB:], _LN_NLIMB)
    xl64 = (x2 * rh) >> 48
    i2 = (xl64 & 0xFF).astype(jnp.int32)
    ll = unpack_limbs(onehot_fetch(i2, ll_limbs), _LN_NLIMB)
    lh2 = (lh + ll) >> 4
    return (1 << 48) - ((iexpon << 44) + lh2)


def _exact_floordiv(neg, w64, recipf):
    """Exact floor(neg / w) for neg int64 in [0, 2^49), w64 int64 > 0:
    base-2^13 schoolbook long division with f32 digit estimation and
    +/-2-step correction (each digit < 2^13, so the f32 estimate of
    cur/w is within 2 of the true digit).  Replaces per-item
    magic-constant division: w arrives at runtime here."""
    q = jnp.zeros_like(neg)
    r = jnp.zeros_like(neg)
    for shift in (39, 26, 13, 0):
        d = (neg >> shift) & 0x1FFF
        cur = (r << 13) + d
        est = (cur.astype(jnp.float32) * recipf).astype(jnp.int64)
        est = jnp.clip(est, 0, 1 << 13)
        rem = cur - est * w64
        for _ in range(2):
            lo = rem < 0
            est = jnp.where(lo, est - 1, est)
            rem = jnp.where(lo, rem + w64, rem)
        for _ in range(2):
            hi = rem >= w64
            est = jnp.where(hi, est + 1, est)
            rem = jnp.where(hi, rem - w64, rem)
        q = (q << 13) + est
        r = rem
    return q


def _exact3_winner(fm, us, ws, ss):
    """Exact straw2 comparison among the three f32 front-runners:
    integer q = floor((2^48-crush_ln(u))/w) for each, lexicographic
    (q, slot) minimum — the first-slot tie-break mirrors mapper.c's
    strict-> draw comparison keeping the earliest maximum.  Resolving
    three (not two) candidates pushes the residual ambiguity (true
    winner outside the resolved set) from ~2.5e-5 per visit to ~1e-7,
    so retry-heavy lanes no longer shed host-fallback dust."""
    u = jnp.stack(us, axis=-1)
    neg = neg_ln_mxu(u, jnp.asarray(_RHLH_LIMBS_NP),
                     jnp.asarray(_LL_LIMBS_NP))
    w = jnp.stack(ws, axis=-1).astype(jnp.int64) & 0xFFFFFFFF
    wsafe = jnp.maximum(w, 1)
    recipf = jnp.float32(1.0) / wsafe.astype(jnp.float32)
    q = _exact_floordiv(neg, wsafe, recipf)
    q = jnp.where(w > 0, q, jnp.int64(S64_MAX))
    best_q, best_s = q[..., 0], ss[0]
    for j in range(1, len(ss)):
        qj, sj = q[..., j], ss[j]
        take = (qj < best_q) | ((qj == best_q) & (sj < best_s))
        best_q = jnp.where(take, qj, best_q)
        best_s = jnp.where(take, sj, best_s)
    return best_s


# ---------------------------------------------------------------------------
# flattened map
# ---------------------------------------------------------------------------


class _ConstRow:
    """Host-side row of one bucket (the static TAKE root): lets level-0
    draws skip the one-hot row fetch entirely (every lane shares the
    bucket, so ids/weights are jit-time constants)."""

    __slots__ = ("ids", "items", "recipf", "w", "size")

    def __init__(self, ids, items, recipf, w, size):
        self.ids = ids          # np [S] int32
        self.items = items      # np [S] int32
        self.recipf = recipf    # np [S] f32 (correctly-rounded 1/w)
        self.w = w              # np [S] int32
        self.size = size        # python int


class FlatMap:
    """CrushMap flattened to dense arrays. Bucket index bid = -1 - id."""

    def __init__(self, m: CrushMap, choose_args_name: str | None = None):
        for b in m.buckets.values():
            if b.alg != STRAW2:
                raise ValueError(
                    "device mapper requires straw2 buckets (bucket %d has "
                    "alg %d)" % (b.id, b.alg))
        t = m.tunables
        if t.choose_local_tries or t.choose_local_fallback_tries:
            raise ValueError("device mapper requires local tries == 0")
        B = m.max_buckets or 1
        S = max((b.size for b in m.buckets.values()), default=1) or 1
        self.B, self.S = B, S
        self.max_devices = m.max_devices
        self.tunables = t
        size = np.zeros(B, np.int32)
        btype = np.zeros(B, np.int32)
        items = np.zeros((B, S), np.int32)
        ids = np.zeros((B, S), np.int32)
        cargs = (m.choose_args.get(choose_args_name)
                 if choose_args_name else None)
        n_pos = 1
        if cargs:
            n_pos = max((len(ws.weight_sets) for ws in cargs.values()
                         if ws.weight_sets), default=1) or 1
        pos_w = np.zeros((n_pos, B, S), np.int64)
        for b in m.buckets.values():
            bid = -1 - b.id
            size[bid] = b.size
            btype[bid] = b.type
            items[bid, :b.size] = b.items
            ids[bid, :b.size] = b.items
            for p in range(n_pos):
                pos_w[p, bid, :b.size] = b.item_weights
            if cargs and b.id in cargs:
                ws = cargs[b.id]
                if ws.ids is not None:
                    ids[bid, :b.size] = ws.ids
                if ws.weight_sets:
                    for p in range(n_pos):
                        src = ws.weight_sets[min(p, len(ws.weight_sets) - 1)]
                        pos_w[p, bid, :b.size] = src
        depth: dict[int, int] = {}

        def _depth(bid_id: int) -> int:
            if bid_id in depth:
                return depth[bid_id]
            b = m.buckets[bid_id]
            d = 1 + max((_depth(i) for i in b.items if i < 0), default=0)
            depth[bid_id] = d
            return d

        self.max_depth = max((_depth(i) for i in m.buckets), default=1)
        self.n_pos = n_pos
        self.rules = dict(m.rules)

        # correctly-rounded f32 reciprocals of the 16.16 weights: full
        # mantissa keeps the q-product error inside the tight _EPS_Q
        with np.errstate(divide="ignore"):
            recipf = np.where(
                pos_w > 0,
                (np.float32(1.0)
                 / np.maximum(pos_w, 1).astype(np.float32)),
                np.float32(0.0)).astype(np.float32)
        self._recipbits_np = recipf.view(np.uint32).astype(np.int64)
        self._recipf_np = recipf
        self._w_np = pos_w

        # -- gather-free lookup tables -----------------------------------
        # per-(pos,bucket) row: for each item slot s, limbs
        # [ids(nl) | items(nl) | recip(4)], then size(2) + btype(2) at
        # the tail.  Fetched with ONE one-hot matmul per bucket visit.
        # Tables are built per requested item capacity S'
        # (row_limbs_for) so each descent level only pays for the
        # largest bucket actually reachable there.
        id_lo = min([0] + [int(v) for v in items.reshape(-1)]
                    + [int(v) for v in ids.reshape(-1)])
        id_hi = max([0] + [int(v) for v in items.reshape(-1)]
                    + [int(v) for v in ids.reshape(-1)])
        self.id_offset = id_lo
        self.nl_id = 3 if (id_hi - id_lo) < (1 << 24) else 4
        # without choose_args id remapping the ids ARE the items: rows
        # then carry one copy and the fetch/unpack does half the work
        self.ids_equal_items = bool(np.array_equal(ids, items))
        self._ids_np = ids
        self._items_np = items
        self._size_np = size
        self._btype_np = btype
        self._row_cache: dict[int, np.ndarray] = {}
        self._roww_cache: dict[int, np.ndarray] = {}
        self._wpair_cache: dict[int, np.ndarray] = {}
        # lanes of a dense pass -> its descents were traced in Pallas
        self.descent_in_pallas: dict[int, bool] = {}
        # per-bucket metadata fetch for arbitrary bucket ids (the child
        # bucket chosen during descent): size(2) + btype(2)
        meta = np.zeros((B, 4), np.int8)
        meta[:, 0:2] = pack_limbs(size, 2)
        meta[:, 2:4] = pack_limbs(btype, 2)
        self.meta_limbs = jnp.asarray(meta)

    def row_limbs_for(self, S: int) -> np.ndarray:
        """[n_pos*B, (2*nl_id+3)*S+4] int8 rows truncated to S item
        slots (only fetched for buckets whose size fits — callers pick
        S per level)."""
        tbl = self._row_cache.get(S)
        if tbl is not None:
            return tbl
        B, n_pos, nl = self.B, self.n_pos, self.nl_id
        dup = 0 if self.ids_equal_items else nl
        pi = nl + dup + 4
        rows = np.zeros((n_pos * B, pi * S + 4), np.int8)
        for p in range(n_pos):
            for bi in range(B):
                row = np.zeros((S, pi), np.int8)
                row[:, 0:nl] = pack_limbs(self._ids_np[bi, :S], nl,
                                          self.id_offset)
                if dup:
                    row[:, nl:2 * nl] = pack_limbs(
                        self._items_np[bi, :S], nl, self.id_offset)
                row[:, nl + dup:pi] = pack_limbs(
                    self._recipbits_np[p, bi, :S], 4)
                r = rows[p * B + bi]
                r[:pi * S] = row.reshape(-1)
                r[pi * S:pi * S + 2] = pack_limbs(
                    self._size_np[bi:bi + 1], 2)[0]
                r[pi * S + 2:] = pack_limbs(
                    self._btype_np[bi:bi + 1], 2)[0]
        # Cache as host numpy: this is lazily reached inside jit traces,
        # where jnp.asarray would bind the constant to the live trace and
        # the cached tracer would leak into later traces.
        self._row_cache[S] = rows
        return rows

    def roww_limbs_for(self, S: int) -> np.ndarray:
        """[n_pos*B, 4*S] int8 weight rows (resolve mode only)."""
        tbl = self._roww_cache.get(S)
        if tbl is not None:
            return tbl
        B, n_pos = self.B, self.n_pos
        rows = np.zeros((n_pos * B, 4 * S), np.int8)
        for p in range(n_pos):
            for bi in range(B):
                rows[p * B + bi] = pack_limbs(
                    self._w_np[p, bi, :S], 4).reshape(-1)
        self._roww_cache[S] = rows
        return rows

    def wpair_limbs_for(self, S: int) -> np.ndarray | None:
        """[n_pos*B*S, 4] int8 per-(bucket,slot) weight limbs: lets the
        resolve path fetch just the top-3 candidates' weights instead of
        unpacking [L, S] int64 rows.  None when the flattened table is
        too large for a one-hot fetch."""
        if self.n_pos * self.B * S > 65536:
            return None
        tbl = self._wpair_cache.get(S)
        if tbl is None:
            w = np.ascontiguousarray(
                self._w_np[:, :, :S]).reshape(-1)
            tbl = pack_limbs(w, 4)
            self._wpair_cache[S] = tbl
        return tbl

    def const_row(self, bucket_id: int, S: int) -> _ConstRow | None:
        """Host row of a single static bucket (level-0 fetch skip);
        None when positional weight-sets make rows lane-dependent."""
        if self.n_pos != 1 or bucket_id >= 0:
            return None
        bi = -1 - bucket_id
        return _ConstRow(
            ids=self._ids_np[bi, :S].copy(),
            items=self._items_np[bi, :S].copy(),
            recipf=self._recipf_np[0, bi, :S].copy(),
            w=self._w_np[0, bi, :S].astype(np.int64),
            size=int(self._size_np[bi]))


# ---------------------------------------------------------------------------
# vector choose primitives
# ---------------------------------------------------------------------------


def _fetch_row(fm: FlatMap, bid, pos, S: int):
    """One one-hot matmul fetches a bucket's full choose row:
    (ids [L,S], items [L,S], recipf [L,S] f32, size [L])."""
    if fm.n_pos == 1:
        idx = bid
    else:
        idx = jnp.minimum(pos, fm.n_pos - 1) * fm.B + bid
    nl = fm.nl_id
    dup = 0 if fm.ids_equal_items else nl
    pi = nl + dup + 4
    r = onehot_fetch(idx, fm.row_limbs_for(S))       # [L, pi*S+4] int32
    per = r[..., :pi * S].reshape(*bid.shape, S, pi)
    ids = unpack_limbs32(per[..., 0:nl], nl, fm.id_offset)
    if dup:
        items = unpack_limbs32(per[..., nl:nl + dup], nl, fm.id_offset)
    else:
        items = ids
    rb = unpack_limbs32(per[..., nl + dup:pi], 4)
    recipf = jax.lax.bitcast_convert_type(rb, jnp.float32)
    size = unpack_limbs32(r[..., pi * S:pi * S + 2], 2)
    return ids, items, recipf, size


def _fetch_w(fm: FlatMap, bid, pos, S: int):
    """[L,S] int64 weights (resolve mode)."""
    if fm.n_pos == 1:
        idx = bid
    else:
        idx = jnp.minimum(pos, fm.n_pos - 1) * fm.B + bid
    r = onehot_fetch(idx, fm.roww_limbs_for(S))
    per = r.reshape(*bid.shape, S, 4)
    return unpack_limbs(per, 4, 0, jnp.int64)


def _fetch_meta(fm: FlatMap, bid):
    """(size [L], btype [L]) of arbitrary bucket indices."""
    r = onehot_fetch(bid, fm.meta_limbs)
    size = unpack_limbs32(r[..., 0:2], 2)
    btype = unpack_limbs32(r[..., 2:4], 2)
    return size, btype


def _pick(arr, sel):
    """Gather-free row select: arr [L,S], sel [L,S] one-hot bool."""
    return jnp.sum(jnp.where(sel, arr, jnp.zeros_like(arr)), axis=1)


def _straw2_choose_exact(fm: FlatMap, bid, x, r, pos, S: int,
                         crow: _ConstRow | None = None):
    """Fully exact straw2 draw: integer q for every slot (no f32
    shortcut, no flags).  Used for the dust lanes whose top-3 interval
    resolution stays ambiguous — replaces the scalar host fallback so
    the whole mapping pipeline can stay device-resident."""
    if crow is not None:
        ids = jnp.asarray(crow.ids)[None, :]
        items_a = jnp.asarray(crow.items)[None, :]
        recipf = jnp.asarray(crow.recipf)[None, :]
        size = jnp.int32(crow.size)
        valid = (jnp.arange(S) < size)[None, :] & (recipf > 0)
        wv = jnp.asarray(crow.w)[None, :] * jnp.ones(
            (x.shape[0], 1), jnp.int64)
    else:
        ids, items_a, recipf, size = _fetch_row(fm, bid, pos, S)
        valid = (jnp.arange(S)[None, :] < size[:, None]) & (recipf > 0)
        wv = _fetch_w(fm, bid, pos, S)
    u = (hash32_3_j(x[:, None], ids, r[:, None])
         & _u32(0xFFFF)).astype(jnp.int64)
    neg = neg_ln_mxu(u, jnp.asarray(_RHLH_LIMBS_NP),
                     jnp.asarray(_LL_LIMBS_NP))
    w = wv & 0xFFFFFFFF
    wsafe = jnp.maximum(w, 1)
    rf = jnp.float32(1.0) / wsafe.astype(jnp.float32)
    q = _exact_floordiv(neg, wsafe, rf)
    q = jnp.where(valid & (w > 0), q, jnp.int64(S64_MAX))
    win = jnp.argmin(q, axis=1).astype(jnp.int32)  # first-slot ties
    selw = jnp.arange(S)[None, :] == win[:, None]
    item = jnp.sum(jnp.where(selw, items_a, 0), axis=1).astype(jnp.int32)
    return item, jnp.zeros((x.shape[0],), bool)


def _straw2_choose(fm: FlatMap, bid, x, r, pos, S: int, resolve,
                   crow: _ConstRow | None = None):
    """Winning item per lane via the f32 certainty draw.

    bid [L] bucket indices (ignored when crow fixes the bucket); pos [L]
    output positions (selects the choose_args weight-set,
    CrushWrapper.h:1500).  S = item capacity for this level.

    resolve: False = fast mode (flag marks lanes whose winner is not
    certain, caller re-runs them in resolve mode); True = exact top-3
    resolution (flag marks only top-3-inside-bound dust); "all" =
    fully exact integer draw for every slot (never flags).
    """
    if resolve == "all":
        return _straw2_choose_exact(fm, bid, x, r, pos, S, crow)
    if crow is not None:
        ids = jnp.asarray(crow.ids)[None, :]
        items_a = jnp.asarray(crow.items)[None, :]
        recipf = jnp.asarray(crow.recipf)[None, :]
        size = jnp.int32(crow.size)
        valid = (jnp.arange(S) < size)[None, :] & (recipf > 0)
    else:
        ids, items_a, recipf, size = _fetch_row(fm, bid, pos, S)
        valid = (jnp.arange(S)[None, :] < size[:, None]) & (recipf > 0)
    u = (hash32_3_j(x[:, None], ids, r[:, None])
         & _u32(0xFFFF)).astype(jnp.int32)
    g = _g_f32(u)
    q = jnp.where(valid, g * recipf, _BIG)
    E = (jnp.float32(_G_DELTA) * recipf + q * jnp.float32(_EPS_Q)
         + jnp.float32(_E_CONST))
    # contender intervals: exact q_i provably lies in [q_i-E_i, q_i+E_i]
    # (per-item bound — E varies with 1/w_i, so gap tests against a
    # single E would be unsound under skewed weights).  An item can be
    # the exact winner only if its lower bound reaches the smallest
    # upper bound.  Exactly one contender => winner proven.
    hi = jnp.where(valid, q + E, _BIG)
    low = jnp.where(valid, q - E, _BIG)
    min_hi = jnp.min(hi, axis=1)
    contend = valid & (low <= min_hi[:, None])
    ncont = jnp.sum(contend.astype(jnp.int32), axis=1)
    certain = ncont <= 1   # 0 = all-invalid: collapses to slot 0 below
    i1 = jnp.argmin(q, axis=1).astype(jnp.int32)
    win_c = jnp.argmax(contend, axis=1).astype(jnp.int32)
    win1 = jnp.where(ncont == 1, win_c, i1)
    if not resolve:
        win = win1
        flag = ~certain
    else:
        sel1 = jnp.arange(S)[None, :] == i1[:, None]
        qm = jnp.where(sel1, _BIG, q)
        i2 = jnp.argmin(qm, axis=1).astype(jnp.int32)
        sel2 = jnp.arange(S)[None, :] == i2[:, None]
        qm2 = jnp.where(sel2, _BIG, qm)
        i3 = jnp.argmin(qm2, axis=1).astype(jnp.int32)
        sel3 = jnp.arange(S)[None, :] == i3[:, None]
        u1 = _pick(u, sel1)
        u2 = _pick(u, sel2)
        u3 = _pick(u, sel3)
        wp = fm.wpair_limbs_for(S)
        if wp is not None:
            # per-(bucket,slot) pair fetch for just the three
            # candidates — the [L,S] int64 row unpack the old path did
            # dominated resolve-mode HBM traffic
            if fm.n_pos == 1:
                base = bid * S
            else:
                base = (jnp.minimum(pos, fm.n_pos - 1) * fm.B + bid) * S

            def _wfetch(slot, sel):
                wl = onehot_fetch(base + slot, wp)          # [L, 4]
                wv = unpack_limbs(wl, 4, 0, jnp.int64)
                return jnp.where(jnp.any(valid & sel, axis=1), wv,
                                 jnp.int64(0))

            w1 = _wfetch(i1, sel1)
            w2 = _wfetch(i2, sel2)
            w3 = _wfetch(i3, sel3)
        else:
            if crow is not None:
                wvalid = jnp.where(valid, jnp.asarray(crow.w)[None, :],
                                   jnp.int64(0))
            else:
                wv = _fetch_w(fm, bid, pos, S)
                wvalid = jnp.where(valid, wv, jnp.int64(0))
            w1 = _pick(wvalid, sel1)
            w2 = _pick(wvalid, sel2)
            w3 = _pick(wvalid, sel3)
        win3 = _exact3_winner(fm, (u1, u2, u3), (w1, w2, w3),
                              (i1, i2, i3))
        win = jnp.where(certain, win1, win3)
        # sound only when every contender was resolved exactly
        outside = contend & ~(sel1 | sel2 | sel3)
        flag = (~certain) & jnp.any(outside, axis=1)
    selw = jnp.arange(S)[None, :] == win[:, None]
    item = jnp.sum(jnp.where(selw, items_a, 0), axis=1).astype(jnp.int32)
    return item, flag


def _get_pallas_descend(fm: FlatMap, depth_sizes: tuple,
                        want_type: int):
    """Cached fused-descent kernel for (fm, depth_sizes, want_type);
    None when pallas is unavailable or the map exceeds its budget."""
    from . import pallas_draw
    if not pallas_draw.pallas_enabled():
        return None
    cache = fm.__dict__.setdefault("_pallas_cache", {})
    key = (depth_sizes, want_type)
    if key not in cache:
        cache[key] = pallas_draw.make_descend_kernel(
            fm, depth_sizes, want_type)
    return cache[key]


@scope("crush.descend")
def _descend(fm: FlatMap, take_bid, x, r, want_type: int, pos,
             depth_sizes: tuple, resolve: bool,
             crow0: _ConstRow | None = None):
    """Walk bucket->bucket until an item of want_type.

    depth_sizes[d] = max bucket size reachable at depth d from the
    start set (static per rule), so each level's draw only pays for
    the buckets that can actually appear there.  crow0, when given, is
    the static level-0 bucket row (fetch-free).

    Returns (item, ok, perm_fail, flag): ok = reached an item of the
    wanted type; perm_fail = hit a wrong-type device (host skips the
    replica permanently, mapper.c:516-520); neither = retryable (empty
    bucket).  flag accumulates draw uncertainty over the levels
    actually walked.
    """
    L = x.shape[0]
    if not resolve:
        from . import pallas_draw
        fn = (_get_pallas_descend(fm, depth_sizes, want_type)
              if L % pallas_draw.TL == 0 else None)
        # which descent this lane count was built with, for the
        # pallas_lanes count on the launch span: the XLA switch below
        # is otherwise silent
        fm.descent_in_pallas[L] = fn is not None
        if fn is not None:
            item, status = fn(x, r, take_bid, pos)
            return (item, (status & 1) != 0, (status & 2) != 0,
                    (status & 4) != 0)
    cur = take_bid
    item = jnp.full((L,), ITEM_NONE, jnp.int32)
    ok = jnp.zeros((L,), bool)
    perm = jnp.zeros((L,), bool)
    flag = jnp.zeros((L,), bool)
    if crow0 is not None:
        done = jnp.full((L,), crow0.size == 0)
    else:
        cur_size, _ = _fetch_meta(fm, cur)
        done = cur_size == 0                 # empty bucket: retryable
    for d, S_d in enumerate(depth_sizes):
        chosen, f = _straw2_choose(fm, cur, x, r, pos, S_d, resolve,
                                   crow0 if d == 0 else None)
        flag = flag | ((~done) & f)
        is_bucket = chosen < 0
        cbid = jnp.where(is_bucket, -1 - chosen, 0)
        csize, cbtype = _fetch_meta(fm, cbid)
        ctype = jnp.where(is_bucket, cbtype, 0)
        oob = (~is_bucket) & (chosen >= fm.max_devices)
        reach = (~done) & (ctype == want_type) & (~oob)
        wrongdev = (~done) & (~reach) & ((~is_bucket) | oob)
        empty_next = (~done) & (~reach) & is_bucket & (csize == 0)
        item = jnp.where(reach, chosen, item)
        ok = ok | reach
        perm = perm | wrongdev
        done = done | reach | wrongdev | empty_next
        cur = jnp.where((~done) & is_bucket, cbid, cur)
    return item, ok, perm, flag


_SF_LO = 16


def small_fetch(table_i32, idx, n_limbs: int):
    """Gather-free elementwise fetch from a small runtime [D] int table
    (values < 2^(8*n_limbs)): one-hot MXU fetch over ceil(D/16) row
    groups + a 16-way in-register column select.  TPU gathers run at
    scalar rate; for the [L]/[L,S]-shaped cluster-state lookups
    (device reweights, up/exists bits, affinities) this is far faster.
    idx must already be clipped to [0, D)."""
    D = table_i32.shape[0]
    HI = -(-D // _SF_LO)
    t = jnp.pad(table_i32.astype(jnp.int32), (0, HI * _SF_LO - D))
    t = t.reshape(HI, _SF_LO)
    planes = [((t >> (8 * j)) & 0xFF) - 128 for j in range(n_limbs)]
    tl = jnp.concatenate(planes, axis=1).astype(jnp.int8)
    hi = (idx >> 4).astype(jnp.int32)
    lo = (idx & 15).astype(jnp.int32)
    r = onehot_fetch(hi, tl).reshape(*idx.shape, n_limbs, _SF_LO)
    sel = lo[..., None] == jnp.arange(_SF_LO)
    pl = jnp.sum(jnp.where(sel[..., None, :], r, 0), axis=-1)
    return unpack_limbs32(pl, n_limbs)


@scope("crush.is_out")
def _is_out(dev_weights, item, x):
    """Reweight rejection (mapper.c:402-416).  Reweights are 16.16
    capped at 0x10000 (17 bits), so three limb planes suffice."""
    idx = jnp.clip(item, 0, dev_weights.shape[0] - 1)
    w = small_fetch(dev_weights, idx, 3)
    oob = (item >= dev_weights.shape[0]) | (item < 0)
    hh = (hash32_2_j(x, item) & _u32(0xFFFF)).astype(jnp.int32)
    return oob | (w == 0) | ((w < 0x10000) & (hh >= w))


# ---------------------------------------------------------------------------
# firstn / indep
# ---------------------------------------------------------------------------

# optimistic rounds of the attempt structure (ftotal = 0, 1, 2; per
# replica for firstn, per choose step for indep): a lane still unplaced
# after them is flagged for the resolve chain.  A whole pool's dense
# pass runs only the first of them at full width and the rest on a
# compacted tail: firstn around the whole rule (_compiled_pool), indep
# inside each choose step, on the step's own takes (_choose_indep_vec).
# The incremental remap and the chain's stage A, whose lanes are a
# compacted set already, run all of them at their own width.
_ATTEMPT_TRIES = 3

# below this lane count the attempt structure isn't worth its
# bookkeeping; run the full retry loops directly
_ATTEMPT_MIN_L = 16384

# chooseleaf retries an optimistic indep round runs at full width: with
# set_chooseleaf_tries 5 and ten OSDs out of 10,000 a handful of lanes
# in two million need a third, fourth and fifth try, and each was a
# descent over all of them (PERF.md, PR 35); those lanes go to the
# resolve chain instead
_INDEP_LEAF_TRIES = 2


def _firstn_full(fm: FlatMap, take_bid, xs, out, leaves, outpos,
                 numrep: int, result_max: int, want_type: int,
                 recurse_to_leaf: bool, dev_weights,
                 tries: int, recurse_tries: int, vary_r: int,
                 stable: int, outer_ds: tuple, inner_ds: tuple,
                 resolve: bool, rootc: _ConstRow | None, limit=None):
    """crush_choose_firstn (mapper.c:438-626) for local-tries==0: per
    replica, retry whole descents while collided/rejected (masked
    lanes); chooseleaf recursion selects one leaf per chosen bucket.
    Full retry semantics; every lane replays from ftotal=0.  limit [L],
    when given, is the lane's out_size: a lane that has placed as many
    draws no more (0: the lane takes no part)."""
    L = xs.shape[0]
    result_slots = out.shape[1]
    flag0 = jnp.zeros((L,), bool)

    def rep_body(rep, carry):
        out, leaves, outpos, flag = carry

        def body(state):
            ftotal, active, out, leaves, outpos, flag = state
            r = jnp.full((L,), 0, jnp.int32) + rep + ftotal
            item, ok, perm, f1 = _descend(fm, take_bid, xs, r, want_type,
                                          outpos, outer_ds, resolve, rootc)
            flag = flag | (active & f1)
            if recurse_to_leaf:
                if vary_r:
                    sub_r = r >> (vary_r - 1)
                else:
                    sub_r = jnp.zeros_like(r)
                rep_i = (jnp.zeros_like(outpos) if stable else outpos)
                bid_in = jnp.where(item < 0, -1 - item, 0)

                def inner_body(istate):
                    ift, iact, leaf, leaf_ok, iflag = istate
                    r_in = rep_i + sub_r + ift
                    cand, cok, _cperm, f2 = _descend(
                        fm, bid_in, xs, r_in, 0, outpos, inner_ds,
                        resolve, None)
                    iflag = iflag | (iact & f2)
                    cok = cok & (item < 0)
                    # leaf collision: the recursive call checks candidates
                    # against leaves already placed in out2[0..outpos)
                    # (mapper.c:535-541 with out=out2)
                    cok = cok & ~jnp.any(leaves == cand[:, None], axis=1)
                    cok = cok & ~_is_out(dev_weights, cand, xs)
                    take = iact & cok
                    leaf = jnp.where(take, cand, leaf)
                    leaf_ok = leaf_ok | take
                    iact = iact & (~cok) & (ift + 1 < recurse_tries)
                    return ift + 1, iact, leaf, leaf_ok, iflag

                izero = jnp.zeros((L,), jnp.int32)
                leaf0 = jnp.full((L,), ITEM_NONE, jnp.int32)
                _, _, leaf, leaf_ok, iflag = jax.lax.while_loop(
                    lambda s: jnp.any(s[1]), inner_body,
                    (izero, active & ok, leaf0, jnp.zeros((L,), bool),
                     jnp.zeros((L,), bool)))
                final, final_ok = leaf, ok & leaf_ok
                flag = flag | iflag
            else:
                final = item
                final_ok = ok
                if want_type == 0:
                    final_ok = final_ok & ~_is_out(dev_weights, item, xs)
            collide = jnp.any(out == item[:, None], axis=1) & ok
            success = (active & final_ok & ~collide
                       & (outpos < result_slots))
            slot = jnp.arange(result_slots)[None, :] == outpos[:, None]
            put = slot & success[:, None]
            out = jnp.where(put, item[:, None], out)
            leaves = jnp.where(put, final[:, None], leaves)
            outpos = outpos + success.astype(jnp.int32)
            ftotal = ftotal + 1
            active = active & ~success & ~perm & (ftotal < tries)
            return ftotal, active, out, leaves, outpos, flag

        z = jnp.zeros((L,), jnp.int32)
        act = (jnp.ones((L,), bool) if limit is None
               else outpos < limit)
        _, _, out, leaves, outpos, flag = jax.lax.while_loop(
            lambda s: jnp.any(s[1]), body,
            (z, act, out, leaves, outpos, flag))
        return out, leaves, outpos, flag

    out, leaves, outpos, flag = jax.lax.fori_loop(
        0, numrep, rep_body, (out, leaves, outpos, flag0))
    return out, leaves, outpos, flag


def _firstn_attempts(tries: int, recurse_to_leaf: bool,
                     recurse_tries: int) -> int:
    """Optimistic rounds per replica of the attempt structure.  An outer
    retry (ftotal+1) after a leaf failure only matches the reference
    when the inner loop is single-try (chooseleaf_descend_once, the
    modern default); otherwise the inner retries first, so the
    structure stops at one round and defers to the resolve chain."""
    if recurse_to_leaf and recurse_tries > 1:
        return 1
    return min(_ATTEMPT_TRIES, tries)


def _take_lanes(take, L: int):
    """The start bucket of every lane as bucket indices [L]: a rule's
    TAKE is one bucket id, a later step's take is per lane already."""
    if isinstance(take, int):
        return jnp.full((L,), -1 - take, jnp.int32)
    return take


def _choose_firstn_vec(fm: FlatMap, take, xs, numrep: int,
                       result_max: int, want_type: int,
                       recurse_to_leaf: bool, dev_weights,
                       tries: int, recurse_tries: int, vary_r: int,
                       stable: int, outer_ds: tuple, inner_ds: tuple,
                       resolve: bool, full: bool,
                       rootc: _ConstRow | None,
                       first_only: bool = False, limit=None):
    """Fast-path firstn, the attempt structure: _firstn_attempts
    optimistic rounds per replica (ftotal = 0, 1, ...), every round over
    all L lanes; a lane still unplaced after them is left to the caller
    instead of driving a masked retry loop.  Resolve mode and small
    batches run the full retry loops.

    Returns (rows, outpos, flag, unfinished): flag marks a lane with an
    uncertain f32 draw, unfinished one whose replica is still unplaced;
    either has to be recomputed.  first_only runs the first round of
    each replica alone: the rows of its unfinished lanes are the state
    at their first failure and are replaced whole (the dense pass of
    _compiled_pool, which replays them on a compacted tail).

    take: the rule's TAKE bucket id, or bucket indices [L] for a later
    step (_chain_step); limit [L] then bounds what a lane places (0: the
    lane's entry of the working vector was no bucket)."""
    L = xs.shape[0]
    slots = min(numrep, result_max)
    take_bid = _take_lanes(take, L)
    out0 = jnp.full((L, slots), ITEM_NONE, jnp.int32)
    leaves0 = jnp.full((L, slots), ITEM_NONE, jnp.int32)
    pos0 = jnp.zeros((L,), jnp.int32)
    if full or L < _ATTEMPT_MIN_L:
        out, leaves, outpos, flag = _firstn_full(
            fm, take_bid, xs, out0, leaves0, pos0, numrep, result_max,
            want_type, recurse_to_leaf, dev_weights, tries, recurse_tries,
            vary_r, stable, outer_ds, inner_ds, resolve, rootc, limit)
        return ((leaves if recurse_to_leaf else out), outpos, flag,
                jnp.zeros((L,), bool))

    n_attempts = (1 if first_only else
                  _firstn_attempts(tries, recurse_to_leaf, recurse_tries))

    def attempt(k, state):
        """Round k: replica k // n_attempts at ftotal k % n_attempts.
        The fast form runs the rounds as a loop, so that a program
        holds each descent kernel once: the kernels are most of a pool
        program's size."""
        out, leaves, outpos, flag, clean, done_rep = state
        rep = (k // n_attempts).astype(jnp.int32)
        ft = (k % n_attempts).astype(jnp.int32)
        # a replica's first round finds it unplaced on every lane
        done_rep = done_rep & (ft > 0)
        if limit is not None:
            done_rep = done_rep | (outpos >= limit)
        r = jnp.zeros((L,), jnp.int32) + rep + ft
        item, ok, perm, f1 = _descend(fm, take_bid, xs, r, want_type,
                                      outpos, outer_ds, resolve, rootc)
        if recurse_to_leaf:
            if vary_r:
                sub_r = r >> (vary_r - 1)
            else:
                sub_r = jnp.zeros_like(r)
            rep_i = (jnp.zeros_like(outpos) if stable else outpos)
            bid_in = jnp.where(item < 0, -1 - item, 0)
            r_in = rep_i + sub_r
            cand, cok, _cp, f2 = _descend(fm, bid_in, xs, r_in, 0,
                                          outpos, inner_ds, resolve,
                                          None)
            cok = cok & (item < 0)
            cok = cok & ~jnp.any(leaves == cand[:, None], axis=1)
            cok = cok & ~_is_out(dev_weights, cand, xs)
            final, final_ok = cand, ok & cok
            f1 = f1 | (f2 & ok & (item < 0))
        else:
            final = item
            final_ok = ok
            if want_type == 0:
                final_ok = final_ok & ~_is_out(dev_weights, item, xs)
        collide = jnp.any(out == item[:, None], axis=1) & ok
        act = ~done_rep
        success = act & final_ok & ~collide & (outpos < slots)
        slot = jnp.arange(slots)[None, :] == outpos[:, None]
        put = slot & success[:, None]
        out = jnp.where(put, item[:, None], out)
        leaves = jnp.where(put, final[:, None], leaves)
        outpos = outpos + success.astype(jnp.int32)
        flag = flag | (clean & act & f1)
        done_rep = done_rep | success | (act & perm)
        # a replica unplaced after its last round leaves the lane dirty
        clean = clean & (done_rep | (ft < n_attempts - 1))
        return out, leaves, outpos, flag, clean, done_rep

    none = jnp.zeros((L,), bool)
    state = (out0, leaves0, pos0, none, ~none, none)
    if resolve:
        # the exact form stays a chain: with its rounds in a loop the
        # 10M-PG resolve program's tables read back to the host at a
        # quarter of the speed (PERF.md, PR 34; cause not found)
        for k in range(numrep * n_attempts):
            state = attempt(jnp.int32(k), state)
    else:
        state = jax.lax.fori_loop(0, numrep * n_attempts, attempt, state)
    out, leaves, outpos, flag, clean, _ = state
    return (leaves if recurse_to_leaf else out), outpos, flag, ~clean


def _indep_round(fm: FlatMap, take_bid, xs, ftotal, out, leaves, flag,
                 numrep: int, slots: int, want_type: int,
                 recurse_to_leaf: bool, dev_weights,
                 recurse_tries: int, outer_ds: tuple, inner_ds: tuple,
                 resolve: bool, rootc: _ConstRow | None,
                 leaf_cap: int | None = None):
    """One crush_choose_indep round (mapper.c:633-821): all UNDEF slots
    draw with r = rep + numrep*ftotal.

    leaf_cap bounds the chooseleaf retries a round runs at its full
    width (every one is a descent over all L lanes, for as long as one
    lane still looks for its leaf): a lane that has tries left when the
    cap is reached is flagged and recomputed by a more exact pass, as a
    lane with an uncertain draw is.  None: all recurse_tries."""
    L = xs.shape[0]
    pos0 = jnp.zeros((L,), jnp.int32)
    cap = recurse_tries if leaf_cap is None else min(leaf_cap,
                                                     recurse_tries)

    def rep_body(rep, carry):
        out, leaves, flag = carry
        undecided = out[:, rep] == ITEM_UNDEF
        r = jnp.full((L,), 0, jnp.int32) + rep + numrep * ftotal
        item, ok, perm, f1 = _descend(fm, take_bid, xs, r, want_type,
                                      pos0, outer_ds, resolve, rootc)
        flag = flag | (undecided & f1)
        collide = jnp.any(out == item[:, None], axis=1) & ok
        if recurse_to_leaf:
            bid_in = jnp.where(item < 0, -1 - item, 0)
            pos_r = jnp.full((L,), 0, jnp.int32) + rep

            def inner_body(istate):
                ift, iact, leaf, leaf_ok, iflag = istate
                r_in = r + rep + numrep * ift
                cand, cok, _cp, f2 = _descend(fm, bid_in, xs, r_in, 0,
                                              pos_r, inner_ds, resolve,
                                              None)
                iflag = iflag | (iact & f2)
                cok = cok & (item < 0)
                cok = cok & ~_is_out(dev_weights, cand, xs)
                take = iact & cok
                leaf = jnp.where(take, cand, leaf)
                leaf_ok = leaf_ok | take
                iact = iact & (~cok) & (ift + 1 < recurse_tries)
                return ift + 1, iact, leaf, leaf_ok, iflag

            izero = jnp.zeros((L,), jnp.int32)
            leaf0 = jnp.full((L,), ITEM_NONE, jnp.int32)
            ift, cut, leaf, leaf_ok, iflag = jax.lax.while_loop(
                lambda s: jnp.any(s[1]) & (s[0][0] < cap), inner_body,
                (izero, undecided & ok & ~collide, leaf0,
                 jnp.zeros((L,), bool), jnp.zeros((L,), bool)))
            final, final_ok = leaf, ok & leaf_ok
            # cut: still looking, with tries left, when the cap came
            flag = flag | iflag | cut
        else:
            final = item
            final_ok = ok
            if want_type == 0:
                final_ok = final_ok & ~_is_out(dev_weights, item, xs)
        success = undecided & final_ok & ~collide
        permfail = undecided & perm
        col = jnp.arange(slots)[None, :] == rep
        out = jnp.where(col & success[:, None], item[:, None], out)
        out = jnp.where(col & permfail[:, None], ITEM_NONE, out)
        leaves = jnp.where(col & success[:, None], final[:, None],
                           leaves)
        leaves = jnp.where(col & permfail[:, None], ITEM_NONE, leaves)
        return out, leaves, flag

    return jax.lax.fori_loop(0, slots, rep_body, (out, leaves, flag))


def _indep_start(L: int, slots: int, nslots):
    """The out vector before the first round: UNDEF where a slot is to
    be drawn.  nslots [L], when given, is the lane's out_size: the
    slots past it are never drawn and end as NONE."""
    if nslots is None:
        return jnp.full((L, slots), ITEM_UNDEF, jnp.int32)
    return jnp.where(jnp.arange(slots)[None, :] < nslots[:, None],
                     jnp.int32(ITEM_UNDEF), jnp.int32(ITEM_NONE))


def _indep_full(fm: FlatMap, take_bid, xs, numrep: int, slots: int,
                want_type: int, recurse_to_leaf: bool, dev_weights,
                tries: int, recurse_tries: int, outer_ds: tuple,
                inner_ds: tuple, resolve: bool,
                rootc: _ConstRow | None, nslots=None):
    """Full positionally-stable retry loop: slots left UNDEF retry with
    r advanced by numrep per round."""
    L = xs.shape[0]
    out = leaves = _indep_start(L, slots, nslots)
    flag = jnp.zeros((L,), bool)

    def body(state):
        ftotal, out, leaves, flag = state
        out, leaves, flag = _indep_round(
            fm, take_bid, xs, ftotal, out, leaves, flag, numrep, slots,
            want_type, recurse_to_leaf, dev_weights, recurse_tries,
            outer_ds, inner_ds, resolve, rootc)
        return ftotal + 1, out, leaves, flag

    def cond(state):
        ftotal, out, _, _ = state
        return jnp.any(out == ITEM_UNDEF) & (ftotal < tries)

    z = jnp.zeros((), jnp.int32)
    _, out, leaves, flag = jax.lax.while_loop(cond, body,
                                              (z, out, leaves, flag))
    res = leaves if recurse_to_leaf else out
    return jnp.where(res == ITEM_UNDEF, ITEM_NONE, res), flag


class _Tail(collections.namedtuple("_Tail", "kt seeds")):
    """The compacted tail of one indep choose step in a whole pool's
    dense pass: kt slots per RC_ROW-lane row group, and seeds(lanes) ->
    the xs of the step's lanes by number (recomputed from the lane
    numbers: a gather runs at scalar rate)."""

    __slots__ = ()


def _choose_indep_vec(fm: FlatMap, take, xs, numrep: int,
                      result_max: int, want_type: int,
                      recurse_to_leaf: bool, dev_weights,
                      tries: int, recurse_tries: int,
                      outer_ds: tuple, inner_ds: tuple,
                      resolve: bool, full: bool,
                      rootc: _ConstRow | None, nslots=None,
                      tail: _Tail | None = None):
    """Fast-path indep: _ATTEMPT_TRIES optimistic rounds (each an exact
    crush_choose_indep round, so chaining them is the reference retry
    semantics verbatim); lanes with UNDEF slots left after them are
    flagged for the resolve pass.

    Without a tail every round runs over all L lanes.  With one (a
    whole pool's dense pass, DeviceMapper._tail_slots) only the first
    does: the lanes it leaves with an undefined slot are compacted into
    tail.kt slots per row group (rowcompact), their out and leaves
    vectors fetched (rowgather) and the later rounds run at that width
    (L / RC_ROW * kt lanes, a loop, so that the program holds each
    descent once), and their rows and flags are put back (rowexpand).
    A lane whose row group had no slot left for it keeps its row and
    stays flagged.

    Returns (rows, flag, retry, seats): retry marks the lanes that
    still had an undefined slot after the first full-width round (none
    where the full loops run); seats is None without a tail, else the
    int32 counts [lanes seated in the tail, lanes left unseated, largest
    row group].  take and nslots as _choose_firstn_vec's take and
    limit."""
    L = xs.shape[0]
    slots = min(numrep, result_max)
    take_bid = _take_lanes(take, L)
    if full or L < _ATTEMPT_MIN_L:
        res, flag = _indep_full(fm, take_bid, xs, numrep, slots,
                                want_type, recurse_to_leaf, dev_weights,
                                tries, recurse_tries, outer_ds, inner_ds,
                                resolve, rootc, nslots)
        return res, flag, jnp.zeros((L,), bool), None

    def round_(ft, take_bid, xs, out, leaves, flag):
        return _indep_round(
            fm, take_bid, xs, ft, out, leaves, flag, numrep, slots,
            want_type, recurse_to_leaf, dev_weights, recurse_tries,
            outer_ds, inner_ds, resolve, rootc, _INDEP_LEAF_TRIES)

    def rows_of(out, leaves, flag):
        res = leaves if recurse_to_leaf else out
        flag = flag | jnp.any(out == ITEM_UNDEF, axis=1)
        return jnp.where(res == ITEM_UNDEF, ITEM_NONE, res), flag

    n_rounds = min(_ATTEMPT_TRIES, tries)
    with scope("crush.step"):
        out = leaves = _indep_start(L, slots, nslots)
    with scope("crush.first"):
        out, leaves, flag = round_(jnp.int32(0), take_bid, xs, out,
                                   leaves, jnp.zeros((L,), bool))
    with scope("crush.step"):
        retry = jnp.any(out == ITEM_UNDEF, axis=1)
    if tail is None or n_rounds < 2:
        with scope("crush.first"):
            for ft in range(1, n_rounds):
                out, leaves, flag = round_(jnp.int32(ft), take_bid, xs,
                                           out, leaves, flag)
        with scope("crush.step"):
            return rows_of(out, leaves, flag) + (retry, None)

    from . import pallas_draw
    row, kt = DeviceMapper.RC_ROW, tail.kt
    with scope("crush.tail.move"):
        idx, _valid, cnt = pallas_draw.make_rowcompact_kernel(
            L, row, kt, L)(retry)
        # what a seated lane's later rounds start from: its take and, per
        # slot, what the first round placed (a pad slot holds its group's
        # first lane: computed, never read back)
        state = [out] + ([leaves] if recurse_to_leaf else [])
        if not isinstance(take, int):
            state.append(take_bid[:, None])
        state = jnp.concatenate(state, axis=1)
        state_t = pallas_draw.make_rowgather_kernel(
            L, row, kt, state.shape[1])(retry, state)
        out_t = state_t[:, :slots]
        leaves_t = (state_t[:, slots:2 * slots] if recurse_to_leaf
                    else out_t)
        take_t = (_take_lanes(take, idx.shape[0]) if isinstance(take, int)
                  else state_t[:, -1])
        xs_t = tail.seeds(idx)

    def later(ft, st):
        return round_(ft.astype(jnp.int32), take_t, xs_t, *st)

    with scope("crush.tail.rounds"):
        out_t, leaves_t, flag_t = jax.lax.fori_loop(
            1, n_rounds, later,
            (out_t, leaves_t, jnp.zeros(idx.shape, bool)))
    with scope("crush.tail.move"):
        res, _ = rows_of(out, leaves, flag)
        res_t, flag_t = rows_of(out_t, leaves_t, flag_t)
        # a seated lane takes its tail row and adds the tail's flag to its
        # first round's; one that got no slot keeps its row, flagged
        rows = pallas_draw.make_rowexpand_kernel(L, row, kt, slots + 1)(
            retry,
            jnp.concatenate([res, retry[:, None].astype(jnp.int32)], axis=1),
            jnp.concatenate([res_t, flag_t[:, None].astype(jnp.int32)],
                            axis=1))
    with scope("crush.step"):
        seated = jnp.minimum(cnt, kt)
    with scope("crush.tail.move"):
        rows, flag = rows[:, :-1], flag | (rows[:, -1] != 0)
    with scope("crush.step"):
        return (rows, flag, retry,
                jnp.stack([jnp.sum(seated), jnp.sum(cnt - seated),
                           jnp.max(cnt)]))


def _chain_step(fm: FlatMap, st, w, xs, result_max: int, dev_weights,
                resolve, full: bool, tail: _Tail | None = None):
    """One choose step after a rule's first, as crush_do_rule chains
    them (mapper.c:878-1083): every entry of the working vector w
    [L, n_in] that is a bucket is a take of its own, and what it
    chooses goes to the lane's next free positions; an entry that is
    none (ITEM_NONE, a device) is skipped and takes up no room.

    Each take draws into a window of its own (outpos 0, parent_r 0,
    collisions within the window only), so the n_in windows of a lane
    are independent and run as n_in * L lanes of one choose: the
    program holds a step's descents once, whatever n_in is.  An indep
    window holds min(numrep, result_max - osize) slots, known before
    any draw from which entries are buckets; a firstn window is filled
    in order, so cutting it to result_max - osize afterwards is what
    running it with that count gives.

    tail (an indep step of a whole pool's dense pass): the step's
    compacted tail, its seeds by PG lane; take-lane j is PG lane j mod L.

    Returns (rows [L, st.width] with NONE past a lane's osize, flag,
    retry, seats): seats as _choose_indep_vec's."""
    L, n_in = w.shape
    slots = min(st.numrep, result_max)
    with scope("crush.step"):
        valid = (w < 0).T                                  # [n_in, L]
        take = jnp.where(valid, -1 - w.T, 0).reshape(-1)
        xs_t = jnp.tile(xs, n_in)
    if st.firstn:
        with scope("crush.step"):
            limit = jnp.where(valid, slots, 0).reshape(-1)
        rows, placed, flag, unfinished = _choose_firstn_vec(
            fm, take, xs_t, st.numrep, result_max, st.want_type, st.leaf,
            dev_weights, st.tries, st.recurse, st.vary_r, st.stable,
            st.outer_ds, st.inner_ds, resolve, full, None, limit=limit)
        with scope("crush.step"):
            flag = flag | unfinished
            retry, seats = jnp.zeros_like(flag), None
            placed = placed.reshape(n_in, L)
    else:
        with scope("crush.step"):
            osize = jnp.zeros((L,), jnp.int32)
            placed = []
            for i in range(n_in):
                placed.append(jnp.where(
                    valid[i], jnp.minimum(slots, result_max - osize), 0))
                osize = osize + placed[i]
            placed = jnp.stack(placed)
            nslots = placed.reshape(-1)
        if tail is not None:
            pg_seeds = tail.seeds
            tail = tail._replace(seeds=lambda j: pg_seeds(j % L))
        rows, flag, retry, seats = _choose_indep_vec(
            fm, take, xs_t, st.numrep, result_max, st.want_type, st.leaf,
            dev_weights, st.tries, st.recurse, st.outer_ds, st.inner_ds,
            resolve, full, None, nslots=nslots, tail=tail)
    with scope("crush.step"):
        rows = rows.reshape(n_in, L, slots)
        flag = jnp.any(flag.reshape(n_in, L) & valid, axis=0)
        retry = jnp.any(retry.reshape(n_in, L) & valid, axis=0)
        out = jnp.full((L, st.width), ITEM_NONE, jnp.int32)
        cols = jnp.arange(st.width)[None, :]
        osize = jnp.zeros((L,), jnp.int32)
        for i in range(n_in):
            n_i = jnp.minimum(placed[i], result_max - osize)
            for k in range(slots):
                put = (cols == (osize + k)[:, None]) & (k < n_i)[:, None]
                out = jnp.where(put, rows[i, :, k:k + 1], out)
            osize = osize + n_i
    return out, flag, retry, seats


# ---------------------------------------------------------------------------
# post-CRUSH mapping pipeline (fused on device)
# ---------------------------------------------------------------------------

CEPH_OSD_MAX_PRIMARY_AFFINITY = 0x10000
CEPH_OSD_DEFAULT_PRIMARY_AFFINITY = 0x10000


def _post_process(raw, seeds, exists_b, isup_b, aff, can_shift: bool,
                  use_aff: bool):
    """Fused _remove_nonexistent_osds + _raw_to_up_osds + _pick_primary +
    _apply_primary_affinity (OSDMap.cc:2626-2802) over the whole batch.

    raw [L,S] int32 with ITEM_NONE holes; seeds [L] uint32 pps values;
    exists_b/isup_b [D] bool; aff [D] int32 16.16 primary affinities.
    Only valid for PGs with no upmap/pg_temp exception (the bulk mapper
    recomputes exception rows on the host scalar path).
    """
    D = exists_b.shape[0]
    valid = raw != ITEM_NONE
    idx = jnp.clip(raw, 0, D - 1)
    keep_t = (exists_b & isup_b).astype(jnp.int32)
    st = small_fetch(keep_t, idx, 1)
    keep = valid & (raw < D) & (st > 0)
    up = jnp.where(keep, raw, ITEM_NONE)
    if can_shift:
        # stable compaction: surviving osds keep order, holes go last.
        # S is tiny, so an S^2 rank-select beats a sort by a mile.
        S = up.shape[1]
        rank = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
        slots = jnp.arange(S)
        hit = keep[:, None, :] & (rank[:, None, :] == slots[None, :, None])
        up = jnp.where(
            jnp.any(hit, axis=2),
            jnp.sum(jnp.where(hit, up[:, None, :], 0), axis=2),
            ITEM_NONE)
    S = up.shape[1]
    slots = jnp.arange(S)
    nonnone = up != ITEM_NONE
    has = jnp.any(nonnone, axis=1)
    first = jnp.argmax(nonnone, axis=1)

    def pick_col(arr, col):
        sel = slots[None, :] == col[:, None]
        return jnp.sum(jnp.where(sel, arr, 0), axis=1)

    prim = jnp.where(has, pick_col(up, first), -1)
    if use_aff:
        a = small_fetch(aff, jnp.clip(up, 0, D - 1), 3)
        row_applies = jnp.any(
            nonnone & (a != CEPH_OSD_DEFAULT_PRIMARY_AFFINITY), axis=1)
        h = (hash32_2_j(seeds[:, None], up.astype(jnp.uint32))
             >> _u32(16)).astype(jnp.int32)
        rejected = (a < CEPH_OSD_MAX_PRIMARY_AFFINITY) & (h >= a)
        accept = nonnone & ~rejected
        has_acc = jnp.any(accept, axis=1)
        pos = jnp.where(has_acc, jnp.argmax(accept, axis=1), first)
        applies = row_applies & has
        new_prim = pick_col(up, pos)
        prim = jnp.where(applies, new_prim, prim)
        if can_shift:
            # move the new primary to the front, shifting [0..pos) right
            i = slots[None, :]
            rotated = jnp.where(
                i == 0, new_prim[:, None],
                jnp.where(i <= pos[:, None], jnp.roll(up, 1, axis=1), up))
            up = jnp.where(applies[:, None], rotated, up)
    return up, prim


def _pps(ps, pgp_num: int, pgp_mask: int, pool_id: int, hashps: bool):
    """pg -> placement seed on device (raw_pg_to_pps, osd_types.cc):
    the stable mod of the pg number, mixed with the pool id."""
    ps = ps.astype(jnp.uint32)
    masked = jnp.where((ps & _u32(pgp_mask)) < _u32(pgp_num),
                       ps & _u32(pgp_mask),
                       ps & _u32(pgp_mask >> 1))
    if hashps:
        return hash32_2_j(masked, _u32(pool_id))
    return masked + _u32(pool_id)


# ---------------------------------------------------------------------------
# rule driver
# ---------------------------------------------------------------------------


class MapState:
    """Device-resident result of a whole-pool mapping pass: the raw
    (pre-filter) rows, the up rows and primaries, plus the host-side
    inputs needed to validate incremental remaps.

    Incremental validity (remap): with the crush map fixed, a lane's
    draw sequence depends only on (x, r) and the reweight rejections
    (mapper.c:402-416).  A rejection outcome changes only for OSDs
    whose reweight changed; under a DECREASE every lane that ever
    accepted the OSD carries it in a raw result slot (a pick either
    lands in the row or collides with an earlier slot holding the same
    OSD), so lanes without a changed OSD in their raw row replay the
    identical sequence.  Up/down/affinity changes only affect the
    post-CRUSH filter, which also reads the raw row.  Reweight
    INCREASES flip previously-hash-rejected lanes that are not
    identifiable from the rows — those fall back to a full pass."""

    __slots__ = ("dm", "ruleno", "result_max", "pg_num", "pgp_num",
                 "pgp_mask", "pool_id", "hashps", "can_shift",
                 "use_aff", "raw", "up_full", "prim_full", "w_np",
                 "ex_np", "iu_np", "af_np", "npg", "lanes",
                 "tail_lanes", "resolve_lanes", "steps", "retry_lanes",
                 "none_slots", "indep_tail_lanes")

    def __init__(self, dm, ruleno, result_max, pg_num, pgp_num,
                 pgp_mask, pool_id, hashps, can_shift, use_aff, raw,
                 up_full, prim_full, w_np, ex_np, iu_np, af_np, npg,
                 lanes=0, tail_lanes=0, resolve_lanes=0, steps=0,
                 retry_lanes=0, none_slots=0, indep_tail_lanes=0):
        self.dm = dm
        self.ruleno = ruleno
        self.result_max = result_max
        self.pg_num = pg_num
        self.pgp_num = pgp_num
        self.pgp_mask = pgp_mask
        self.pool_id = pool_id
        self.hashps = hashps
        self.can_shift = can_shift
        self.use_aff = use_aff
        self.raw = raw
        self.up_full = up_full
        self.prim_full = prim_full
        self.w_np = w_np
        self.ex_np = ex_np
        self.iu_np = iu_np
        self.af_np = af_np
        self.npg = npg
        # the pass that made this state: lanes it computed, those of
        # them replayed on the dense pass's compacted tail, those the
        # resolve chain took (the mark crush.lanes carries the same)
        self.lanes = lanes
        self.tail_lanes = tail_lanes
        self.resolve_lanes = resolve_lanes
        # a whole-pool pass only: the rule's choose steps it ran on the
        # device, the lanes an indep rule's first full-width round left
        # with an undefined slot in some step, the take-lanes of its
        # steps that sat in a tail for their later rounds, the up slots
        # that ended ITEM_NONE
        self.steps = steps
        self.retry_lanes = retry_lanes
        self.indep_tail_lanes = indep_tail_lanes
        self.none_slots = none_slots

    @property
    def up(self):
        return self.up_full[:self.pg_num]

    @property
    def prim(self):
        return self.prim_full[:self.pg_num]

    def remap(self, dev_weights, exists, isup, aff=None) -> "MapState":
        """New MapState after a cluster-state change, recomputing only
        the affected lanes when the change qualifies (see class doc);
        otherwise a full pass."""
        use_aff = aff is not None
        w_np = np.asarray(dev_weights, dtype=np.int32)
        ex_np = np.asarray(exists, dtype=bool)
        iu_np = np.asarray(isup, dtype=bool)
        af_np = (np.asarray(aff, dtype=np.int32) if use_aff
                 else np.zeros((ex_np.shape[0],), np.int32))

        def full():
            return self.dm.map_pool_state(
                self.ruleno, self.result_max, self.pg_num,
                self.pgp_num, self.pgp_mask, self.pool_id, self.hashps,
                w_np, ex_np, iu_np, aff, self.can_shift)

        if (use_aff != self.use_aff
                or w_np.shape != self.w_np.shape):
            return full()
        changed = ((w_np != self.w_np) | (ex_np != self.ex_np)
                   | (iu_np != self.iu_np) | (af_np != self.af_np))
        if not changed.any():
            return self
        if (w_np > self.w_np).any():
            return full()        # reweight increase: not incremental
        w, ex = jnp.asarray(w_np), jnp.asarray(ex_np)
        iu, af = jnp.asarray(iu_np), jnp.asarray(af_np)
        cm = jnp.asarray(changed)
        K1 = max(8, min(1 << 13, 1 << max(
            1, (self.pg_num - 1).bit_length())))
        K2 = max(8, min(1 << 11, K1))
        K3 = max(8, min(1 << 10, K2))
        KA = 0
        KT = 0
        if self.dm._rc_ok(self.npg):
            # expected hits per 2048-lane row group: a lane is hit if
            # any of its S raw slots holds a changed osd; size the
            # compaction slots with a ~6-sigma margin (overflow is
            # detected and retried wider, never silent)
            D = max(1, ex_np.shape[0])
            frac = float(changed.sum()) / D
            # .shape is metadata — np.asarray here would drag the
            # whole device-resident raw table back to the host
            S = int(self.raw.shape[1])
            mu = self.dm.RC_ROW * min(1.0, S * frac)
            thresh = mu + 6.0 * (mu ** 0.5) + 16.0
            KT = 128 * int(-(-thresh // 128))
            if KT > 1024:
                KT = 0      # massive churn: XLA nonzero path
        if KT == 0:
            KA = max(64, min(
                1 << 19,
                1 << (max(1, self.pg_num - 1)).bit_length()))
        while True:
            rm = self.dm._compiled_remap(
                self.ruleno, self.result_max, self.can_shift,
                self.use_aff, self.pgp_num, self.pgp_mask,
                self.pool_id, self.hashps, KA, K1, K2, K3, self.npg,
                self.pg_num, KT)
            raw2, up2, prim2, counts = rm(self.raw, self.up_full,
                                          self.prim_full, w, ex, iu,
                                          af, cm)
            nA, nf, n2, n3, rowmax = (int(v)
                                      for v in np.asarray(counts))
            if KT and rowmax > KT:
                KT = 128 * (-(-int(rowmax * 2) // 128))
                if KT > 2048:
                    KT = 0
                    KA = max(64, min(
                        1 << 19,
                        1 << (max(1, self.pg_num - 1)).bit_length()))
                continue
            if (KA == 0 or nA <= KA) and nf <= K1 and n2 <= K2 \
                    and n3 <= K3:
                break
            if KA:
                KA = max(KA, 1 << (max(1, nA - 1)).bit_length())
            K1 = max(K1, min(1 << (max(1, nf - 1)).bit_length(),
                             KA or (1 << 19)))
            K2 = max(K2, min(1 << (max(1, n2 - 1)).bit_length(), K1))
            K3 = max(K3, min(1 << (max(1, n3 - 1)).bit_length(), K2))
        return MapState(
            self.dm, self.ruleno, self.result_max, self.pg_num,
            self.pgp_num, self.pgp_mask, self.pool_id, self.hashps,
            self.can_shift, self.use_aff, raw2, up2, prim2, w_np,
            ex_np, iu_np, af_np, self.npg, lanes=nA, resolve_lanes=nf)


# one choose step with the tunables in force where it stands in the rule;
# width: entries of the working vector after it (static; a lane's own
# osize may be less); collide: the share of an indep step's takes whose
# numrep draws are expected to collide (what its tail starts from)
_Step = collections.namedtuple(
    "_Step", "firstn numrep want_type leaf tries recurse vary_r stable "
             "outer_ds inner_ds width collide")


class _Plan(collections.namedtuple("_Plan", "take_id steps")):
    """A rule in device scope: TAKE, its choose steps in order, EMIT."""

    __slots__ = ()

    @property
    def firstn(self) -> bool:
        return self.steps[0].firstn

    @property
    def lane_factors(self) -> tuple:
        """Per step, the lanes its choose runs on over the lanes of the
        pass: the entries of the working vector it takes from."""
        return (1,) + tuple(st.width for st in self.steps[:-1])


class DeviceMapper:
    """Bulk do_rule on device for straw2 maps and rules of one TAKE, one
    or more choose steps of one kind (firstn or indep) and one EMIT.

    do_rule_batch(ruleno, xs, result_max, dev_weights) mirrors
    CrushWrapper::do_rule over a whole batch of inputs; results carry
    ITEM_NONE holes exactly like the host engine.  Internally a fast
    f32 pass flags uncertain lanes, a resolve pass recomputes them
    exactly, and top-3-ambiguous dust goes to the scalar host engine —
    so results are always bit-identical to the host.
    """

    def __init__(self, crushmap: CrushMap,
                 choose_args_name: str | None = None):
        self.fm = FlatMap(crushmap, choose_args_name)
        self.map = crushmap
        self._cargs = (crushmap.choose_args.get(choose_args_name)
                       if choose_args_name else None)
        # (ruleno, result_max, chunk lanes, choose step) -> slots per
        # row group a dense pass's tail was found to need; the passes
        # thrown away to find it
        self._tail_want: dict[tuple, int] = {}
        self.tail_overflows = 0
        # (ruleno, result_max, lanes) -> the resolve chain's capacities
        # (K1, K2, K3) a pass of the pool outgrew its first ones to: the
        # next pass starts there and runs the chain once
        self._chain_want: dict[tuple, tuple] = {}

    @functools.lru_cache(maxsize=None)
    def _plan(self, ruleno: int, result_max: int) -> "_Plan":
        """The rule's choose steps, each with the tunables that govern
        it; ValueError for a rule outside device scope."""
        rule = self.fm.rules[ruleno]
        t = self.fm.tunables
        tries = t.choose_total_tries + 1     # historical off-by-one
        leaf_tries = 0
        vary_r = t.chooseleaf_vary_r
        stable = t.chooseleaf_stable
        take_id = None
        steps: list[_Step] = []
        emitted = False
        for op, arg1, arg2 in rule.steps:
            if op == TAKE:
                if steps:
                    raise ValueError(
                        "device mapper supports one TAKE/EMIT pair")
                take_id = arg1
            elif op == SET_CHOOSE_TRIES:
                if arg1 > 0:
                    tries = arg1
            elif op == SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    leaf_tries = arg1
            elif op == SET_CHOOSELEAF_VARY_R:
                if arg1 >= 0:
                    vary_r = arg1
            elif op == SET_CHOOSELEAF_STABLE:
                if arg1 >= 0:
                    stable = arg1
            elif op in (CHOOSE_FIRSTN, CHOOSELEAF_FIRSTN,
                        CHOOSE_INDEP, CHOOSELEAF_INDEP):
                if emitted:
                    raise ValueError(
                        "device mapper supports one TAKE/EMIT pair")
                if take_id is None or take_id >= 0:
                    raise ValueError("choose without a bucket take")
                numrep = arg1
                if numrep <= 0:
                    numrep += result_max
                if numrep <= 0:
                    raise ValueError("choose step of no replicas")
                firstn = op in (CHOOSE_FIRSTN, CHOOSELEAF_FIRSTN)
                leaf = op in (CHOOSELEAF_FIRSTN, CHOOSELEAF_INDEP)
                if firstn:
                    recurse = (leaf_tries if leaf_tries else
                               (1 if t.chooseleaf_descend_once else tries))
                else:
                    recurse = leaf_tries if leaf_tries else 1
                if not steps:
                    starts, n_in = [take_id], 1
                else:
                    prev = steps[-1]
                    if prev.firstn != firstn:
                        raise ValueError(
                            "device mapper supports steps of one kind, "
                            "firstn or indep")
                    if prev.leaf or prev.want_type == 0:
                        raise ValueError("choose step below devices")
                    starts = [b.id for b in self.map.buckets.values()
                              if b.type == prev.want_type]
                    n_in = prev.width
                outer_ds = self._depth_sizes(starts, arg2)
                if leaf:
                    inner_ds = self._depth_sizes(
                        [b.id for b in self.map.buckets.values()
                         if b.type == arg2], 0)
                else:
                    inner_ds = ()
                steps.append(_Step(
                    firstn, numrep, arg2, leaf, tries, recurse, vary_r,
                    stable, outer_ds, inner_ds,
                    min(result_max, n_in * min(numrep, result_max)),
                    0.0 if firstn else self._collide_share(
                        starts, arg2, min(numrep, result_max))))
            elif op == EMIT:
                emitted = bool(steps)
        if not steps:
            raise ValueError("rule has no choose step")
        if len(steps) > 1 and any(
                c < 0 and c not in self.map.buckets
                for b in self.map.buckets.values() for c in b.items):
            # the host skips a working-vector entry that names no
            # bucket; the flat map cannot tell it from an empty one
            raise ValueError("bucket item names no bucket")
        return _Plan(take_id, tuple(steps))

    def _compile(self, ruleno: int, result_max: int, resolve: bool,
                 full: bool = True, first_only: bool = False,
                 tails: tuple | None = None):
        """core(xs, dev_weights) -> (rows, flag): flag marks the lanes
        that a more exact pass has to recompute.  first_only (one firstn
        step, full=False): the first optimistic round of each replica
        alone, -> (rows, flag, unfinished) with the unplaced lanes
        apart.  tails (a whole pool's dense pass: per choose step the
        slots of its compacted tail, 0 for none): core(xs, dev_weights,
        seeds) -> (rows, flag, counts), seeds as _Tail's by PG lane and
        counts int32: the lanes an indep step's first full-width round
        left with an undefined slot, then _choose_indep_vec's three
        seats of each step that has a tail."""
        plan = self._plan(ruleno, result_max)
        fm = self.fm
        head = plan.steps[0]
        rootc = fm.const_row(plan.take_id, head.outer_ds[0])
        assert not first_only or len(plan.steps) == 1

        def core(xs, dev_weights, seeds=None):
            tail = [_Tail(kt, seeds) if kt else None
                    for kt in tails or (0,) * len(plan.steps)]
            if head.firstn:
                res, _, flag, unfinished = _choose_firstn_vec(
                    fm, plan.take_id, xs, head.numrep, result_max,
                    head.want_type, head.leaf, dev_weights,
                    head.tries, head.recurse, head.vary_r,
                    head.stable, head.outer_ds, head.inner_ds,
                    resolve, full, rootc, first_only)
                if first_only:
                    return res, flag, unfinished
                with scope("crush.step"):
                    flag = flag | unfinished
                    retry, seats = jnp.zeros_like(flag), [None]
            else:
                res, flag, retry, seat = _choose_indep_vec(
                    fm, plan.take_id, xs, head.numrep, result_max,
                    head.want_type, head.leaf, dev_weights,
                    head.tries, head.recurse, head.outer_ds,
                    head.inner_ds, resolve, full, rootc, tail=tail[0])
                seats = [seat]
            for st, tl in zip(plan.steps[1:], tail[1:]):
                res, f, rt, seat = _chain_step(
                    fm, st, res, xs, result_max, dev_weights, resolve,
                    full, tl)
                with scope("crush.step"):
                    flag, retry = flag | f, retry | rt
                seats.append(seat)
            if tails is None:
                return res, flag
            with scope("crush.step"):
                return res, flag, jnp.concatenate(
                    [jnp.sum(retry, dtype=jnp.int32)[None]]
                    + [seat for seat in seats if seat is not None])

        return core

    def _depth_sizes(self, start_bucket_ids: list[int],
                     want_type: int) -> tuple:
        """depth_sizes[d] = max size of any bucket reachable at depth d
        by walking bucket children from the start set (static per
        rule/map).  The walk stops once no child bucket can continue
        the descent — children of the wanted type are terminal (the
        draw 'reach'es them), so e.g. a root->host chooseleaf descent
        costs one draw level, not the tree height."""
        m = self.map
        sizes = []
        level = {b for b in start_bucket_ids if b in m.buckets}
        seen_levels = 0
        while level and seen_levels < 64:    # cycle guard
            sizes.append(max(
                (m.buckets[b].size for b in level), default=1) or 1)
            level = {c for b in level for c in m.buckets[b].items
                     if c < 0 and c in m.buckets
                     and m.buckets[c].type != want_type}
            seen_levels += 1
        return tuple(sizes) if sizes else (1,)

    def _collide_share(self, starts: list, want_type: int,
                       n: int) -> float:
        """The share of an indep step's takes whose n draws are expected
        to collide in their first round: the birthday collision among
        the items of the wanted type below a start bucket, at even
        weights, averaged over the start buckets.  Uneven weights and
        reweights add to it; a pass that finds more widens its tail
        (map_pool_state)."""
        m = self.map

        def below(bid: int) -> int:
            k = 0
            for c in m.buckets[bid].items:
                if c >= 0:
                    k += want_type == 0
                elif c in m.buckets:
                    k += (1 if m.buckets[c].type == want_type
                          else below(c))
            return k

        shares = []
        for b in starts:
            k = below(b) if b in m.buckets else 0
            shares.append(1.0 - math.prod(
                max(0.0, 1.0 - i / k) if k else 0.0 for i in range(n)))
        return sum(shares) / len(shares) if shares else 0.0

    @staticmethod
    def _note_compile(what: str, key: tuple) -> None:
        """Register a distinct crush program with the device runtime's
        compile counter.  Keys carry only the program signature (rule,
        shape, K buckets) — NOT instance identity — so DeviceMapper
        rebuilds across map epochs do not count as fresh compiles:
        the counter tracks what the acceptance criteria assert, the
        number of distinct programs a steady-state workload needs."""
        from ...device.runtime import DeviceRuntime
        DeviceRuntime.get().note_program("crush", (what,) + key)

    @functools.lru_cache(maxsize=None)
    def _compiled(self, ruleno: int, result_max: int, resolve: bool,
                  full: bool = True):
        self._note_compile("rule", (ruleno, result_max, resolve, full))
        return jax.jit(self._compile(ruleno, result_max, resolve, full))

    @functools.lru_cache(maxsize=None)
    def _compiled_map(self, ruleno: int, result_max: int,
                      can_shift: bool, use_aff: bool, resolve: bool,
                      full: bool = True):
        self._note_compile("map", (ruleno, result_max, can_shift,
                                   use_aff, resolve, full))
        core = self._compile(ruleno, result_max, resolve, full)

        @jax.jit
        def run(xs, dev_weights, exists_b, isup_b, aff):
            raw, flag = core(xs, dev_weights)
            up, prim = _post_process(raw, xs, exists_b, isup_b, aff,
                                     can_shift, use_aff)
            return up, prim, flag

        return run

    # per-dispatch PG cap: bounds live [L, S] f32/int32 temps in HBM
    CHUNK = 1 << 20

    # the dense pass's tail: compaction slots per RC_ROW-lane row group
    # a firstn rule starts with, and the most a tail is widened to
    # before its rounds go back to dense.  firstn's tail replays all
    # nine rounds of three replicas (at 512 of 2048 they cost 2.25
    # full-width rounds of the 6 they replace, gathers aside); an indep
    # step's runs the two later rounds of the step's three (at 1024 a
    # full-width round of the two they replace, and moving the lanes in
    # and out an eighth of one more)
    TAIL_KT = 256
    TAIL_KT_MAX = 512
    INDEP_TAIL_KT_MAX = 1024

    def _tail_start(self, ruleno: int, result_max: int, step: int) -> int:
        """Slots per row group a step's tail starts with, before any
        pass has been counted: firstn TAIL_KT; an indep step the hits
        its geometry lets expect in a row group (_collide_share) and two
        standard deviations (a group that runs over leaves a few lanes
        to the resolve chain, flagged)."""
        plan = self._plan(ruleno, result_max)
        if plan.firstn:
            return self.TAIL_KT
        p = plan.steps[step].collide
        mu = self.RC_ROW * p
        return math.ceil(mu + 2.0 * math.sqrt(mu * (1.0 - p))) or 1

    def _tail_slots(self, ruleno: int, result_max: int, C: int,
                    want: int, step: int = 0) -> int:
        """Slots per row group (>= want) for the tail of choose step
        `step` in a dense pass whose chunks are C PG lanes wide, or 0:
        no tail, every optimistic round of the step dense.  A firstn
        tail is the whole rule's, and needs a rule of one step; an
        indep step has its own, on the step's takes (C lanes times the
        entries of the working vector before it).  Either needs rounds
        to save, the attempt structure (lanes >= _ATTEMPT_MIN_L),
        rowcompact's alignment, and a width that keeps its descents in
        Pallas."""
        from . import pallas_draw
        plan = self._plan(ruleno, result_max)
        st = plan.steps[step]
        lanes = C * plan.lane_factors[step]
        if plan.firstn:
            rounds = (_firstn_attempts(st.tries, st.leaf, st.recurse)
                      if len(plan.steps) == 1 else 1)
            most = self.TAIL_KT_MAX
        else:
            rounds = min(_ATTEMPT_TRIES, st.tries)
            most = self.INDEP_TAIL_KT_MAX
        if not (rounds > 1 and lanes >= _ATTEMPT_MIN_L
                and self._rc_ok(lanes)):
            return 0
        nr = lanes // self.RC_ROW
        unit = max(128, pallas_draw.TL // math.gcd(nr, pallas_draw.TL))
        kt = unit * -(-want // unit)
        return kt if kt <= most else 0

    # -- whole-pool mapping with device-side pps -------------------------

    @functools.lru_cache(maxsize=None)
    def _compiled_pool(self, ruleno: int, result_max: int,
                       can_shift: bool, use_aff: bool, pgp_num: int,
                       pgp_mask: int, pool_id: int, hashps: bool,
                       n: int, n_chunks: int, tails: tuple = ()):
        """Whole pool in ONE dispatch: a lax.scan over fixed-size
        chunks (the chunking bounds the live [L,S] temps, the scan
        removes per-chunk dispatch/readback latency).  The dense pass
        runs the bounded attempt structure; lanes needing deeper
        retries are flagged and settled by the resolve passes, so its
        cost does not follow the worst lane's retry count.

        tails: per choose step the slots of its tail (_tail_slots), 0
        or nothing for every round over all n lanes.  A firstn rule's
        (one step, kt_tail slots): only the first optimistic round of
        each replica runs over all n lanes of a chunk (numrep descents,
        twice that for chooseleaf).  The lanes it leaves unplaced are
        compacted into kt_tail slots per row group (rowcompact),
        replayed from scratch through the whole attempt structure at
        that width (n / RC_ROW * kt_tail lanes) and their rows and
        flags put back (rowexpand).  An indep rule's are inside its
        steps (_choose_indep_vec).  Either way a row group with more
        unplaced lanes than slots keeps the rest flagged for the
        resolve chain; the pass's counts (lanes seated, lanes left
        unseated, largest group) tell the host when that is worth a
        wider tail.

        The pass's counts: a firstn tail's three, the lanes an indep
        rule's first full-width round left with an undefined slot (0
        for firstn), then three for each indep step that has a tail."""
        self._note_compile("pool", (ruleno, result_max, can_shift,
                                    use_aff, pgp_num, pgp_mask,
                                    pool_id, hashps, n, n_chunks,
                                    tails))
        from . import pallas_draw
        firstn = self._plan(ruleno, result_max).firstn
        kt_tail = tails[0] if firstn and tails else 0
        core = self._compile(
            ruleno, result_max, False, full=False,
            tails=None if kt_tail else tuple(tails))
        if kt_tail:
            first = self._compile(ruleno, result_max, False, full=False,
                                  first_only=True)
            rc = pallas_draw.make_rowcompact_kernel(n, self.RC_ROW,
                                                    kt_tail, n)

        @scope("crush.seeds")
        def pps(ps):
            return _pps(ps, pgp_num, pgp_mask, pool_id, hashps)

        def descend(start, dev_weights):
            xs = pps(jnp.arange(n, dtype=jnp.uint32) + start)
            if not kt_tail:
                # a firstn rule's rounds all run here at full width; an
                # indep rule's steps scope their own rounds and tails
                with scope("crush.first") if firstn else nullcontext():
                    raw, flag, counts = core(
                        xs, dev_weights,
                        lambda lane: pps(lane.astype(jnp.uint32) + start))
                with scope("crush.step"):
                    return xs, raw, flag, jnp.concatenate(
                        [jnp.zeros((3,), jnp.int32), counts])
            with scope("crush.first"):
                raw, flag, unfinished = first(xs, dev_weights)
            with scope("crush.tail.move"):
                idx, _valid, cnt = rc(unfinished)
                xs_t = pps(idx.astype(jnp.uint32) + start)
            with scope("crush.tail.rounds"):
                raw_t, flag_t = core(xs_t, dev_weights)
            # a seated lane takes its replayed row and flag; one that
            # got no slot keeps its row, flagged for the resolve chain
            expand = pallas_draw.make_rowexpand_kernel(
                n, self.RC_ROW, kt_tail, raw.shape[1] + 1)
            with scope("crush.tail.move"):
                rows = expand(
                    unfinished,
                    jnp.concatenate(
                        [raw,
                         (flag | unfinished)[:, None].astype(jnp.int32)],
                        axis=1),
                    jnp.concatenate(
                        [raw_t, flag_t[:, None].astype(jnp.int32)],
                        axis=1))
            with scope("crush.step"):
                seated = jnp.minimum(cnt, kt_tail)
            with scope("crush.tail.move"):
                rows, flag = rows[:, :-1], rows[:, -1] != 0
            with scope("crush.step"):
                return (xs, rows, flag,
                        jnp.stack([jnp.sum(seated), jnp.sum(cnt - seated),
                                   jnp.max(cnt), jnp.int32(0)]))

        @scope("crush.post")
        def post(raw, xs, exists_b, isup_b, aff):
            if not use_aff:
                if (pallas_draw.pallas_enabled()
                        and raw.shape[0] % pallas_draw.TL == 0):
                    pk = self._post_kernel(int(exists_b.shape[0]),
                                           int(raw.shape[1]),
                                           can_shift)
                    return pk(raw, exists_b & isup_b)
            return _post_process(raw, xs, exists_b, isup_b, aff,
                                 can_shift, use_aff)

        @jax.jit
        def run(dev_weights, exists_b, isup_b, aff):
            def body(_, start):
                xs, raw, flag, tail = descend(start, dev_weights)
                up, prim = post(raw, xs, exists_b, isup_b, aff)
                return 0, (raw, up, prim, flag, tail)

            with scope("crush.step"):
                starts = (jnp.arange(n_chunks, dtype=jnp.uint32)
                          * _u32(n))
            _, (raws, ups, prims, flags, counts) = jax.lax.scan(
                body, 0, starts)
            S = ups.shape[2]
            # per chunk: seated, unseated, largest group; the retry
            # count; then the same three per indep step with a tail
            with scope("crush.step"):
                return (raws.reshape(-1, S), ups.reshape(-1, S),
                        prims.reshape(-1), flags.reshape(-1),
                        jnp.stack([
                            (jnp.max if i == 2 or (i > 3 and i % 3 == 0)
                             else jnp.sum)(counts[:, i])
                            for i in range(counts.shape[1])]))

        return run

    def _post_kernel(self, D: int, S: int, can_shift: bool):
        """Cached fused post-CRUSH kernel (non-affinity path)."""
        from . import pallas_draw
        cache = self.__dict__.setdefault("_post_kernel_cache", {})
        key = (D, S, can_shift)
        if key not in cache:
            cache[key] = pallas_draw.make_post_kernel(D, S, can_shift)
        return cache[key]

    def _resolve_chain_parts(self, ruleno: int, result_max: int,
                             can_shift: bool, use_aff: bool,
                             pgp_num: int, pgp_mask: int, pool_id: int,
                             hashps: bool, K1: int, K2: int, K3: int):
        """Shared pieces of the device-resident resolve chain: the
        device pps seed computation, the settle-and-scatter helper and
        the three-stage compact/resolve cascade (exact-top3 attempt
        structure -> full retry loops -> fully exact integer draw).
        Used by both the full-map resolve and the incremental remap so
        the pad-masking subtleties live in one place."""
        acore_a = self._compile(ruleno, result_max, True, full=False)
        rcore = self._compile(ruleno, result_max, True, True)
        acore = self._compile(ruleno, result_max, "all", True)

        @scope("crush.seeds")
        def pps(idx):
            return _pps(idx, pgp_num, pgp_mask, pool_id, hashps)

        def settle(core_fn, raw_t, up, prim, lanes, w, ex, iu, af):
            with scope("crush.settle.draw"):
                xs = pps(lanes)
                rr, f = core_fn(xs, w)
            with scope("crush.settle.post"):
                u2, p2 = _post_process(rr, xs, ex, iu, af, can_shift,
                                       use_aff)
            with scope("crush.settle.scatter"):
                raw_t = raw_t.at[lanes].set(rr.astype(jnp.int32))
                up = up.at[lanes].set(u2.astype(jnp.int32))
                prim = prim.at[lanes].set(p2.astype(jnp.int32))
            return raw_t, up, prim, f

        def chain(raw_t, up, prim, flag, nflag, to_lane, w, ex, iu,
                  af):
            """flag: bool over the caller's index space; to_lane maps
            compacted positions to global lane ids.  Padding positions
            compact to index 0 whose resolved row is exact anyway, but
            their FLAGS must be masked (pads mirror position 0 — if it
            flags, every pad copy would flag with it)."""
            with scope("crush.resolve.compact"):
                pos = jnp.nonzero(flag, size=K1, fill_value=0)[0]
                idx = to_lane(pos)
            # stage A: exact draws through the bounded attempt
            # structure (covers the f32-uncertainty majority)
            with scope("crush.resolve.a"):
                raw_t, up, prim, f2 = settle(acore_a, raw_t, up, prim,
                                             idx, w, ex, iu, af)
                f2 = f2 & (jnp.arange(K1, dtype=jnp.int32) < nflag)
                n2 = jnp.sum(f2, dtype=jnp.int32)
            # stage B: stragglers (unfinished retries + dust) through
            # the full retry loops, on a compacted subset
            with scope("crush.resolve.b"):
                lanesB = idx[jnp.nonzero(f2, size=K2, fill_value=0)[0]]
                raw_t, up, prim, f3 = settle(rcore, raw_t, up, prim,
                                             lanesB, w, ex, iu, af)
                f3 = f3 & (jnp.arange(K2, dtype=jnp.int32) < n2)
                n3 = jnp.sum(f3, dtype=jnp.int32)
            # stage C: residual top-3-ambiguous dust, fully exact
            with scope("crush.resolve.c"):
                lanesC = lanesB[jnp.nonzero(f3, size=K3, fill_value=0)[0]]
                raw_t, up, prim, _ = settle(acore, raw_t, up, prim,
                                            lanesC, w, ex, iu, af)
            return raw_t, up, prim, n2, n3

        return pps, settle, chain

    # rowcompact geometry: lanes per row group / default slot count
    RC_ROW = 2048
    RC_KT = 128

    def _rc_ok(self, npg: int) -> bool:
        """The pallas rowcompact path needs aligned lane counts and a
        mosaic-capable backend (or interpret mode in tests)."""
        from . import pallas_draw
        return (pallas_draw.pallas_enabled()
                and npg % (8 * self.RC_ROW) == 0)

    @functools.lru_cache(maxsize=None)
    def _compiled_device_resolve(self, ruleno: int, result_max: int,
                                 can_shift: bool, use_aff: bool,
                                 pgp_num: int, pgp_mask: int,
                                 pool_id: int, hashps: bool,
                                 K1: int, K2: int, K3: int, npg: int,
                                 pg_num: int, kt: int = 0):
        """Device-resident resolve for the full-map pass: compact the
        flagged lanes, settle them through the three-stage chain, and
        scatter back — the only host traffic is the overflow-guard
        counters (every readback is a host round trip); `tail`, the
        dense pass's own counters, rides along in them, and so does the
        count of up slots that end ITEM_NONE.

        kt > 0 uses the pallas rowcompact kernel for the first
        compaction: XLA's nonzero over the full PG axis is the single
        most expensive op of the resolve on this platform (~0.9s at
        10M lanes, BENCH r4 notes); rowcompact reduces the nonzero to
        the npg/ROW*kt padded index space.  kt == 0 is the pure-XLA
        fallback."""
        self._note_compile("resolve", (ruleno, result_max, can_shift,
                                       use_aff, K1, K2, K3, npg,
                                       pg_num, kt))
        from . import pallas_draw
        _pps, _settle, chain = self._resolve_chain_parts(
            ruleno, result_max, can_shift, use_aff, pgp_num, pgp_mask,
            pool_id, hashps, K1, K2, K3)
        rc = (pallas_draw.make_rowcompact_kernel(
                  npg, self.RC_ROW, kt, pg_num) if kt else None)

        @jax.jit
        def run(raw_t, up, prim, flag, tail, w, ex, iu, af):
            if rc is not None:
                with scope("crush.resolve.compact"):
                    idxp, validp, cnt = rc(flag)
                    nflag = jnp.sum(validp, dtype=jnp.int32)
                    rowmax = jnp.max(cnt)
                raw_t, up, prim, n2, n3 = chain(
                    raw_t, up, prim, validp, nflag,
                    lambda p: idxp[p], w, ex, iu, af)
            else:
                with scope("crush.resolve.compact"):
                    flag2 = flag & (jnp.arange(npg, dtype=jnp.int32)
                                    < pg_num)
                    nflag = jnp.sum(flag2, dtype=jnp.int32)
                    rowmax = jnp.int32(0)
                raw_t, up, prim, n2, n3 = chain(
                    raw_t, up, prim, flag2, nflag, lambda p: p, w, ex,
                    iu, af)
            with scope("crush.resolve.counts"):
                none = jnp.sum((up == ITEM_NONE) & (jnp.arange(
                    npg, dtype=jnp.int32) < pg_num)[:, None],
                    dtype=jnp.int32)
                return raw_t, up, prim, jnp.concatenate(
                    [jnp.stack([nflag, n2, n3, rowmax]), tail, none[None]])

        return run

    def map_pool_batch(self, ruleno: int, result_max: int, pg_num: int,
                       pgp_num: int, pgp_num_mask: int, pool_id: int,
                       hashpspool: bool, dev_weights, exists, isup,
                       aff=None, can_shift: bool = True):
        """Whole-pool pg->up pipeline as dense numpy arrays; thin
        wrapper over map_pool_state (which keeps everything
        device-resident for consumers that chain incremental
        remaps)."""
        return self.read_tables(self.map_pool_state(
            ruleno, result_max, pg_num, pgp_num, pgp_num_mask, pool_id,
            hashpspool, dev_weights, exists, isup, aff, can_shift))

    @staticmethod
    def read_tables(state: "MapState"):
        """A pass's up rows and primaries as numpy arrays on the host."""
        with span("crush.readback", bytes=state.pg_num * (
                state.up_full.nbytes + state.prim_full.nbytes)
                // state.npg):
            return np.array(state.up), np.array(state.prim)

    def map_pool_state(self, ruleno: int, result_max: int, pg_num: int,
                       pgp_num: int, pgp_num_mask: int, pool_id: int,
                       hashpspool: bool, dev_weights, exists, isup,
                       aff=None, can_shift: bool = True) -> "MapState":
        """Full device pass returning a MapState (device-resident
        raw/up/prim + the host-side inputs needed to validate later
        incremental remaps)."""
        use_aff = aff is not None
        w_np = np.asarray(dev_weights, dtype=np.int32)
        ex_np = np.asarray(exists, dtype=bool)
        iu_np = np.asarray(isup, dtype=bool)
        af_np = (np.asarray(aff, dtype=np.int32) if use_aff
                 else np.zeros((ex_np.shape[0],), np.int32))
        with span("crush.upload", bytes=w_np.nbytes + ex_np.nbytes
                  + iu_np.nbytes + af_np.nbytes):
            w, ex = jnp.asarray(w_np), jnp.asarray(ex_np)
            iu, af = jnp.asarray(iu_np), jnp.asarray(af_np)
        C = min(self.CHUNK, max(8, -(-pg_num // 8) * 8))
        n_chunks = -(-pg_num // C)
        npg = C * n_chunks
        K1 = max(64, min(1 << 16,
                         1 << (max(1, pg_num - 1)).bit_length()))
        K2 = max(8, min(1 << 13, K1))
        K3 = max(8, min(2048, K1))
        chain_key = (ruleno, result_max, npg)
        K1, K2, K3 = self._chain_want.get(chain_key, (K1, K2, K3))
        kt = self.RC_KT if self._rc_ok(npg) else 0
        plan = self._plan(ruleno, result_max)
        dense = None
        while True:
            if dense is None:
                tails = tuple(
                    self._tail_slots(
                        ruleno, result_max, C, self._tail_want.get(
                            (ruleno, result_max, C, i),
                            self._tail_start(ruleno, result_max, i)), i)
                    for i in range(len(plan.steps)))
                fn = self._compiled_pool(
                    ruleno, result_max, bool(can_shift), use_aff,
                    int(pgp_num), int(pgp_num_mask), int(pool_id),
                    bool(hashpspool), C, n_chunks, tails)
                in_pallas = self.fm.descent_in_pallas
                widths = [C * f for f in plan.lane_factors]
                widths += [n // self.RC_ROW * t
                           for n, t in zip(widths, tails) if t]
                with span("crush.launch", lanes=npg, pallas_lanes=(
                        npg if all(in_pallas.get(n) for n in widths)
                        else 0), steps=len(plan.steps)):
                    dense = fn(w, ex, iu, af)
            res = self._compiled_device_resolve(
                ruleno, result_max, bool(can_shift), use_aff,
                int(pgp_num), int(pgp_num_mask), int(pool_id),
                bool(hashpspool), K1, K2, K3, npg, pg_num, kt)
            with span("crush.launch"):
                raw2, up2, prim2, counts = res(*dense, w, ex, iu, af)
            with span("crush.wait"):
                (nflag, n2, ndust, rowmax, tail_lanes, unseated,
                 tail_max, retry_lanes, *indep, none_slots) = (
                    int(v) for v in np.asarray(counts))
            # per tail (step, lanes seated, left unseated, largest row
            # group): a firstn rule's one, or an indep rule's steps'
            tailed = [i for i, t in enumerate(tails) if t]
            seats = ([(0, tail_lanes, unseated, tail_max)] if plan.firstn
                     else [(i, *indep[3 * k:3 * k + 3])
                           for k, i in enumerate(tailed)])
            over = [(i, most) for i, seated, left, most in seats
                    if left * 16 > seated]
            if over:
                # row groups with more unplaced lanes than the tail has
                # slots left the rest to the resolve chain, flagged: a
                # few cost nothing and change no program, but past a
                # sixteenth of the tail the pass is thrown away and
                # this pool's passes run that step's tail wider from
                # here on, or its rounds dense past the most a tail may
                # have (until the crush map, and with it this mapper,
                # is replaced)
                self.tail_overflows += 1
                for i, most in over:
                    self._tail_want[(ruleno, result_max, C, i)] = (
                        most + most // 4)
                dense = None
                continue
            if kt and rowmax > kt:
                # a row group overflowed its compaction slots: widen
                kt = 128 * (-(-int(rowmax * 2) // 128))
                if kt > 2048:
                    kt = 0      # absurd flag density: XLA fallback
                continue
            if nflag <= K1 and n2 <= K2 and ndust <= K3:
                break
            K1 = max(K1, 1 << (max(1, nflag - 1)).bit_length())
            K2 = max(K2, min(1 << (max(1, n2 - 1)).bit_length(), K1))
            K3 = max(K3, min(1 << (max(1, ndust - 1)).bit_length(),
                             K1))
            self._chain_want[chain_key] = (K1, K2, K3)
        indep_tail_lanes = (0 if plan.firstn
                            else sum(seated for _, seated, _, _ in seats))
        mark("crush.lanes", lanes=npg, tail_lanes=tail_lanes,
             resolve_lanes=nflag, retry_lanes=retry_lanes,
             indep_tail_lanes=indep_tail_lanes, none_slots=none_slots)
        return MapState(
            self, ruleno, result_max, pg_num, pgp_num, pgp_num_mask,
            pool_id, bool(hashpspool), bool(can_shift), use_aff,
            raw2, up2, prim2, w_np, ex_np, iu_np, af_np, npg,
            lanes=npg, tail_lanes=tail_lanes, resolve_lanes=nflag,
            steps=len(plan.steps), retry_lanes=retry_lanes,
            none_slots=none_slots, indep_tail_lanes=indep_tail_lanes)

    @functools.lru_cache(maxsize=None)
    def _compiled_remap(self, ruleno: int, result_max: int,
                        can_shift: bool, use_aff: bool, pgp_num: int,
                        pgp_mask: int, pool_id: int, hashps: bool,
                        KA: int, K1: int, K2: int, K3: int, npg: int,
                        pg_num: int, KT: int = 0):
        """Incremental remap: find the lanes whose raw row touches a
        changed OSD (a hit-scan kernel over the stored raw rows),
        recompute only those through the fast pass, and settle their
        flagged residue through the shared resolve chain — all
        device-resident.  Sound because a lane's draw/rejection
        sequence is bit-identical under reweight DECREASES and
        up/down/affinity changes unless one of its raw result slots
        held a changed OSD (see MapState's validity argument)."""
        self._note_compile("remap", (ruleno, result_max, can_shift,
                                     use_aff, KA, K1, K2, K3, npg,
                                     pg_num, KT))
        from . import pallas_draw
        core = self._compile(ruleno, result_max, False, full=False)
        _pps, settle, chain = self._resolve_chain_parts(
            ruleno, result_max, can_shift, use_aff, pgp_num, pgp_mask,
            pool_id, hashps, K1, K2, K3)
        # KA == 0 selects the pallas rowcompact compaction (KT slots
        # per 2048-lane row group): the npg-wide jnp.nonzero this
        # replaces was ~70% of the whole remap on this platform
        rc = (pallas_draw.make_rowcompact_kernel(
                  npg, self.RC_ROW, KT, pg_num)
              if KA == 0 else None)

        @jax.jit
        def run(raw_t, up, prim, w, ex, iu, af, changed):
            D = changed.shape[0]
            if (pallas_draw.pallas_enabled()
                    and raw_t.shape[0] % pallas_draw.TL == 0):
                hs = pallas_draw.make_hitscan_kernel(
                    D, int(raw_t.shape[1]))
                hit = hs(raw_t, changed)
            else:
                idxc = jnp.clip(raw_t, 0, D - 1)
                cb = small_fetch(changed.astype(jnp.int32), idxc, 1)
                hit = jnp.any((raw_t != ITEM_NONE) & (raw_t < D)
                              & (cb > 0), axis=1)
            if rc is not None:
                # padded per-group compaction: pad slots duplicate the
                # group base lane (settle recomputes it harmlessly)
                # and the validity mask gates the flags
                idxA, validA, cnt = rc(hit)
                nA = jnp.sum(validA, dtype=jnp.int32)
                rowmax = jnp.max(cnt)
                raw_t, up, prim, flag = settle(core, raw_t, up, prim,
                                               idxA, w, ex, iu, af)
                flag = flag & validA
            else:
                hit = hit & (jnp.arange(npg, dtype=jnp.int32)
                             < pg_num)
                nA = jnp.sum(hit, dtype=jnp.int32)
                rowmax = jnp.int32(0)
                idxA = jnp.nonzero(hit, size=KA, fill_value=0)[0]
                raw_t, up, prim, flag = settle(core, raw_t, up, prim,
                                               idxA, w, ex, iu, af)
                flag = flag & (jnp.arange(KA, dtype=jnp.int32) < nA)
            nflag = jnp.sum(flag, dtype=jnp.int32)
            raw_t, up, prim, n2, n3 = chain(
                raw_t, up, prim, flag, nflag, lambda p: idxA[p],
                w, ex, iu, af)
            return raw_t, up, prim, jnp.stack(
                [nA, nflag, n2, n3, rowmax])

        return run

    def do_rule_batch(self, ruleno: int, xs, result_max: int,
                      dev_weights) -> np.ndarray:
        """xs: int array [L] of inputs (pps values); dev_weights: int32
        [max_devices] 16.16 reweights.  Returns [L, numrep] int32 with
        ITEM_NONE holes."""
        fast = self._compiled(ruleno, result_max, False, full=False)
        xs = np.asarray(xs, dtype=np.int64) & 0xFFFFFFFF
        w = jnp.asarray(np.asarray(dev_weights, dtype=np.int32))
        res, flag = fast(jnp.asarray(xs, dtype=jnp.uint32), w)
        res = np.array(res)
        flag = np.array(flag)
        flagged = np.nonzero(flag)[0]
        if flagged.size:
            rfn = self._compiled(ruleno, result_max, True)
            # pad to a pow2 bucket: a per-call exact size would recompile
            # the full retry pipeline for every distinct flagged count
            n2 = max(8, 1 << (int(flagged.size) - 1).bit_length())
            part = np.zeros((n2,), np.int64)
            part[:flagged.size] = xs[flagged]
            r2, f2 = rfn(jnp.asarray(part, dtype=jnp.uint32), w)
            res[flagged] = np.array(r2)[:flagged.size]
            f2 = np.array(f2)[:flagged.size]
            for lane in flagged[np.nonzero(f2)[0]]:
                row = self._host_raw(ruleno, int(xs[lane]), result_max,
                                     dev_weights)
                res[lane] = row[:res.shape[1]]
        return res

    # -- host dust (scalar exact fallback) ------------------------------

    def _host_raw(self, ruleno: int, x: int, result_max: int,
                  dev_weights) -> np.ndarray:
        from .host import Mapper
        weights = [int(v) for v in np.asarray(dev_weights)]
        raw = Mapper(self.map).do_rule(ruleno, x, result_max, weights,
                                       choose_args=self._cargs)
        row = np.full((result_max,), ITEM_NONE, np.int32)
        row[:len(raw)] = raw[:result_max]
        return row
