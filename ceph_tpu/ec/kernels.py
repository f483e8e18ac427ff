"""TPU erasure-code kernels: GF(2^w) region matmul as bit-plane GF(2)
matrix multiply on the MXU.

Every GF(2^w) multiply-by-constant is linear over GF(2), so an (m x k)
GF coding matrix expands to an (m*w x k*w) 0/1 bitmatrix (the same
expansion jerasure uses for its XOR schedules — see
ceph_tpu.ec.matrices.matrix_to_bitmatrix).  Encoding a batch of chunks
is then

    parity_bits = (B @ data_bits) mod 2

i.e. one int8 matmul on the MXU plus cheap shift/mask pack/unpack on
the VPU — no gathers, no scalar GF tables, batch axis as wide as all
in-flight stripes (the reference's per-4KiB-call path,
src/erasure-code/isa/ErasureCodeIsa.cc:129 ec_encode_data, iterates on
the CPU instead).

Two implementations:
  * encode_xla / make_encoder — pure XLA (unpack, dot_general, pack),
    fused by the compiler; works on any backend.
  * pallas kernel (make_encoder(..., use_pallas=True)) — tiles the
    batch axis and keeps the 8x bit-plane expansion in VMEM only, so
    HBM traffic stays (k+m)/k of the payload.

Decode reuses the same kernel with the inverted matrix (host-side
inversion, cached by erasure signature like ErasureCodeIsaTableCache).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import matrices
from ..trace.span import scope

# ---------------------------------------------------------------------------
# bit-plane helpers
# ---------------------------------------------------------------------------


def _unpack_bits(data: jax.Array, w: int) -> jax.Array:
    """(k, n) uint8/uint16/uint32 words -> (k*w, n) int8 bit-planes,
    row j*w + x = bit x of word j (matching matrix_to_bitmatrix column
    order)."""
    k, n = data.shape
    d = data.astype(jnp.int32)
    planes = jnp.stack([(d >> x) & 1 for x in range(w)], axis=1)  # (k, w, n)
    return planes.reshape(k * w, n).astype(jnp.int8)


def _pack_bits(bits: jax.Array, w: int, dtype) -> jax.Array:
    """(m*w, n) int32 0/1 -> (m, n) packed words."""
    mw, n = bits.shape
    m = mw // w
    planes = bits.reshape(m, w, n).astype(jnp.uint32)
    weights = jnp.asarray([(1 << x) & 0xFFFFFFFF for x in range(w)],
                          dtype=jnp.uint32)[None, :, None]
    return jnp.sum(planes * weights, axis=1).astype(dtype)


def _word_dtype(w: int):
    return {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}[w]


# ---------------------------------------------------------------------------
# XLA path
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("w",))
def encode_xla(bitmatrix: jax.Array, data: jax.Array, w: int = 8) -> jax.Array:
    """bitmatrix (m*w, k*w) int8; data (k, n) words -> (m, n) words."""
    bits = _unpack_bits(data, w)
    acc = jax.lax.dot_general(
        bitmatrix.astype(jnp.int8), bits,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return _pack_bits(acc & 1, w, data.dtype)


# ---------------------------------------------------------------------------
# Pallas path (TPU): keep the bit-plane expansion in VMEM
# ---------------------------------------------------------------------------


def _ec_tile_kernel(b_ref, d_ref, o_ref, *, w: int, k: int, m: int):
    d = d_ref[...].astype(jnp.int32)                       # (k, T)
    planes = jnp.stack([(d >> x) & 1 for x in range(w)], axis=1)
    bits = planes.reshape(k * w, d.shape[1]).astype(jnp.int8)
    acc = jax.lax.dot_general(
        b_ref[...], bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32) & 1              # (m*w, T)
    pl = acc.reshape(m, w, d.shape[1])
    packed = pl[:, 0, :]
    for x in range(1, w):
        packed = packed | (pl[:, x, :] << x)
    o_ref[...] = packed.astype(o_ref.dtype)


def _encode_pallas(bitmatrix: np.ndarray, w: int, k: int, m: int,
                   tile: int = 16384):
    from jax.experimental import pallas as pl

    bm = jnp.asarray(bitmatrix, dtype=jnp.int8)
    # mosaic lowering is TPU-only; elsewhere run the kernel interpreted
    interpret = jax.default_backend() != "tpu"

    # index maps must yield int32 — under x64 (on for bit-exact CRUSH)
    # plain ints trace as i64, which mosaic cannot legalize
    i32 = jnp.int32

    @jax.jit
    def run(data: jax.Array) -> jax.Array:
        n = data.shape[1]
        pad = (-n) % tile
        if pad:
            data = jnp.pad(data, ((0, 0), (0, pad)))
        np_ = n + pad
        grid = (np_ // tile,)
        kern = functools.partial(_ec_tile_kernel, w=w, k=k, m=m)
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[
                pl.BlockSpec((m * w, k * w), lambda i: (i32(0), i32(0))),
                pl.BlockSpec((k, tile), lambda i: (i32(0), i32(i))),
            ],
            out_specs=pl.BlockSpec((m, tile), lambda i: (i32(0), i32(i))),
            out_shape=jax.ShapeDtypeStruct((m, np_), data.dtype),
            interpret=interpret,
            name="ec_encode_bitmatrix",
        )(bm, data)
        return out[:, :n] if pad else out

    return run


# ---------------------------------------------------------------------------
# XOR-schedule kernel on the bit-sliced ("planes8") chunk layout
# ---------------------------------------------------------------------------
#
# The MXU matmul path above is capped by the tiny M=m*w dimension (~5% MXU
# utilization).  The VPU path below reaches HBM bandwidth instead: chunks are
# stored bit-sliced — the same packetized layout jerasure's schedule encode
# uses on disk for cauchy/liberation codes — so encode degenerates to
# full-width vector XORs chosen by the bitmatrix, with no unpacking at all.
#
# planes8 layout of one chunk of L bytes (w=8): bit-plane x (bit x of every
# data byte) is packed little-endian into L/8 bytes and laid out as 8 sublane
# rows of L/64 columns; a chunk is a (64, L/64) uint8 array, a k-chunk stripe
# batch is (k*64, P) with P = total columns.


def bytes_to_planes8(chunks: np.ndarray) -> np.ndarray:
    """(k, L) uint8 byte-layout chunks -> (k*64, L//64) planes8."""
    k, L = chunks.shape
    bits = np.unpackbits(chunks.reshape(k, L, 1), axis=2, bitorder="little")
    planes = []
    for j in range(k):
        for x in range(8):
            pb = np.packbits(bits[j, :, x], bitorder="little")  # (L/8,)
            planes.append(pb.reshape(8, L // 64))
    return np.concatenate(planes, axis=0)


def planes8_to_bytes(planes: np.ndarray, nchunks: int) -> np.ndarray:
    """(nchunks*64, P) planes8 -> (nchunks, P*64) byte-layout chunks."""
    rows, P = planes.shape
    L = P * 64
    out = np.zeros((nchunks, L), dtype=np.uint8)
    for j in range(nchunks):
        byte_bits = np.zeros((L, 8), dtype=np.uint8)
        for x in range(8):
            pb = planes[j * 64 + x * 8:(j * 64) + (x + 1) * 8].reshape(L // 8)
            byte_bits[:, x] = np.unpackbits(pb, bitorder="little")
        out[j] = np.packbits(byte_bits, axis=1, bitorder="little").reshape(L)
    return out


def _xor_schedule_pallas(bitmatrix: np.ndarray, tile: int):
    """Compiled planes8 encode: (in_rows*8, P) -> (out_rows*8, P)."""
    from jax.experimental import pallas as pl

    out_rows, in_rows = bitmatrix.shape
    bm = np.asarray(bitmatrix, dtype=bool)
    interpret = jax.default_backend() != "tpu"
    i32 = jnp.int32

    def kern(d_ref, o_ref):
        for i in range(out_rows):
            srcs = [j for j in range(in_rows) if bm[i, j]]
            if not srcs:
                o_ref[8 * i:8 * i + 8, :] = jnp.zeros(
                    (8, d_ref.shape[1]), dtype=o_ref.dtype)
                continue
            acc = d_ref[8 * srcs[0]:8 * srcs[0] + 8, :]
            for j in srcs[1:]:
                acc = acc ^ d_ref[8 * j:8 * j + 8, :]
            o_ref[8 * i:8 * i + 8, :] = acc

    @jax.jit
    def run(planes: jax.Array) -> jax.Array:
        P = planes.shape[1]
        if P % tile:
            raise ValueError(
                "plane column count %d must be a multiple of tile %d"
                % (P, tile))
        return pl.pallas_call(
            kern,
            grid=(P // tile,),
            in_specs=[pl.BlockSpec((in_rows * 8, tile),
                                   lambda i: (i32(0), i32(i)))],
            out_specs=pl.BlockSpec((out_rows * 8, tile),
                                   lambda i: (i32(0), i32(i))),
            out_shape=jax.ShapeDtypeStruct((out_rows * 8, P), planes.dtype),
            interpret=interpret,
            name="ec_xor_schedule",
        )(planes)

    return run


class PlanesEncoder:
    """HBM-bandwidth-bound encode/decode on the planes8 layout (w=8).

    `planes` is (k*64, P); returns (m*64, P). Batch many stripes by
    concatenating their chunk planes along the column axis; P must be a
    multiple of `tile`.
    """

    def __init__(self, matrix: list[list[int]], tile: int = 2048):
        self.m = len(matrix)
        self.k = len(matrix[0])
        self.w = 8
        self.matrix = matrix
        self.tile = tile
        self._bitmatrix = np.array(
            matrices.matrix_to_bitmatrix(self.k, self.m, 8, matrix),
            dtype=np.int8)
        self._fn = _xor_schedule_pallas(self._bitmatrix, tile)
        self._decoders: dict[tuple, object] = {}

    def __call__(self, planes: jax.Array) -> jax.Array:
        return self._fn(planes)

    def encode_stripes(self, stripes: np.ndarray) -> np.ndarray:
        """(batch, k, chunk_bytes) byte-layout -> (batch, m, chunk_bytes);
        convenience wrapper that converts layouts on the host."""
        b, k, c = stripes.shape
        if (b * c) % 64:
            raise ValueError(
                "batch*chunk_bytes=%d must be a multiple of 64 for the "
                "planes8 layout" % (b * c))
        planes = bytes_to_planes8(
            stripes.transpose(1, 0, 2).reshape(k, b * c))
        pad = (-planes.shape[1]) % self.tile
        if pad:
            planes = np.pad(planes, ((0, 0), (0, pad)))
        out = np.asarray(self._fn(jnp.asarray(planes)))
        if pad:
            out = out[:, :-pad]
        parity = planes8_to_bytes(out, self.m)   # (m, b*c)
        return parity.reshape(self.m, b, c).transpose(1, 0, 2)

    def decode_rows(self, erased: tuple[int, ...],
                    survivors: tuple[int, ...]):
        """Compiled planes8 reconstruction of `erased` from the first k
        of `survivors` (bit-level inversion, cached per signature)."""
        key = (erased, survivors[:self.k])
        fn = self._decoders.get(key)
        if fn is None:
            k, w = self.k, self.w
            rows = matrices.survivor_bitrows(
                k, w, self._bitmatrix, survivors)
            inv = np.array(matrices.gf2_invert(rows), dtype=np.int8)
            want = []
            for e in erased:
                if e < k:
                    want.extend(inv[e * w:(e + 1) * w])
                else:
                    # parity rows re-encoded through the inverse
                    comp = (self._bitmatrix[(e - k) * w:(e - k + 1) * w]
                            .astype(np.int32) @ inv.astype(np.int32)) & 1
                    want.extend(comp.astype(np.int8))
            fn = _xor_schedule_pallas(np.array(want, dtype=np.int8),
                                      self.tile)
            self._decoders[key] = fn
        return fn


# ---------------------------------------------------------------------------
# Fused byte-layout kernel: in-VMEM planes8 transpose + XOR schedule
# ---------------------------------------------------------------------------
#
# The PlanesEncoder above is HBM-bound but needs its input bit-sliced —
# and the cluster stores shards in ordinary byte layout, so round 3's
# write path fell back to the (MXU-underutilised) matmul kernel at ~5%
# utilisation.  This kernel closes that gap without changing the shard
# layout: chunks stream in byte layout, and the bytes<->planes8
# conversion happens *inside* the kernel as an 8x8 bit transpose done
# with a SWAR butterfly over uint32 lanes (3 masked swap rounds, 72
# vector ops per 8 segment vectors — the in-register transpose8 trick),
# so HBM traffic stays (k+m)/k of payload and the XOR schedule runs on
# full-width vectors.  The intra-kernel plane layout packs bit s from
# lane-segment s rather than from adjacent bytes; any fixed positional
# permutation commutes with the elementwise XOR schedule and the unpack
# butterfly (an involution) restores exact byte order, so outputs are
# bit-identical to the host codecs (pinned by tests).
#
# Replaces the reference's per-call CPU SIMD encode
# (src/erasure-code/isa/ErasureCodeIsa.cc:129 ec_encode_data;
# src/osd/ECBackend.cc:1539 submit_transaction -> ECUtil::encode).

_M4LO = np.uint32(0x0F0F0F0F)
_M4HI = np.uint32(0xF0F0F0F0)
_M2LO = np.uint32(0x33333333)
_M2HI = np.uint32(0xCCCCCCCC)
_M1LO = np.uint32(0x55555555)
_M1HI = np.uint32(0xAAAAAAAA)


def _bit_transpose8(v: list) -> list:
    """8x8 bit transpose across eight uint32 vectors (per byte slot):
    returns t with t[x] byte-bit s == v[s] byte-bit x.  Involution."""
    s4 = np.uint32(4)
    s2 = np.uint32(2)
    s1 = np.uint32(1)
    w = [None] * 8
    for i in range(4):
        a, b = v[i], v[i + 4]
        w[i] = (a & _M4LO) | ((b & _M4LO) << s4)
        w[i + 4] = ((a >> s4) & _M4LO) | (b & _M4HI)
    u = [None] * 8
    for g in (0, 4):
        for i in (0, 1):
            a, b = w[g + i], w[g + i + 2]
            u[g + i] = (a & _M2LO) | ((b & _M2LO) << s2)
            u[g + i + 2] = ((a >> s2) & _M2LO) | (b & _M2HI)
    t = [None] * 8
    for g in (0, 2, 4, 6):
        a, b = u[g], u[g + 1]
        t[g] = (a & _M1LO) | ((b & _M1LO) << s1)
        t[g + 1] = ((a >> s1) & _M1LO) | (b & _M1HI)
    return t


def _fused_xor_pallas(bitmatrix: np.ndarray, tile_lanes: int):
    """Compiled byte-layout encode: (k, P) uint32 -> (m, P) uint32.

    bitmatrix is (m*8, k*8) with col j*8+x = bit x of data chunk j,
    row i*8+y = bit y of parity chunk i (matrix_to_bitmatrix order).
    tile_lanes must be a multiple of 1024 (8 segments x 128 lanes).
    """
    from jax.experimental import pallas as pl

    out_bits, in_bits = bitmatrix.shape
    if out_bits % 8 or in_bits % 8:
        raise ValueError("bitmatrix dims must be multiples of 8")
    k = in_bits // 8
    m = out_bits // 8
    if tile_lanes % 1024:
        raise ValueError("tile_lanes must be a multiple of 1024")
    bm = np.asarray(bitmatrix, dtype=bool)
    interpret = jax.default_backend() != "tpu"
    i32 = jnp.int32
    # Sublane utilization: every ALU op (transpose butterflies and the
    # XOR schedule) runs on (R, seg) operands — R subtiles of each
    # chunk row stacked in sublanes — instead of height-1 rows that
    # would waste 7/8 of the VPU.  Largest R whose segments stay
    # lane-aligned wins.
    R = next(r for r in (8, 4, 2, 1)
             if tile_lanes % (8 * r * 128) == 0)
    seg = tile_lanes // (8 * R)

    def kern(d_ref, o_ref):
        # pack: per chunk row, 8 lane segments per subtile -> planes
        planes = []                      # planes[j][x]: (R, seg)
        for j in range(k):
            v = [jnp.concatenate(
                    [d_ref[j:j + 1, (r * 8 + s) * seg:
                           (r * 8 + s + 1) * seg] for r in range(R)],
                    axis=0) for s in range(8)]
            planes.append(_bit_transpose8(v))
        # XOR schedule on full-height (R, seg) plane blocks
        q = []
        for i in range(out_bits):
            srcs = [c for c in range(in_bits) if bm[i, c]]
            if not srcs:
                q.append(jnp.zeros((R, seg), dtype=jnp.uint32))
                continue
            j, x = divmod(srcs[0], 8)
            acc = planes[j][x]
            for c in srcs[1:]:
                j, x = divmod(c, 8)
                acc = acc ^ planes[j][x]
            q.append(acc)
        # unpack per parity chunk: transpose back, scatter segments
        for i in range(m):
            segs = _bit_transpose8([q[i * 8 + y] for y in range(8)])
            for s in range(8):
                for r in range(R):
                    o_ref[i:i + 1, (r * 8 + s) * seg:
                          (r * 8 + s + 1) * seg] = segs[s][r:r + 1, :]

    @jax.jit
    @scope("ec.encode")
    def run(data32: jax.Array) -> jax.Array:
        P = data32.shape[1]
        pad = (-P) % tile_lanes
        if pad:
            data32 = jnp.pad(data32, ((0, 0), (0, pad)))
        Pp = P + pad
        out = pl.pallas_call(
            kern,
            grid=(Pp // tile_lanes,),
            in_specs=[pl.BlockSpec((k, tile_lanes),
                                   lambda i: (i32(0), i32(i)))],
            out_specs=pl.BlockSpec((m, tile_lanes),
                                   lambda i: (i32(0), i32(i))),
            out_shape=jax.ShapeDtypeStruct((m, Pp), jnp.uint32),
            interpret=interpret,
            name="ec_encode_fused",
        )(data32)
        return out[:, :P] if pad else out

    return run


def _reconstruction_rows(matrix: list[list[int]], k: int, w: int,
                         erased: tuple[int, ...],
                         survivors: tuple[int, ...]) -> list[list[int]]:
    """GF rows that rebuild `erased` chunks from the first k usable
    survivors: invert the surviving rows, compose parity rows through
    the inverse (the decode-as-encode reformulation both device
    encoders share)."""
    inv, _chosen = matrices.decoding_matrix(
        k, w, matrix, list(erased), list(survivors))
    rows = []
    for e in erased:
        if e < k:
            rows.append(inv[e])
        else:
            coeff = matrix[e - k]
            rows.append([
                functools.reduce(
                    lambda a, t: a ^ t,
                    (matrices.gf_mul(coeff[j], inv[j][i], w)
                     for j in range(k)), 0)
                for i in range(k)])
    return rows


class FusedEncoder:
    """Byte-layout encode/reconstruct at HBM bandwidth (w=8 only).

    Drop-in for DeviceEncoder where w == 8: `data` is (k, n) uint8
    words in ordinary byte layout; returns (m, n) parity bytes,
    bit-identical to the host codecs.  run32 is the device-resident
    entry point on (k, n//4) uint32 views (free reinterpretation of
    the same bytes, little-endian lanes).

    Ragged-segment friendliness: the kernel pads its input to a tile
    multiple, so a fixed big tile would hand a small bucket-ladder
    segment (ec.batcher ragged staging) back all the padding the
    ladder just removed.  The tile therefore ADAPTS: inputs smaller
    than `tile_bytes` compile against the largest halving of the tile
    that still covers them (floored at the 1024-lane VPU alignment),
    one cached program per clamped tile — the tile ladder mirrors the
    bucket ladder, so segment programs stay few and pad stays
    sub-tile.
    """

    def __init__(self, matrix: list[list[int]], tile_bytes: int = 32768):
        self.m = len(matrix)
        self.k = len(matrix[0])
        self.w = 8
        self.matrix = matrix
        self.tile_bytes = tile_bytes
        bm = np.array(
            matrices.matrix_to_bitmatrix(self.k, self.m, 8, matrix),
            dtype=np.int8)
        self._bitmatrix = bm
        self._fns: dict[int, object] = {}   # tile_lanes -> compiled
        self._decoders: dict[tuple, "FusedEncoder"] = {}

    def _tile_lanes_for(self, lanes: int) -> int:
        """Clamped tile (uint32 lanes) for an input of `lanes`: halve
        the configured tile while it still over-covers the input,
        never below the 1024-lane alignment _fused_xor_pallas needs."""
        tile = self.tile_bytes // 4
        while tile > 1024 and tile >= 2 * max(1, lanes):
            tile //= 2
        return max(tile, 1024)

    def _fn_for(self, lanes: int):
        tile = self._tile_lanes_for(lanes)
        fn = self._fns.get(tile)
        if fn is None:
            fn = _fused_xor_pallas(self._bitmatrix, tile)
            self._fns[tile] = fn
        return fn

    def run32(self, data32: jax.Array) -> jax.Array:
        """(k, P) uint32 -> (m, P) uint32, device-resident."""
        return self._fn_for(data32.shape[1])(data32)

    @property
    def program_count(self) -> int:
        """Distinct compiled tile programs this encoder holds — the
        encoder-side ground truth the dispatch-stream bench reports
        beside the runtime's note_program bookkeeping (the two must
        agree on 'a handful': slots reuse the fixed tile family)."""
        return len(self._fns)

    def __call__(self, data: np.ndarray) -> np.ndarray:
        k, n = data.shape
        pad = (-n) % 4
        if pad or data.dtype != np.uint8:
            data = np.ascontiguousarray(data, dtype=np.uint8)
        if pad:
            data = np.pad(data, ((0, 0), (0, pad)))
        d32 = np.ascontiguousarray(data).view(np.uint32)
        out = np.asarray(self._fn_for(d32.shape[1])(jnp.asarray(d32)))
        out8 = out.view(np.uint8)
        return out8[:, :n] if pad else out8

    def decoder_for(self, erased: tuple[int, ...],
                    survivors: tuple[int, ...]) -> "FusedEncoder":
        """Reconstruction rows through the same fused kernel (cached
        per erasure signature, like ErasureCodeIsaTableCache)."""
        key = (erased, survivors[:self.k])
        dec = self._decoders.get(key)
        if dec is None:
            rows = _reconstruction_rows(self.matrix, self.k, self.w,
                                        erased, survivors)
            dec = FusedEncoder(rows, self.tile_bytes)
            self._decoders[key] = dec
        return dec


# ---------------------------------------------------------------------------
# public surface
# ---------------------------------------------------------------------------


class DeviceEncoder:
    """Compiled encode (and decode) for one (matrix, w) on the current
    backend. `data` is (k, n) words; n is the flattened batch of all
    in-flight stripes — pad n to the tile size for the pallas path."""

    def __init__(self, matrix: list[list[int]], w: int = 8,
                 use_pallas: bool = False, tile: int = 16384):
        self.m = len(matrix)
        self.k = len(matrix[0])
        self.w = w
        self.matrix = matrix
        self.tile = tile
        bm = np.array(
            matrices.matrix_to_bitmatrix(self.k, self.m, w, matrix),
            dtype=np.int8)
        self._bm = jnp.asarray(bm)
        if use_pallas:
            self._fn = _encode_pallas(bm, w, self.k, self.m, tile)
        else:
            self._fn = functools.partial(encode_xla, self._bm, w=self.w)
        self._decoders: dict[tuple, "DeviceEncoder"] = {}
        self._shapes: set[tuple] = set()    # traced input shapes

    def __call__(self, data: jax.Array) -> jax.Array:
        self._shapes.add((int(data.shape[0]), int(data.shape[1])))
        return self._fn(data)

    @property
    def program_count(self) -> int:
        """Distinct input shapes this encoder has traced (one XLA
        program each under jit's shape-keyed cache) — the encoder-side
        ground truth for the dispatch-stream bench's compile-budget
        cross-check."""
        return len(self._shapes)

    def encode_batch(self, stripes: np.ndarray) -> jax.Array:
        """(batch, k, chunk_bytes) uint8 -> (batch, m, chunk_bytes)."""
        b, k, c = stripes.shape
        flat = jnp.asarray(stripes).transpose(1, 0, 2).reshape(k, b * c)
        out = self._fn(flat)
        return out.reshape(self.m, b, c).transpose(1, 0, 2)

    def decoder_for(self, erased: tuple[int, ...],
                    survivors: tuple[int, ...]) -> "DeviceEncoder":
        """Compiled reconstruction: rows = erased chunk ids, inputs = the
        first k survivors. Cached per erasure signature."""
        key = (erased, survivors[:self.k])
        dec = self._decoders.get(key)
        if dec is None:
            rows = _reconstruction_rows(self.matrix, self.k, self.w,
                                        erased, survivors)
            dec = DeviceEncoder(rows, self.w)
            self._decoders[key] = dec
        return dec


@functools.lru_cache(maxsize=64)
def encoder_for_profile(plugin: str, technique: str, k: int, m: int,
                        w: int = 8, use_pallas: bool = False) -> DeviceEncoder:
    """Device encoder for the common matrix-backed profiles."""
    if plugin == "isa":
        mat = (matrices.isa_rs_vandermonde_matrix(k, m)
               if technique == "reed_sol_van"
               else matrices.isa_cauchy_matrix(k, m))
        return DeviceEncoder(mat, 8, use_pallas)
    if technique == "reed_sol_van":
        mat = matrices.reed_sol_vandermonde_coding_matrix(k, m, w)
    elif technique == "reed_sol_r6_op":
        mat = matrices.reed_sol_r6_coding_matrix(k, w)
    elif technique == "cauchy_orig":
        mat = matrices.cauchy_original_coding_matrix(k, m, w)
    elif technique == "cauchy_good":
        mat = matrices.cauchy_good_general_coding_matrix(k, m, w)
    else:
        raise ValueError("no device path for technique %r" % technique)
    return DeviceEncoder(mat, w, use_pallas)
