"""Device EC offload with cross-object batching.

SURVEY.md "hard parts": 4KiB stripes are tiny against dispatch/HBM
latency — the TPU win only materialises when many in-flight stripes
ride one dispatch.  This is the aggregation layer the reference doesn't
need (ISA-L encodes synchronously per call inside the OSD thread,
src/erasure-code/isa/ErasureCodeIsa.cc:129).

`DeviceBatcher.encode` is a thin enqueue shim onto the caller chip's
persistent dispatch stream (ceph_tpu.device.stream): continuous
admission into fixed-geometry slots, independent per-slot retire.
The stream's slot dispatches call back into `stream_dispatch` below,
which owns staging, mesh sharding, tickets and host degradation; a
"flush" in this module is one such slot dispatch.  With the whole
mesh down there is no stream to enter and `encode` host-encodes the
call inline.

Every flush routes through the shared device runtime
(ceph_tpu.device.runtime) onto a mesh **chip** — the caller's
affinity chip (OSDs pass `chip=`; chip-less callers take the first
available chip):

* the batch is **ragged**: items of heterogeneous width pack
  contiguously along the column axis with per-item segment offsets,
  and the flush TOTAL stages across a pow2 **bucket ladder**
  (``DeviceRuntime.ragged_plan`` — the Ragged Paged Attention recipe,
  arXiv:2604.15464), so only the ladder's tail rounds up: per-item
  padding is zero, mixed-size workloads stop burning bucket-ceiling
  bandwidth, and steady state still re-dispatches a handful of
  compiled bucket programs (zero padding is exact under GF linearity
  — parity columns of the pad are zeros that are sliced off, so
  ladder parity is bit-identical to the unpadded host encode, pinned
  by tests/test_device_runtime.py + tests/test_ec_ragged.py);
* admission is weighted-fair across classes (client-EC, recovery-EC,
  mapping) with bounded in-flight dispatches per chip; queue-full
  degrades THIS flush to the host codepath rather than stacking
  device work;
* an **oversized flush shards column-wise across every available
  chip** (the stripe-axis split MULTICHIP_SCALING.json proves
  collective-free: GF parity is column-independent) and reassembles
  bit-identically; a shard failure poisons only its chip and that
  shard is re-encoded on the host;
* a failed dispatch poisons ITS chip (host fallback for the OSDs
  bound there + per-chip DEVICE_FALLBACK health via the OSD beacon)
  and the flush is re-encoded on the host, so awaiting OSD ops never
  observe the loss — the rest of the mesh keeps serving on-device;
* each device flush carries a DispatchTicket delivered to per-item
  `on_ticket` callbacks — the exact per-op device-dispatch
  attribution the OpTracker stage histograms consume.

Decode/reconstruct rides the same queue: a reconstruction is an encode
with the cached inverted matrix (ErasureCodeIsaTableCache's trick), so
degraded reads and recovery batch with ordinary writes.
"""

from __future__ import annotations

import asyncio
import functools

import numpy as np

from . import matrices
from ..device.runtime import (DeviceBusy, DeviceRuntime, K_CLIENT_EC)
from ..trace.span import span

_WORD_DTYPE = {8: np.uint8, 16: np.uint16, 32: np.uint32}


def device_offload_enabled() -> bool:
    """Device EC offload defaults to on only where it pays: a real
    accelerator backend.  CEPH_TPU_EC_OFFLOAD=1/0 forces it (tests
    force 1 to exercise the batcher on the CPU backend)."""
    import os
    v = os.environ.get("CEPH_TPU_EC_OFFLOAD")
    if v is not None:
        return v not in ("0", "false", "no")
    try:
        import jax
    except ImportError:     # pragma: no cover - jax always present
        return False
    return jax.default_backend() == "tpu"


def host_encode(matrix, w: int, data: np.ndarray) -> np.ndarray:
    """Synchronous host GF matmul — the fallback codepath when the
    device is lost or admission pushes back.  [k, n] words -> [m, n]."""
    from . import gf
    m = np.asarray(matrix, dtype=np.int64)
    if int(w) == 8:
        return gf.matmul_u8(m.astype(np.uint8),
                            np.ascontiguousarray(data, np.uint8))
    return gf.matmul_words(m, data, int(w))


def tenant_label(tenants) -> str | None:
    """A dispatch's tenant attribution: the one tenant every batched
    item agreed on, "mixed" when several tenants' stripes share the
    dispatch, None for tenant-less work."""
    distinct = {t for t in tenants if t is not None}
    if not distinct:
        return None
    if len(distinct) == 1:
        return next(iter(distinct))
    return "mixed"


class DeviceBatcher:
    """Batches GF(2^w) region matmuls across concurrent callers.

    One instance per event loop (get() is loop-local): the dispatch
    path every chip's stream shares, and the counters of what it ran.
    """

    def __init__(self):
        self.batches_flushed = 0
        self.items_encoded = 0
        self.host_flushes = 0        # flushes served by the host path
        self.sharded_flushes = 0     # flushes split across the mesh

    @classmethod
    def get(cls) -> "DeviceBatcher":
        """Per-event-loop instance, stored ON the loop object so its
        lifetime tracks the loop's (an id(loop)-keyed registry would
        hand a recycled address a dead loop's instance)."""
        loop = asyncio.get_event_loop()
        inst = getattr(loop, "_ceph_tpu_ec_batcher", None)
        if inst is None:
            inst = cls()
            loop._ceph_tpu_ec_batcher = inst
        return inst

    @staticmethod
    @functools.lru_cache(maxsize=256)
    def _encoder(matrix_key: tuple, w: int):
        import os

        import jax

        from .kernels import DeviceEncoder, FusedEncoder
        matrix = [list(row) for row in matrix_key]
        if jax.default_backend() == "tpu" and w == 8 \
                and os.environ.get("CEPH_TPU_EC_FUSED") != "0":
            # the HBM-bandwidth path: XOR schedule with the planes8
            # bit transpose fused in VMEM, byte layout in/out — the
            # fast kernel IS the cluster write path (measured 391
            # GiB/s payload at this tile, k=8,m=3, round 4).  Tile
            # bounded for wide profiles so ~(2k+2m+buffering) x tile
            # stays inside VMEM.
            k, m = len(matrix[0]), len(matrix)
            tile = 262144 if k + m <= 11 else 131072
            return FusedEncoder(matrix, tile_bytes=tile)
        # the pallas matmul path keeps the w-fold bit-plane expansion
        # in VMEM; w=8 only — wider words use the XLA path
        use_pallas = jax.default_backend() == "tpu" and w == 8
        return DeviceEncoder(matrix, w, use_pallas=use_pallas,
                             tile=4096)

    async def encode(self, matrix: list[list[int]], w: int,
                     data: np.ndarray, klass: str = K_CLIENT_EC,
                     on_ticket=None, chip: int | None = None,
                     tenant: str | None = None) -> np.ndarray:
        """data [k, n] words -> [m, n] parity words, batched with any
        concurrent callers using the same (matrix, w, klass, chip).

        `chip` is the caller's mesh affinity (OSDs pass their bound
        chip; None routes to the first available chip) — each chip
        runs its own stream and a poisoned chip degrades only its own
        callers.

        `on_ticket` (if given) receives the slot's DispatchTicket
        after the device call — exact per-op dispatch attribution
        (the primary shard's ticket when the slot sharded across the
        mesh).  Host-encoded work delivers no ticket (there was no
        device dispatch to attribute).

        A thin enqueue shim onto the routed chip's persistent dispatch
        stream (device.stream): continuous admission, independent
        retire.  With the whole mesh down there is no stream to enter
        and this call is host-encoded inline."""
        rt = DeviceRuntime.get()
        data = np.ascontiguousarray(data)
        target = rt.route(chip)
        if target is not None:
            return await target.stream.encode(
                matrix, int(w), data, klass, on_ticket=on_ticket,
                tenant=tenant)
        try:
            out = self._host_dispatch(
                rt.chip(chip), tuple(tuple(r) for r in matrix),
                int(w), [data])
        except Exception as e:
            # a real codec error: it must reach the awaiting OSD op
            raise IOError("EC encode failed: %r" % e) from e
        self.batches_flushed += 1
        self.items_encoded += 1
        return out

    def _host_dispatch(self, chip, matrix_key, w: int,
                       parts: list[np.ndarray]) -> np.ndarray:
        """Host-codec degradation route, counted on `chip` (device
        lost / DeviceBusy / whole mesh down; a slot, or one shard of a
        mesh-split slot): bit-parity with the device path by
        construction, so correctness never depends on the mesh.
        Raises on a real codec error — the caller must fail the
        awaiting futures, never hang them."""
        flat = (parts[0] if len(parts) == 1
                else np.concatenate(parts, axis=1))
        out = host_encode([list(r) for r in matrix_key], w, flat)
        chip.host_fallbacks += 1
        self.host_flushes += 1
        return out

    async def stream_dispatch(self, chip, matrix_key, w: int,
                              klass: str, parts: list[np.ndarray],
                              n: int, tenant: str | None = None,
                              t_enqueue: float | None = None):
        """One stream slot's dispatch (device.stream DispatchStream):
        ragged bucket-ladder staging on the slot's chip, mesh
        sharding for oversized groups, with the host codec as the
        degradation route.
        Returns (out, ticket-or-None); raises only on a host-codec
        failure."""
        out = ticket = None
        if chip.available:
            plan = chip.rt.shard_plan(chip, n)
            if len(plan) == 1:
                out, ticket = await self._encode_shard(
                    chip, matrix_key, int(w), klass, parts, n,
                    solo=True, tenant=tenant, t_enqueue=t_enqueue)
            else:
                out, ticket = await self._encode_sharded(
                    plan, matrix_key, int(w), klass, parts,
                    tenant=tenant, t_enqueue=t_enqueue)
        if out is None:
            out = self._host_dispatch(chip, matrix_key, w, parts)
        self.batches_flushed += 1
        self.items_encoded += len(parts)
        return out, ticket

    async def _encode_shard(self, chip, matrix_key, w: int,
                            klass: str, parts: list[np.ndarray],
                            n: int, solo: bool,
                            tenant: str | None = None,
                            t_enqueue: float | None = None):
        """One chip's slice of a flush: admit on the chip's queue,
        stage the ragged total into its pooled bucket-ladder buffers,
        dispatch on its device.  Returns (parity [m, n], ticket).

        Ragged staging: the flush's heterogeneous-width items pack
        contiguously along the column axis; the packed total covers a
        **bucket ladder** (``DeviceRuntime.ragged_plan``) of pow2
        segments, each staged in its own pooled buffer and encoded by
        an already-compiled bucket program, so only the ladder's tail
        rounds up — per-item widths never pad, and a mixed-size flush
        stops burning bucket-ceiling bandwidth (GF parity is
        column-independent, so the segment split is exact).  Items may
        span segment boundaries; per-item offsets stay global column
        offsets, so the stream's per-op slicing is unchanged.

        `solo=True` is the whole-flush single-chip path: DeviceBusy
        and device loss return (None, None) so the caller degrades
        the WHOLE flush to the host codec (the pre-mesh behavior).
        Shards of a mesh-split flush (`solo=False`) instead degrade
        THEMSELVES to the host inline — a lost chip costs its shard,
        not the flush — so reassembly is unconditional."""
        dtype = _WORD_DTYPE[int(w)]
        k = parts[0].shape[0]
        plan = chip.rt.ragged_plan(n)
        padded = sum(seg for _lo, seg in plan)
        ticket = chip.open_ticket(klass, padded,
                                  n * k * dtype().itemsize,
                                  tenant=tenant, t_enqueue=t_enqueue,
                                  stream=True)
        try:
            await chip.admit(ticket)
        except DeviceBusy:
            if solo:
                return None, None
            return self._host_dispatch(chip, matrix_key, w, parts), None
        bufs: list[np.ndarray] = []
        try:
            with span("ec.stage", words=n, padded=padded):
                for _lo, seg in plan:
                    bufs.append(chip.pool.lease((k, seg), dtype))
                # pack items contiguously across the ladder (an item
                # can straddle two segments); leased buffers come back
                # zeroed so segment tails are exact GF zero columns
                si, soff = 0, 0
                for arr in parts:
                    ni, pos = arr.shape[1], 0
                    while pos < ni:
                        take = min(plan[si][1] - soff, ni - pos)
                        bufs[si][:, soff:soff + take] = \
                            arr[:, pos:pos + take]
                        soff += take
                        pos += take
                        if soff == plan[si][1]:
                            si += 1
                            soff = 0
            chip.launch(ticket)         # injected-fault hook
            enc = self._encoder(matrix_key, int(w))
            outs = []
            used = n
            with chip.scope():
                for (_lo, seg), buf in zip(plan, bufs):
                    chip.note_program("ec", (matrix_key, int(w), seg))
                    u = min(seg, used)
                    with span("ec.dispatch", bytes_in=buf.nbytes,
                              bytes_out=buf.nbytes // k
                              * len(matrix_key)):
                        outs.append(np.asarray(enc(buf))[:, :u])
                    used -= u
            out = (outs[0] if len(outs) == 1
                   else np.concatenate(outs, axis=1))
            chip.finish(ticket, ok=True)
            chip.note_staging(n, padded)
            return out, ticket
        except Exception as e:
            # device loss: poison THIS chip (host fallback + per-chip
            # DEVICE_FALLBACK health for the OSDs bound to it); the
            # rest of the mesh keeps serving
            chip.finish(ticket, ok=False, error=e)
            chip.poison(e)
            if solo:
                return None, None
            return self._host_dispatch(chip, matrix_key, w, parts), None
        finally:
            for buf in bufs:
                chip.pool.release(buf)

    async def _encode_sharded(self, plan, matrix_key, w: int,
                              klass: str, arrays: list[np.ndarray],
                              tenant: str | None = None,
                              t_enqueue: float | None = None):
        """Mesh-shard one oversized flush across the plan's chips:
        contiguous column slices encode concurrently (proven
        collective-free over the stripe axis) and reassemble
        bit-identically.  Returns (parity, primary ticket)."""
        flat = (arrays[0] if len(arrays) == 1
                else np.concatenate(arrays, axis=1))
        self.sharded_flushes += 1
        parts = await asyncio.gather(*[
            self._encode_shard(chip, matrix_key, w, klass,
                               [flat[:, lo:hi]], hi - lo, solo=False,
                               tenant=tenant, t_enqueue=t_enqueue)
            for chip, lo, hi in plan])
        out = np.concatenate([p for p, _t in parts], axis=1)
        ticket = next((t for _p, t in parts if t is not None), None)
        return out, ticket


def reconstruct_matrix(k: int, w: int, matrix: list[list[int]],
                       erased: tuple[int, ...],
                       have: tuple[int, ...]):
    """(rows, chosen): rows rebuild `erased` chunks directly from the
    `chosen` survivors — the decode-as-encode reformulation both
    device paths share (invert surviving rows, compose parity rows
    through the inverse).  Cached per erasure signature so a recovery
    sweep pays the O(k^3) GF inversion once, like
    ErasureCodeIsaTableCache."""
    key = (k, w, tuple(tuple(r) for r in matrix), erased, have)
    return _reconstruct_matrix_cached(key)


@functools.lru_cache(maxsize=512)
def _reconstruct_matrix_cached(key):
    k, w, matrix_t, erased, have = key
    matrix = [list(r) for r in matrix_t]
    inv, chosen = matrices.decoding_matrix(k, w, matrix, list(erased),
                                           list(have))
    rows = []
    for e in erased:
        if e < k:
            rows.append(list(inv[e]))
        else:
            coef = matrix[e - k]
            rows.append([
                functools.reduce(
                    lambda a, t: a ^ t,
                    (matrices.gf_mul(coef[j], inv[j][i], w)
                     for j in range(k)), 0)
                for i in range(k)])
    return rows, chosen
