"""Per-process TPU device runtime: the shared substrate under both
accelerator hot paths (batched EC matmuls and bulk CRUSH mapping),
now mesh-aware.

Why a runtime at all (PAPERS: Ragged Paged Attention 2604.15464 for the
shape-bucket recipe; "GPUs as Storage System Accelerators" 1202.3669
for admission control): until this layer existed each hot path talked
to JAX ad hoc — every novel batch width recompiled, staging buffers
were allocated per flush, and nothing bounded device queue depth, so a
mapping storm could starve EC writes.  The runtime centralises four
concerns, each now **per chip** (mesh discipline from "Large Scale
Distributed Linear Algebra With TPUs", 2112.09017):

* **shape-bucketed compile cache** — flushes stage as a **bucket
  ladder** (`ragged_plan`): power-of-two segments covering the exact
  ragged flush total, so only the ladder's tail rounds up instead of
  the whole flush padding to its pow2 ceiling, while steady state
  still hits a handful of jitted programs; `note_program` is the
  compile counter the acceptance criteria assert against, and
  `warmup_ec` pre-compiles the common buckets at OSD boot.  Each chip
  accounts its own programs (a real mesh compiles per chip) and its
  staging waste (`bucket_waste_ratio`).
* **HBM staging pool** — bucket-sized arrays leased/released across
  flushes instead of allocated per flush (`BufferPool`), one pool per
  chip.
* **dispatch queue with admission backpressure** — bounded in-flight
  dispatches, weighted-fair across service classes (client-EC /
  recovery-EC / mapping — the weights mirror the mClock op-scheduler
  profile, osd/scheduler.py DEVICE_DISPATCH_WEIGHTS); queue-full
  surfaces as `DeviceBusy` so callers degrade to deadline-flush or
  the host path instead of piling device work.  One queue per chip,
  so one OSD's storm cannot starve a co-located OSD on another chip.
* **device-loss degradation** — a failed/poisoned dispatch flips
  *its chip* to fallback: only the OSDs whose affinity lands on that
  chip degrade to host paths (and beacon it, so the mon's
  DEVICE_FALLBACK detail names the chip), while the rest of the mesh
  keeps serving on-device.  A per-chip probe loop retries under
  ExpBackoff until the chip heals.

The mesh is enumerated once per runtime (ceph_tpu.device.mesh): real
chips on a TPU host, ``CEPH_TPU_MESH_CHIPS`` logical chips on CPU CI
(or a real forced count under
``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  OSDs take
``chip_for(osd_id)`` affinity; oversized flushes shard column-wise
across every available chip (``shard_plan``) — the proven
collective-free split — and reassemble bit-identically.

Every dispatch carries a `DispatchTicket` (chip, class, bucket, bytes,
enqueue/launch/done stamps) that feeds the exporter
(`device_dispatch_seconds`, `device_queue_depth`,
`device_bucket_hit_ratio`, all labeled by ``chip``) and gives the
OpTracker exact per-op flush attribution.

Back-compat: the single-chip API (``DeviceRuntime.poison/heal/
inject_fault``, aggregate counters, ``pool``/``queue`` views) still
works — on a 1-chip mesh (plain CPU CI) behavior is identical to the
pre-mesh runtime.
"""

from __future__ import annotations

import asyncio
import heapq
import time

import numpy as np

from . import mesh
from ..trace import recorder as flight
from ..utils.log import global_logger

# service classes (the device-side analog of the mClock op classes)
K_CLIENT_EC = "client-ec"
K_RECOVERY_EC = "recovery-ec"
K_MAPPING = "mapping"
# background integrity/maintenance work (scrub digests, pool
# compression pacing): weighted below recovery so an always-on scrub
# or a compressed-pool burst can never starve the data-path classes
K_BACKGROUND = "background"


class DeviceBusy(Exception):
    """Admission rejected: the dispatch queue is at its bound.  The
    caller degrades (deadline-flush later, or host fallback) instead
    of stacking more device work."""


class DeviceLost(Exception):
    """A dispatch failed at the device layer (or a fault was
    injected): the chip flips to host fallback."""


class DispatchTicket:
    """One device dispatch's identity + timeline.

    Stamps: t_enqueue (admission requested) -> t_admit (queue granted)
    -> t_launch (dispatch handed to the device) -> t_done.  queue_wait
    and device_s are the two stages the exporter and the OpTracker
    attribute separately.  `t_enqueue` may be passed explicitly so the
    wait an op spent *before* the dispatch existed counts too: the
    stream stamps the earliest admitted op's arrival, the flush path
    its batch's first append — queue_wait is then arrival->grant, not
    merely device-queue wait.  `chip` names the mesh chip the dispatch
    ran on (the exporter's chip label).  `tenant` attributes the
    dispatch to the tenant whose ops it carried — the single tenant
    when every batched item agreed, the literal "mixed" when a flush
    batched several tenants' stripes, None for tenant-less work
    (recovery, scrub, mapping).  `stream` marks a slot dispatch of the
    continuous per-chip stream (False: a legacy/degradation flush)."""

    __slots__ = ("seq", "klass", "bucket", "nbytes", "chip",
                 "t_enqueue", "t_admit", "t_launch", "t_done", "ok",
                 "error", "tenant", "stream")

    def __init__(self, seq: int, klass: str, bucket: int, nbytes: int,
                 chip: int = 0, tenant: str | None = None,
                 t_enqueue: float | None = None,
                 stream: bool = False):
        self.seq = seq
        self.klass = klass
        self.bucket = bucket
        self.nbytes = nbytes
        self.chip = chip
        self.tenant = tenant
        self.stream = bool(stream)
        self.t_enqueue = (time.monotonic() if t_enqueue is None
                          else float(t_enqueue))
        self.t_admit = 0.0
        self.t_launch = 0.0
        self.t_done = 0.0
        self.ok = False
        self.error: str | None = None

    @property
    def queue_wait(self) -> float:
        return max(0.0, (self.t_admit or self.t_enqueue)
                   - self.t_enqueue)

    @property
    def device_s(self) -> float:
        """Host-blocked dispatch time, launch -> done: the upload, the
        kernel and the readback as the calling thread waited for them.
        Not device-busy time (the kernel is a small part of it); that
        comes from a profiler trace alone."""
        if not self.t_done or not self.t_launch:
            return 0.0
        return max(0.0, self.t_done - self.t_launch)

    def dump(self) -> dict:
        return {"seq": self.seq, "klass": self.klass,
                "bucket": self.bucket, "bytes": self.nbytes,
                "chip": self.chip, "tenant": self.tenant,
                "stream": self.stream,
                "queue_wait": self.queue_wait,
                "device_s": self.device_s, "ok": self.ok,
                "error": self.error}


class BufferPool:
    """Free-lists of bucket-sized staging arrays keyed (shape, dtype).

    The HBM-buffer-pool role scaled to this build's dispatch layer:
    flushes stage their padded batch into a leased array instead of
    allocating per flush, so steady state does zero per-flush
    allocation (tests pin `misses` flat while `hits` grows).  Leased
    arrays come back zeroed — bucket padding must be zero for GF
    bit-parity with the unpadded host encode."""

    def __init__(self, max_per_key: int = 4):
        self.max_per_key = max_per_key
        self._free: dict[tuple, list[np.ndarray]] = {}
        self.hits = 0
        self.misses = 0
        self.outstanding = 0

    def lease(self, shape: tuple, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        free = self._free.get(key)
        if free:
            arr = free.pop()
            arr[...] = 0
            self.hits += 1
        else:
            arr = np.zeros(shape, dtype=dtype)
            self.misses += 1
        self.outstanding += 1
        return arr

    def release(self, arr: np.ndarray) -> None:
        self.outstanding -= 1
        key = (arr.shape, arr.dtype.str)
        free = self._free.setdefault(key, [])
        if len(free) < self.max_per_key:
            free.append(arr)

    def clear(self) -> None:
        self._free.clear()


class DispatchQueue:
    """Bounded in-flight dispatches with weighted-fair admission.

    Start-time fair queueing over virtual time: each class keeps a
    finish tag advanced by cost/weight per grant, waiters are served
    in tag order — so under contention client-EC (weight 4) gets ~4x
    the grants of mapping (weight 1), mirroring how mClock shares OSD
    capacity.  `admit` parks the caller while the queue has room;
    once `max_queue` waiters are parked further admissions raise
    DeviceBusy — that is the backpressure edge the batcher and the
    mapper degrade on."""

    def __init__(self, weights: dict[str, float],
                 max_inflight: int = 2, max_queue: int = 64):
        self.weights = dict(weights)
        self.max_inflight = max(1, int(max_inflight))
        self.max_queue = max(0, int(max_queue))
        self.inflight = 0
        self._vt = 0.0                      # virtual clock
        self._finish: dict[str, float] = {}
        self._seq = 0
        # heap of (finish_tag, seq, klass, cost, future)
        self._waiters: list = []
        self.granted = {k: 0 for k in self.weights}
        self.rejected = 0

    @property
    def depth(self) -> int:
        return self.inflight + len(self._waiters)

    def _tag(self, klass: str, cost: float) -> float:
        w = self.weights.get(klass, 1.0)
        start = max(self._vt, self._finish.get(klass, 0.0))
        fin = start + cost / max(w, 1e-9)
        self._finish[klass] = fin
        return fin

    def _grant(self, klass: str) -> None:
        self.inflight += 1
        self.granted[klass] = self.granted.get(klass, 0) + 1

    def try_admit(self, klass: str, cost: float = 1.0) -> None:
        """Synchronous, non-blocking admission (the bulk mapper's
        path — it runs outside a coroutine).  Raises DeviceBusy when
        a grant would overtake parked waiters or exceed the bound."""
        if self.inflight >= self.max_inflight or self._waiters:
            self.rejected += 1
            raise DeviceBusy("device dispatch queue at depth %d"
                             % self.depth)
        self._vt = max(self._vt, self._finish.get(klass, 0.0))
        self._tag(klass, cost)
        self._grant(klass)

    async def admit(self, klass: str, cost: float = 1.0) -> None:
        if self.inflight < self.max_inflight and not self._waiters:
            self._tag(klass, cost)
            self._grant(klass)
            return
        if len(self._waiters) >= self.max_queue:
            self.rejected += 1
            raise DeviceBusy("device dispatch queue full (%d waiting)"
                             % len(self._waiters))
        fut = asyncio.get_event_loop().create_future()
        self._seq += 1
        heapq.heappush(self._waiters,
                       (self._tag(klass, cost), self._seq, klass,
                        cost, fut))
        await fut

    def release(self) -> None:
        self.inflight = max(0, self.inflight - 1)
        while self.inflight < self.max_inflight and self._waiters:
            tag, _seq, klass, _cost, fut = heapq.heappop(self._waiters)
            self._vt = max(self._vt, tag)
            if fut.cancelled():
                continue
            self._grant(klass)
            fut.set_result(None)


_MIN_BUCKET = 512          # words: floor so tiny flushes share one program
_TICKET_RING = 512
_HIST_BUCKETS = 32         # power-of-two microsecond histogram

# bucket-ladder cap: a ragged flush stages at most this many pow2
# segments (each an already-compiled bucket program); the tail-only
# rounding then bounds waste at ~n / 2^(cap-1) of the flush, while
# more segments would trade the padding win back for per-dispatch
# overhead
_RAGGED_MAX_SEGMENTS = 6

# words at/above which a flush shards across the mesh's available
# chips (the zero-collective stripe-axis split); conf
# device_shard_min_words overrides via configure()
_SHARD_MIN_WORDS = 1 << 19


class ChipRuntime:
    """One mesh chip's isolation domain: its own DispatchQueue,
    BufferPool, compile-cache accounting, ticket ring and
    fallback/poison state.  OSDs bind to a chip via
    ``DeviceRuntime.chip_for`` affinity; a poisoned chip degrades only
    its own OSDs to the host paths while the rest of the mesh keeps
    serving on-device."""

    def __init__(self, rt: "DeviceRuntime", index: int,
                 weights: dict[str, float], max_inflight: int,
                 max_queue: int):
        self.rt = rt
        self.index = int(index)
        self.queue = DispatchQueue(weights, max_inflight, max_queue)
        self.pool = BufferPool()
        # compile cache bookkeeping: program identity -> compiled once
        # (per chip: a real mesh compiles each program per chip)
        self.programs: set[tuple] = set()
        self.compile_count = 0
        self.bucket_hits = 0
        self.bucket_misses = 0
        # ragged staging accounting: payload vs bucket-padded words
        # per flush (the waste the bucket ladder exists to kill;
        # exported as device_bucket_waste_ratio per chip), plus the
        # counterfactual pad a whole-flush pow2 bucket would have
        # burned — the before/after the bench publishes
        self.staged_payload_words = 0
        self.staged_pad_words = 0
        self.staged_pow2_pad_words = 0
        # repair-traffic accounting (direction-3 codec plane): bytes
        # the recovery flows bound to this chip read from survivors
        # and pushed to rebuilt shards — the observable the
        # locality-aware codecs (LRC/SHEC/CLAY) exist to shrink
        self.repair_bytes_read = 0
        self.repair_bytes_moved = 0
        # compression-plane accounting: raw bytes whose match
        # planning dispatched on this chip vs the blob bytes emitted
        # from those plans (device/lzkernel + compress/tlz) — the
        # observable that says force-mode compression pools stopped
        # burning host CPU here
        self.compress_bytes_in = 0
        self.compress_bytes_out = 0
        # dedup-plane accounting: chunks and bytes whose content
        # fingerprints digested on this chip's CRC lanes — the
        # observable that says dedup fingerprinting stopped burning
        # host CPU here
        self.fingerprint_chunks = 0
        self.fingerprint_bytes = 0
        # dispatch telemetry
        self.tickets: list[DispatchTicket] = []     # bounded ring
        self.dispatch_buckets_us = [0] * _HIST_BUCKETS
        self.dispatches = 0
        self.dispatch_seconds = 0.0
        self.queue_wait_seconds = 0.0  # summed ticket queue waits
        self.host_fallbacks = 0        # flushes served by host codecs
        # device-loss state
        self.fallback = False
        self.fallback_reason: str | None = None
        self.fallback_count = 0
        self.heal_count = 0
        self._fault_budget = 0         # injected failures outstanding
        self._probe_task = None
        self._listeners: list = []     # on_state_change(fallback: bool)
        self._jdev = None              # lazy jax device handle
        self._jdev_resolved = False
        # continuous dispatch stream (device.stream): created lazily
        # on first stream-mode submit so flush-mode/loop-less callers
        # never pay for it
        self._stream = None

    @property
    def stream(self):
        """This chip's persistent dispatch stream (lazy)."""
        if self._stream is None:
            from .stream import DispatchStream
            self._stream = DispatchStream(self)
        return self._stream

    # -- placement ---------------------------------------------------------

    @property
    def jax_device(self):
        """The jax device backing this chip (lazy; None when logical
        chips share the process default device — placement is then a
        no-op, which is the cheap path on single-device CI)."""
        if not self._jdev_resolved:
            self._jdev_resolved = True
            devs = mesh.local_devices()
            if len(devs) > 1:
                self._jdev = devs[self.index % len(devs)]
        return self._jdev

    def place(self, arr):
        """Commit an array to this chip's device (computation follows
        data placement — the 2112.09017 dispatch discipline).  Returns
        the input unchanged when the mesh shares one physical
        device.  For callers that hand a jitted program jnp arrays
        they built themselves (digest, lz, chunker, balancer); a new
        caller uses `scope`, which also covers what the callee
        stages."""
        dev = self.jax_device
        if dev is None:
            return arr
        import jax
        return jax.device_put(arr, dev)

    def scope(self):
        """Context in which host arrays handed to a compiled program,
        and the program itself, land on this chip's device — for
        callees that stage their own inputs (FusedEncoder views bytes
        as uint32 on the host; DeviceMapper uploads its own tables),
        where `place` on the caller's side would be undone.  A no-op
        when the mesh shares one physical device."""
        import jax
        return jax.default_device(self.jax_device)

    # -- shape buckets / compile cache ------------------------------------

    def note_program(self, kind: str, key: tuple) -> bool:
        """Record a program dispatch; True when this (kind, key) had
        never compiled on THIS chip before.  The summed
        `compile_count` is the acceptance criterion's counter: a
        steady-state mixed workload must stay within a handful of
        distinct programs."""
        pk = (kind,) + tuple(key)
        if pk in self.programs:
            self.bucket_hits += 1
            return False
        self.programs.add(pk)
        self.compile_count += 1
        self.bucket_misses += 1
        return True

    def note_staging(self, payload_words: int,
                     padded_words: int) -> None:
        """Account one flush's staging: `payload_words` real columns
        staged into `padded_words` of bucket capacity.  The cumulative
        pad/(pad+payload) ratio is the padding-waste figure the
        exporter publishes and bench --device gates on; the pow2
        counterfactual records what rounding the whole flush to its
        pow2 ceiling (the pre-ragged behavior) would have padded."""
        self.staged_payload_words += max(0, int(payload_words))
        self.staged_pad_words += max(
            0, int(padded_words) - int(payload_words))
        self.staged_pow2_pad_words += max(
            0, DeviceRuntime.bucket_for(payload_words)
            - int(payload_words))

    def note_repair(self, bytes_read: int, bytes_moved: int) -> None:
        """Account one shard repair's traffic on this chip: survivor
        bytes sourced (`bytes_read` — what minimum_to_decode's
        minimal shard set actually fetched) and rebuilt bytes pushed
        (`bytes_moved`).  Exported as the chip-labeled
        device_repair_bytes_read/_moved series the repair-traffic
        bench leg gates on."""
        self.repair_bytes_read += max(0, int(bytes_read))
        self.repair_bytes_moved += max(0, int(bytes_moved))

    def note_compress(self, bytes_in: int, bytes_out: int) -> None:
        """Account one device-planned compression: raw bytes in,
        container bytes out.  Exported as the chip-labeled
        device_compress_bytes_in/_out series the compression bench
        leg and the thrasher's poison oracle read."""
        self.compress_bytes_in += max(0, int(bytes_in))
        self.compress_bytes_out += max(0, int(bytes_out))

    def note_fingerprint(self, chunks: int, nbytes: int) -> None:
        """Account one device-fingerprinted chunk batch on this chip.
        Exported as the chip-labeled device_fingerprint_chunks/_bytes
        series the dedup bench leg and `--dedup` gate read."""
        self.fingerprint_chunks += max(0, int(chunks))
        self.fingerprint_bytes += max(0, int(nbytes))

    # -- tickets -----------------------------------------------------------

    def open_ticket(self, klass: str, bucket: int, nbytes: int,
                    tenant: str | None = None,
                    t_enqueue: float | None = None,
                    stream: bool = False) -> DispatchTicket:
        return DispatchTicket(self.rt.next_seq(), klass, bucket,
                              nbytes, chip=self.index, tenant=tenant,
                              t_enqueue=t_enqueue, stream=stream)

    async def admit(self, ticket: DispatchTicket,
                    cost: float | None = None) -> None:
        await self.queue.admit(
            ticket.klass,
            cost if cost is not None
            else max(1.0, ticket.nbytes / 65536.0))
        ticket.t_admit = time.monotonic()

    def try_admit(self, ticket: DispatchTicket,
                  cost: float | None = None) -> None:
        self.queue.try_admit(
            ticket.klass,
            cost if cost is not None
            else max(1.0, ticket.nbytes / 65536.0))
        ticket.t_admit = time.monotonic()

    def launch(self, ticket: DispatchTicket) -> None:
        """Stamp launch; consumes one injected fault if armed (the
        deterministic chip-loss hook the thrasher uses)."""
        ticket.t_launch = time.monotonic()
        if self._fault_budget > 0:
            self._fault_budget -= 1
            raise DeviceLost("injected device fault (chip %d)"
                             % self.index)

    def finish(self, ticket: DispatchTicket, ok: bool = True,
               error: Exception | None = None) -> None:
        ticket.t_done = time.monotonic()
        ticket.ok = ok
        ticket.error = repr(error) if error is not None else None
        self.queue.release()
        self.tickets.append(ticket)
        if len(self.tickets) > _TICKET_RING:
            del self.tickets[:_TICKET_RING // 2]
        self.queue_wait_seconds += ticket.queue_wait
        if ok:
            self.dispatches += 1
            dt = ticket.device_s
            self.dispatch_seconds += dt
            us = max(1, int(dt * 1e6))
            i = min(_HIST_BUCKETS - 1, max(0, us.bit_length() - 1))
            self.dispatch_buckets_us[i] += 1
        # flight recorder: every completed ticket is a device-lane
        # span (the process ring the Perfetto export renders per chip)
        flight.note_ticket(ticket)

    # -- device-loss degradation ------------------------------------------

    @property
    def available(self) -> bool:
        return not self.fallback

    def add_listener(self, fn) -> None:
        """fn(fallback: bool) on every poison/heal transition of THIS
        chip (the OSD bound here uses it to beacon the state change
        immediately)."""
        self._listeners.append(fn)

    def _notify(self) -> None:
        for fn in list(self._listeners):
            try:
                fn(self.fallback)
            except Exception:
                pass        # observability must never sink the runtime

    def poison(self, reason) -> None:
        """Flip this chip to host fallback; a probe loop retries the
        device under ExpBackoff until it heals.  Other chips are
        untouched — their OSDs keep serving on-device."""
        if self.fallback:
            return
        self.fallback = True
        self.fallback_reason = repr(reason)
        self.fallback_count += 1
        # a deterministic failure (a kernel the chip's compiler
        # refuses, a program that does not fit HBM) looks like a
        # healthy cluster from outside: say why, once per transition
        global_logger().error(
            "device", "chip %d poisoned -> host fallback: %s"
            % (self.index, self.fallback_reason))
        self._notify()
        try:
            loop = asyncio.get_event_loop()
            if loop.is_running() and self._probe_task is None:
                self._probe_task = loop.create_task(self._probe_loop())
        except RuntimeError:
            pass            # no loop: heal() is manual (sync callers)

    def heal(self) -> None:
        if not self.fallback:
            return
        self.fallback = False
        self.fallback_reason = None
        self.heal_count += 1
        self._notify()

    def inject_fault(self, n: int = 1) -> None:
        """Arm n deterministic dispatch failures on this chip
        (thrasher hook); probes consume from the same budget, so the
        chip stays in fallback until the budget drains (or
        clear_faults())."""
        self._fault_budget += int(n)

    def clear_faults(self) -> None:
        self._fault_budget = 0

    def _run_probe(self) -> None:
        """One probe dispatch: trivially small device work on this
        chip; raises on failure.  Injected faults make probes fail
        too, so the fallback window is controllable in tests."""
        if self._fault_budget > 0:
            self._fault_budget -= 1
            raise DeviceLost("injected device fault (probe, chip %d)"
                             % self.index)
        import jax.numpy as jnp
        np.asarray(self.place(jnp.zeros((8,), jnp.uint8))
                   + jnp.uint8(1))

    async def _probe_loop(self) -> None:
        from ..utils.backoff import ExpBackoff
        bo = ExpBackoff(base=self.rt._probe_base,
                        cap=self.rt._probe_cap)
        try:
            while self.fallback:
                await bo.sleep()
                try:
                    self._run_probe()
                except Exception:
                    continue
                self.heal()
        finally:
            self._probe_task = None

    # -- telemetry ---------------------------------------------------------

    @property
    def bucket_hit_ratio(self) -> float:
        total = self.bucket_hits + self.bucket_misses
        return self.bucket_hits / total if total else 1.0

    @property
    def bucket_waste_ratio(self) -> float:
        """Fraction of staged bucket capacity that was padding (0.0
        with no flushes yet): the ragged batcher's observable win."""
        total = self.staged_payload_words + self.staged_pad_words
        return self.staged_pad_words / total if total else 0.0

    def utilization(self, window: float | None = None,
                    now: float | None = None) -> dict:
        """Windowed utilization integrals over the ticket ring — the
        per-chip busy/idle accounting arXiv:2112.09017 treats as the
        primary scaling signal:

        * ``busy_frac``  — seconds of ``DispatchTicket.device_s`` per
          wall second in the window: host-blocked dispatch time
          (upload + kernel + readback), not device-busy time (can
          exceed 1.0 while multiple dispatches are in flight);
        * ``queue_wait_frac`` — admission-wait seconds per wall
          second (the saturation leading indicator: latency is
          queueing, not compute);
        * ``idle_frac``  — max(0, 1 - busy_frac).

        Only the ticket overlap with the window counts (a dispatch
        straddling the window edge is clipped), so the figures are
        honest rates, not lifetime averages."""
        w = float(window if window is not None
                  else self.rt.util_window)
        t_now = time.monotonic() if now is None else now
        lo = t_now - w
        busy = qwait = 0.0
        for t in self.tickets:
            if not t.t_done or t.t_done <= lo:
                continue
            if t.ok:
                busy += min(t.device_s, t.t_done - lo)
            admit_end = t.t_admit or t.t_done
            if admit_end > lo:
                qwait += min(t.queue_wait, admit_end - lo)
        busy_frac = busy / w if w > 0 else 0.0
        qw_frac = qwait / w if w > 0 else 0.0
        return {"window_s": round(w, 3),
                "busy_frac": round(busy_frac, 4),
                "queue_wait_frac": round(qw_frac, 4),
                "idle_frac": round(max(0.0, 1.0 - busy_frac), 4)}

    def metrics(self) -> dict:
        util = self.utilization()
        # dispatch-stream telemetry (zeros/identity until the first
        # stream-mode submit creates the stream — metrics() must
        # never instantiate it)
        s = self._stream
        return {
            "device_queue_depth": self.queue.depth,
            "device_inflight": self.queue.inflight,
            "device_bucket_hit_ratio": round(self.bucket_hit_ratio, 4),
            "device_bucket_waste_ratio": round(self.bucket_waste_ratio,
                                               4),
            "device_compile_count": self.compile_count,
            "device_dispatches": self.dispatches,
            "device_host_fallbacks": self.host_fallbacks,
            "device_pool_hits": self.pool.hits,
            "device_pool_misses": self.pool.misses,
            "device_fallback": int(self.fallback),
            "device_fallback_count": self.fallback_count,
            "device_heal_count": self.heal_count,
            "device_queue_rejected": self.queue.rejected,
            # windowed utilization integrals (chip-labeled gauges:
            # saturation visible per chip, cluster-wide via the mgr)
            "device_util_busy": util["busy_frac"],
            "device_util_queue_wait": util["queue_wait_frac"],
            "device_util_idle": util["idle_frac"],
            # continuous dispatch stream: payload fraction of slot
            # capacity, mean arrival->slot-grant latency, ops retired
            # independently, and ops still pending admission
            "device_slot_occupancy": round(
                s.slot_occupancy if s is not None else 1.0, 4),
            "device_admission_wait": round(
                s.admission_wait_mean if s is not None else 0.0, 6),
            "device_stream_retires": s.retired if s is not None else 0,
            "device_stream_pending": s.pending if s is not None else 0,
            # repair-traffic plane: survivor bytes read / rebuilt
            # bytes pushed by the recovery flows bound to this chip
            "device_repair_bytes_read": self.repair_bytes_read,
            "device_repair_bytes_moved": self.repair_bytes_moved,
            # compression plane: raw bytes match-planned on this chip
            # vs emitted container bytes (ratio = in/out)
            "device_compress_bytes_in": self.compress_bytes_in,
            "device_compress_bytes_out": self.compress_bytes_out,
            # dedup plane: chunks / bytes content-fingerprinted on
            # this chip's CRC lanes
            "device_fingerprint_chunks": self.fingerprint_chunks,
            "device_fingerprint_bytes": self.fingerprint_bytes,
        }


class DeviceRuntime:
    """One per process (per event loop, with a loop-less fallback for
    synchronous callers such as the bulk mapper warming outside
    asyncio).  Both hot paths route dispatches through here — each
    onto a mesh chip (``ChipRuntime``): OSDs via ``chip_for``
    affinity, chip-less callers via ``route(None)`` (first available
    chip)."""

    _global: "DeviceRuntime | None" = None

    def __init__(self, weights: dict[str, float] | None = None,
                 max_inflight: int = 2, max_queue: int = 64,
                 chips: int | None = None):
        if weights is None:
            from ..osd.scheduler import DEVICE_DISPATCH_WEIGHTS
            weights = DEVICE_DISPATCH_WEIGHTS
        n = int(chips) if chips else mesh.chip_count()
        self._seq = 0
        self._probe_base = 0.05
        self._probe_cap = 1.0
        self.shard_min_words = _SHARD_MIN_WORDS
        self.util_window = 10.0     # utilization-integral window (s)
        # continuous dispatch stream (device.stream): geometry
        self.stream_interval = 100e-6   # admission-loop idle tick (s)
        self.stream_slot_words = 1 << 19  # slot-group geometry cap
        self.stream_max_slots = 4         # in-flight slots per chip
        self.stream_weights = dict(weights)
        # per-tenant dmClock rows the stream orders admission by
        # (osd_mclock_tenant_qos; weight column only — reservation
        # and limit stay host-side in the op scheduler)
        self.tenant_qos: dict[str, tuple] = {}
        self.chips: list[ChipRuntime] = [
            ChipRuntime(self, i, weights, max_inflight, max_queue)
            for i in range(max(1, n))]

    # -- lifecycle ---------------------------------------------------------

    @classmethod
    def get(cls) -> "DeviceRuntime":
        """Loop-local instance (lifetime tracks the loop, same
        reasoning as DeviceBatcher.get); synchronous callers with no
        loop share a process-global instance."""
        try:
            loop = asyncio.get_event_loop()
        except RuntimeError:
            loop = None
        if loop is None:
            if cls._global is None:
                cls._global = cls()
            return cls._global
        inst = getattr(loop, "_ceph_tpu_device_runtime", None)
        if inst is None:
            inst = cls()
            loop._ceph_tpu_device_runtime = inst
        return inst

    @classmethod
    def reset(cls, chips: int | None = None) -> "DeviceRuntime":
        """Fresh instance bound to the current loop (tests); `chips`
        forces the logical mesh size regardless of environment."""
        inst = cls(chips=chips)
        try:
            loop = asyncio.get_event_loop()
            loop._ceph_tpu_device_runtime = inst
        except RuntimeError:
            cls._global = inst
        return inst

    def configure(self, conf) -> None:
        """Adopt daemon config (OSD boot): per-chip queue bounds +
        probe ramp + mesh shard threshold."""
        try:
            max_inflight = max(1, int(conf["device_max_inflight"]))
            max_queue = int(conf["device_queue_len"])
            for c in self.chips:
                c.queue.max_inflight = max_inflight
                c.queue.max_queue = max_queue
            self.probe_interval = float(conf["device_probe_interval"])
            self._probe_base = self.probe_interval / 4.0
            self._probe_cap = self.probe_interval
        except (KeyError, TypeError):
            pass
        try:
            self.shard_min_words = max(
                _MIN_BUCKET, int(conf["device_shard_min_words"]))
        except (KeyError, TypeError, ValueError):
            pass
        try:
            self.util_window = max(
                0.1, float(conf["device_util_window"]))
        except (KeyError, TypeError, ValueError):
            pass
        # dispatch-stream geometry + per-tenant admission rows
        try:
            self.stream_interval = max(
                1e-6, int(conf["device_stream_interval_us"]) / 1e6)
            self.stream_slot_words = max(
                _MIN_BUCKET, int(conf["device_stream_slot_words"]))
            self.stream_max_slots = max(
                1, int(conf["device_stream_max_slots"]))
        except (KeyError, TypeError, ValueError):
            pass
        try:
            from ..osd.scheduler import parse_tenant_qos
            self.tenant_qos = parse_tenant_qos(
                str(conf.get("osd_mclock_tenant_qos", "") or ""))
        except Exception:
            pass

    # -- mesh placement ----------------------------------------------------

    @property
    def n_chips(self) -> int:
        return len(self.chips)

    def chip(self, index: int | None = None) -> ChipRuntime:
        """Chip by index (modulo the mesh), or the default chip."""
        if index is None:
            index = 0
        return self.chips[int(index) % len(self.chips)]

    def chip_for(self, osd_id: int) -> ChipRuntime:
        """The chip OSD `osd_id` binds to: deterministic modulo
        affinity, so co-located daemons land on distinct chips and a
        chip loss degrades a knowable OSD subset."""
        return self.chips[mesh.affinity(osd_id, len(self.chips))]

    def route(self, chip: int | None) -> ChipRuntime | None:
        """Resolve a dispatch target.  An explicit chip index is
        honored even while poisoned (the caller's affinity chip IS
        its isolation domain — it must degrade to host, not borrow a
        neighbor and erode the isolation story).  None picks the
        first available chip (chip-less callers: client-side codecs,
        warmup, bulk mapping outside a daemon) and returns None only
        when the whole mesh is down."""
        if chip is not None:
            return self.chips[int(chip) % len(self.chips)]
        for c in self.chips:
            if c.available:
                return c
        return None

    def chip_available(self, chip: int | None = None) -> bool:
        """Availability gate: explicit chip -> that chip's state;
        None -> any chip available."""
        if chip is not None:
            return self.chips[int(chip) % len(self.chips)].available
        return any(c.available for c in self.chips)

    def available_chips(self) -> list[ChipRuntime]:
        return [c for c in self.chips if c.available]

    def shard_plan(self, chip: ChipRuntime,
                   n_words: int) -> list[tuple[ChipRuntime, int, int]]:
        """Column ranges for one flush: [(chip, lo, hi)].  A flush at
        or above `shard_min_words` splits contiguously across the
        owning chip plus every other available chip — the stripe-axis
        split MULTICHIP_SCALING.json proves collective-free — and
        reassembles bit-identically (GF parity is column-independent).
        Below the threshold (or on a 1-chip mesh) the plan is the
        single owning chip."""
        n_words = int(n_words)
        targets = [chip] + [c for c in self.chips
                            if c.available and c is not chip]
        if n_words < self.shard_min_words or len(targets) == 1:
            return [(chip, 0, n_words)]
        per = -(-n_words // len(targets))       # ceil
        plan = []
        lo = 0
        for c in targets:
            hi = min(n_words, lo + per)
            if hi <= lo:
                break
            plan.append((c, lo, hi))
            lo = hi
        return plan

    def next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def note_program(self, kind: str, key: tuple) -> bool:
        """Chip-less compile accounting (the crush device mapper's
        deep hook has no chip context): attributed to the first
        available chip."""
        target = self.route(None) or self.chips[0]
        return target.note_program(kind, key)

    # -- shape buckets / warmup -------------------------------------------

    @staticmethod
    def bucket_for(n_words: int) -> int:
        """Pad target: next power of two >= n, floored at _MIN_BUCKET
        so micro-flushes share one program."""
        n = max(int(n_words), _MIN_BUCKET)
        return 1 << (n - 1).bit_length()

    @classmethod
    def ragged_plan(cls, n_words: int,
                    max_segments: int | None = None
                    ) -> list[tuple[int, int]]:
        """Bucket ladder for one ragged flush: [(lo, segment_bucket)]
        covering `n_words` columns with power-of-two segments (each an
        already-compiled bucket program, so the compile cache stays
        bounded).  Only the ladder's TAIL rounds up — greedy
        largest-pow2-first, final remainder to its own bucket — so a
        mixed-size flush wastes at most one small bucket instead of
        padding the whole total to the next power of two (the Ragged
        Paged Attention recipe, arXiv:2604.15464: one program family
        serving variable-length batches from packed buffers).  When
        the ladder would pad as much as the single pow2 bucket it
        degenerates to that bucket (one dispatch beats several for
        equal padding)."""
        n = max(int(n_words), 1)
        single = cls.bucket_for(n)
        cap = max_segments or _RAGGED_MAX_SEGMENTS
        plan: list[tuple[int, int]] = []
        lo = 0
        remaining = n
        while len(plan) < cap - 1 and remaining > _MIN_BUCKET:
            p = 1 << (remaining.bit_length() - 1)
            plan.append((lo, p))
            lo += p
            remaining -= p
        if remaining > 0:
            b = cls.bucket_for(remaining)
            plan.append((lo, b))
            lo += b
        if lo >= single:
            return [(0, single)]
        return plan

    async def warmup_ec(self, matrix, w: int,
                        buckets: tuple = (1024, 4096, 16384),
                        chip: int | None = None) -> None:
        """Pre-compile the common EC buckets for one coding matrix at
        boot — on the caller's affinity chip (OSD boot passes its
        own) — so the first client flushes hit the cache instead of
        paying a compile inside the write path."""
        from ..ec.batcher import DeviceBatcher
        target = self.route(chip)
        if target is None:
            return
        matrix_key = tuple(tuple(r) for r in matrix)
        k = len(matrix[0])
        dtype = {8: np.uint8, 16: np.uint16, 32: np.uint32}[int(w)]
        for b in buckets:
            if not target.available:
                return
            key = ("ec", matrix_key, int(w), int(b))
            if key in target.programs:
                continue
            try:
                enc = DeviceBatcher._encoder(matrix_key, int(w))
                buf = target.pool.lease((k, int(b)), dtype)
                try:
                    with target.scope():
                        np.asarray(enc(buf))
                finally:
                    target.pool.release(buf)
                target.note_program("ec",
                                    (matrix_key, int(w), int(b)))
            except Exception as e:      # warmup must never wedge boot
                target.poison(e)
                return
            await asyncio.sleep(0)      # yield between compiles

    # -- aggregate views (single-chip back-compat + telemetry) ------------

    def _sum(self, attr: str) -> int:
        return sum(getattr(c, attr) for c in self.chips)

    @property
    def compile_count(self) -> int:
        return self._sum("compile_count")

    @property
    def bucket_hits(self) -> int:
        return self._sum("bucket_hits")

    @property
    def bucket_misses(self) -> int:
        return self._sum("bucket_misses")

    @property
    def dispatches(self) -> int:
        return self._sum("dispatches")

    @property
    def dispatch_seconds(self) -> float:
        return sum(c.dispatch_seconds for c in self.chips)

    @property
    def host_fallbacks(self) -> int:
        return self._sum("host_fallbacks")

    @host_fallbacks.setter
    def host_fallbacks(self, v: int) -> None:
        # legacy `rt.host_fallbacks += 1` path: the default chip
        # absorbs the delta (mesh-aware callers count on their chip)
        others = sum(c.host_fallbacks for c in self.chips[1:])
        self.chips[0].host_fallbacks = max(0, int(v) - others)

    @property
    def fallback_count(self) -> int:
        return self._sum("fallback_count")

    @property
    def heal_count(self) -> int:
        return self._sum("heal_count")

    @property
    def programs(self) -> set:
        out: set = set()
        for c in self.chips:
            out |= c.programs
        return out

    @property
    def tickets(self) -> list[DispatchTicket]:
        out: list[DispatchTicket] = []
        for c in self.chips:
            out.extend(c.tickets)
        out.sort(key=lambda t: t.seq)
        return out

    @property
    def pool(self) -> BufferPool:
        """Default chip's staging pool (single-chip back-compat)."""
        return self.chips[0].pool

    @property
    def queue(self) -> DispatchQueue:
        """Default chip's dispatch queue (single-chip back-compat)."""
        return self.chips[0].queue

    @property
    def bucket_hit_ratio(self) -> float:
        total = self.bucket_hits + self.bucket_misses
        return self.bucket_hits / total if total else 1.0

    @property
    def bucket_waste_ratio(self) -> float:
        """Mesh-aggregate staging waste: padded words that carried no
        payload over total staged capacity."""
        pay = self._sum("staged_payload_words")
        pad = self._sum("staged_pad_words")
        return pad / (pay + pad) if (pay + pad) else 0.0

    @property
    def pow2_waste_ratio(self) -> float:
        """What the same flushes would have wasted under whole-flush
        pow2 bucketing (the counterfactual the ragged figure is
        gated against)."""
        pay = self._sum("staged_payload_words")
        pad = self._sum("staged_pow2_pad_words")
        return pad / (pay + pad) if (pay + pad) else 0.0

    @property
    def fallback(self) -> bool:
        """Whole-mesh loss: every chip poisoned.  Per-chip state is
        `chips[i].fallback` (what OSD beacons carry)."""
        return all(c.fallback for c in self.chips)

    @property
    def fallback_reason(self) -> str | None:
        for c in self.chips:
            if c.fallback_reason:
                return c.fallback_reason
        return None

    @property
    def available(self) -> bool:
        return any(c.available for c in self.chips)

    def add_listener(self, fn) -> None:
        """Mesh-wide listener (back-compat): fires on every chip's
        transition.  Per-OSD daemons register on their affinity chip
        instead."""
        for c in self.chips:
            c.add_listener(fn)

    def poison(self, reason) -> None:
        """Whole-mesh poison (back-compat / catastrophic loss): every
        chip flips to host fallback."""
        for c in self.chips:
            c.poison(reason)

    def heal(self) -> None:
        for c in self.chips:
            c.heal()

    def inject_fault(self, n: int = 1) -> None:
        """Arm n failures on EVERY chip (whole-device loss shape);
        chip-scoped injection is `chips[i].inject_fault`."""
        for c in self.chips:
            c.inject_fault(n)

    def clear_faults(self) -> None:
        for c in self.chips:
            c.clear_faults()

    # -- telemetry ---------------------------------------------------------

    def dispatch_pctls(self) -> dict:
        """p50/p99 (ms) over every chip's ticket ring."""
        samples = sorted(t.device_s for c in self.chips
                         for t in c.tickets if t.ok)
        if not samples:
            return {"n": 0}
        n = len(samples)

        def at(p):
            return round(samples[min(n - 1, int(p / 100.0 * n))] * 1e3,
                         4)

        return {"n": n, "p50": at(50), "p99": at(99)}

    def metrics(self) -> dict:
        """Mesh-aggregate metric map (the pre-mesh names; per-chip
        series come from prom_lines' chip label)."""
        return {
            "device_chips": len(self.chips),
            "device_queue_depth": sum(c.queue.depth
                                      for c in self.chips),
            "device_inflight": sum(c.queue.inflight
                                   for c in self.chips),
            "device_bucket_hit_ratio": round(self.bucket_hit_ratio, 4),
            "device_bucket_waste_ratio": round(self.bucket_waste_ratio,
                                               4),
            "device_compile_count": self.compile_count,
            "device_dispatches": self.dispatches,
            "device_host_fallbacks": self.host_fallbacks,
            "device_pool_hits": self._sum_pool("hits"),
            "device_pool_misses": self._sum_pool("misses"),
            "device_fallback": int(self.fallback),
            "device_fallback_count": self.fallback_count,
            "device_heal_count": self.heal_count,
            "device_queue_rejected": sum(c.queue.rejected
                                         for c in self.chips),
            "device_fallback_chips": sum(1 for c in self.chips
                                         if c.fallback),
        }

    def _sum_pool(self, attr: str) -> int:
        return sum(getattr(c.pool, attr) for c in self.chips)

    def prom_lines(self, prefix: str = "ceph_tpu") -> list[str]:
        """Prometheus exposition lines: every device series carries a
        ``chip`` label (one series per mesh chip), plus the unlabeled
        mesh-size gauge.  TYPE is emitted once per family across
        chips (the exposition rule utils.exporter lints)."""
        from ..utils.exporter import hist_lines
        lines = ["# HELP %s_device_chips chips in the device mesh"
                 % prefix,
                 "# TYPE %s_device_chips gauge" % prefix,
                 "%s_device_chips %d" % (prefix, len(self.chips))]
        typed: set[str] = set()
        hist_typed: set[str] = set()
        for c in self.chips:
            label = 'chip="%d"' % c.index
            for name, val in sorted(c.metrics().items()):
                base = "%s_%s" % (prefix, name)
                if base not in typed:
                    typed.add(base)
                    lines.append("# HELP %s per-chip %s" % (base, name))
                    lines.append("# TYPE %s gauge" % base)
                lines.append("%s{%s} %g" % (base, label, float(val)))
            lines.extend(hist_lines(
                "%s_device_dispatch_seconds" % prefix,
                c.dispatch_buckets_us, labels=label,
                typed=hist_typed,
                desc="per-chip dispatch wall time "
                     "(us pow2 buckets)"))
        return lines
