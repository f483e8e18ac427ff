"""Persistent per-chip dispatch streams: continuous EC admission.

An accumulate-and-flush batcher makes a small urgent op wait for
whichever flush it rides: the deadline window, the co-batched bulk,
and the single all-or-nothing retire.  The utilization integrals
(`queue_wait_frac`) measure exactly that wait; the stream has none of
it, following continuous batching from LLM serving — the Ragged Paged
Attention kernel (arXiv:2604.15464) pages heterogeneous work through
one compiled program family instead of re-bucketing per flush, and
the GF(2^w) inner loops tolerate the fixed-geometry restructuring
(the XOR-scheduling results of arXiv:2108.02692).

One ``DispatchStream`` per ``ChipRuntime``:

* **continuous admission** — `encode` lands an op (one encode/delta/
  decode matmul request) in the stream with a weighted-fair virtual
  finish tag: class shares mirror ``osd.scheduler
  DEVICE_DISPATCH_WEIGHTS`` and tenant-stamped client ops order by
  their dmClock weight row (``osd_mclock_tenant_qos`` — reservation
  and limit stay host-side in the op scheduler; the device honors the
  proportional column).  The admission loop wakes on every arrival
  and slot completion (and at most ``device_stream_interval_us``
  apart) and packs whatever is resident into **slots**;
* **fixed-geometry slots** — a slot group is the tag-contiguous run
  of pending ops sharing one program family (matrix, w, class),
  capped at ``device_stream_slot_words``; its words stage across a
  pow2 bucket ladder (``DeviceRuntime.ragged_plan``), so slot
  programs are one already-compiled bucket family and the
  <=8-program budget holds.  Oversized groups mesh-shard across the
  available chips (ec.batcher);
* **independent retire** — each slot dispatches as its own task and
  retires ITS ops' futures the moment it completes: an urgent client
  op never waits on a co-batched recovery stripe's flush, and a
  recovery slot in flight never blocks the next client slot's
  admission;
* **degradation** — a poisoned chip or failed dispatch host-encodes
  the slot's ops (bit-parity with the host codecs by construction:
  ``ec.batcher.host_encode``), so every submitted future retires
  exactly once, mid-stream chip loss included.

Every slot carries a ``DispatchTicket`` stamped with the earliest
admitted op's arrival (queue_wait = arrival -> grant, the honest
figure) and ``stream=True`` (other planes' tickets are not stream
tickets), which the flight recorder renders on the Perfetto device
lanes.
"""

from __future__ import annotations

import asyncio
import heapq
import time

from ..trace.span import span


class StreamOp:
    """One admitted matmul request: [k, n] words awaiting parity."""

    __slots__ = ("matrix_key", "w", "klass", "tenant", "arr", "n",
                 "fut", "on_ticket", "t_arrive")

    def __init__(self, matrix_key, w, klass, tenant, arr, fut,
                 on_ticket):
        self.matrix_key = matrix_key
        self.w = int(w)
        self.klass = klass
        self.tenant = tenant
        self.arr = arr
        self.n = int(arr.shape[1])
        self.fut = fut
        self.on_ticket = on_ticket
        self.t_arrive = time.monotonic()

    @property
    def group_key(self):
        return (self.matrix_key, self.w, self.klass)


class DispatchStream:
    """The persistent admission loop of one mesh chip."""

    def __init__(self, chip):
        self.chip = chip
        self.rt = chip.rt
        self._heap: list = []           # (finish_tag, seq, op)
        self._seq = 0
        self._vt = 0.0                  # admission virtual clock
        self._finish: dict = {}         # book key -> finish tag
        self._wake = asyncio.Event()
        self._task = None
        self._slots_inflight = 0
        # telemetry (ChipRuntime.metrics: device_slot_occupancy,
        # device_admission_wait, device_stream_retires,
        # device_stream_pending)
        self.admitted = 0
        self.retired = 0
        self.slot_dispatches = 0
        self.slot_payload_words = 0
        self.slot_capacity_words = 0
        self.admission_wait_sum = 0.0
        self.admission_waits = 0

    # -- telemetry ---------------------------------------------------------

    @property
    def pending(self) -> int:
        return len(self._heap)

    @property
    def slot_occupancy(self) -> float:
        """Payload fraction of dispatched slot capacity (1.0 before
        the first slot: no capacity has been wasted yet)."""
        if not self.slot_capacity_words:
            return 1.0
        return self.slot_payload_words / self.slot_capacity_words

    @property
    def admission_wait_mean(self) -> float:
        if not self.admission_waits:
            return 0.0
        return self.admission_wait_sum / self.admission_waits

    # -- admission ---------------------------------------------------------

    def _tag(self, op: StreamOp) -> float:
        """Weighted-fair virtual finish tag: start-time fair queueing
        over (class, tenant) books with the mClock-mirrored class
        shares x the tenant's dmClock weight row."""
        from ..osd.scheduler import device_admission_weight
        key = ((op.klass, op.tenant)
               if op.tenant is not None and op.klass == "client-ec"
               else op.klass)
        w = device_admission_weight(op.klass, op.tenant,
                                    self.rt.tenant_qos)
        cost = 1.0 + op.n / 65536.0
        start = max(self._vt, self._finish.get(key, 0.0))
        fin = start + cost / max(w, 1e-9)
        self._finish[key] = fin
        return fin

    async def encode(self, matrix, w: int, data, klass: str,
                     on_ticket=None, tenant: str | None = None):
        """What DeviceBatcher.encode enqueues onto: admit the op and
        await its independently-retired parity slice."""
        matrix_key = tuple(tuple(r) for r in matrix)
        loop = asyncio.get_event_loop()
        fut = loop.create_future()
        op = StreamOp(matrix_key, w, klass, tenant, data, fut,
                      on_ticket)
        self._seq += 1
        heapq.heappush(self._heap, (self._tag(op), self._seq, op))
        self.admitted += 1
        self._wake.set()
        if self._task is None or self._task.done():
            self._task = loop.create_task(self._run())
        return await fut

    # -- the admission loop ------------------------------------------------

    async def _wait(self) -> None:
        self._wake.clear()
        try:
            await asyncio.wait_for(self._wake.wait(),
                                   self.rt.stream_interval)
        except asyncio.TimeoutError:
            pass

    async def _run(self) -> None:
        """Pack-and-dispatch until drained: each iteration admits the
        tag-ordered resident ops into slots and hands each slot to its
        own retire task.  Exits when idle (respawned by the next
        submit), so no task outlives the work."""
        try:
            while True:
                if not self._heap:
                    if self._slots_inflight == 0:
                        return
                    await self._wait()
                    continue
                if (self.chip.available and self._slots_inflight
                        >= self.rt.stream_max_slots):
                    # keep ops pending in the stream rather than deep
                    # in the device queue: a later-arriving urgent
                    # class can still overtake here
                    await self._wait()
                    continue
                group = self._take_group()
                self._slots_inflight += 1
                asyncio.get_event_loop().create_task(
                    self._slot_task(group))
                # yield one beat so concurrent arrivals land before
                # the next packing decision
                await asyncio.sleep(0)
        except asyncio.CancelledError:
            return              # loop teardown
        finally:
            self._task = None

    def _take_group(self) -> list:
        """The tag-contiguous run of pending ops sharing the head
        op's program family, capped at the slot-geometry words."""
        tag, _seq, op = heapq.heappop(self._heap)
        self._vt = max(self._vt, tag)
        group = [op]
        total = op.n
        cap = self.rt.stream_slot_words
        gkey = op.group_key
        while self._heap:
            t2, _s2, op2 = self._heap[0]
            if op2.group_key != gkey or total + op2.n > cap:
                break
            heapq.heappop(self._heap)
            self._vt = max(self._vt, t2)
            group.append(op2)
            total += op2.n
        return group

    async def _slot_task(self, group: list) -> None:
        """Dispatch one slot and retire its ops — independent of any
        other slot in flight.  Device loss/DeviceBusy degrade to the
        host codec inside the batcher's shared dispatch path; only a
        host-codec failure (a real codec error) reaches the futures
        as an exception."""
        from ..ec.batcher import DeviceBatcher, tenant_label
        op0 = group[0]
        n = sum(op.n for op in group)
        try:
            out, ticket = await DeviceBatcher.get().stream_dispatch(
                self.chip, op0.matrix_key, op0.w, op0.klass,
                [op.arr for op in group], n,
                tenant=tenant_label(op.tenant for op in group),
                t_enqueue=min(op.t_arrive for op in group))
        except Exception as e:
            for op in group:
                if not op.fut.cancelled():
                    op.fut.set_exception(
                        IOError("EC encode failed: %r" % e))
            return
        finally:
            self._slots_inflight -= 1
            self._wake.set()
        with span("ec.deliver", items=len(group)):
            now = time.monotonic()
            granted = (ticket.t_admit if ticket is not None
                       and ticket.t_admit else now)
            self.slot_dispatches += 1
            self.slot_payload_words += n
            self.slot_capacity_words += (ticket.bucket
                                         if ticket is not None else n)
            off = 0
            for op in group:
                if not op.fut.cancelled():
                    op.fut.set_result(out[:, off:off + op.n])
                off += op.n
                self.retired += 1
                self.admission_waits += 1
                self.admission_wait_sum += max(0.0,
                                               granted - op.t_arrive)
                if op.on_ticket is not None and ticket is not None:
                    try:
                        op.on_ticket(ticket)
                    except Exception:
                        pass    # attribution must never sink the slot
