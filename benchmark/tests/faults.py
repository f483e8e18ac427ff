"""Faults planted under the timed path, and the controls: each must make a
run come out `correct: false`.  Used by test_rehearsal.py at a tiny size on
the CPU and by control.py at the cell's own size on the chip.

This system is exact integer arithmetic and states no precision, so a
cell's control breaks one guarantee its configuration states:

  rados cells   parity_zeroed: parity is never computed (the cheapest
                "speed-up" of an EC write), so an acknowledged write no
                longer reads back with one data shard's OSD lost.
  crush cells   stale_mapping: every remap hands out the previous epoch's
                table (a stale answer where it was exact).

The others are the faults a cell can have: an answer altered where it is
produced (altered_read, altered_rows), half of the work left out
(half_dropped), the device path left for the host's (host_ec).
"""

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def parity_zeroed():
    """Encodes return zero parity.  Only a call with the coding matrix
    itself is altered, which is the first the batcher sees (set-up encodes
    one stripe before it warms the reconstructions)."""
    from ceph_tpu.ec.batcher import DeviceBatcher
    real = DeviceBatcher.encode
    coding = {}

    async def encode(self, matrix, w, data, *a, **kw):
        out = await real(self, matrix, w, data, *a, **kw)
        key = tuple(tuple(r) for r in matrix)
        coding.setdefault("first", key)
        return np.zeros_like(out) if key == coding["first"] else out

    return _patched(DeviceBatcher, "encode", encode)


def altered_read():
    """One byte of every fifth object read comes back flipped."""
    from ceph_tpu.client.rados import IoCtx
    real, n = IoCtx.read, [0]

    async def read(self, oid, *a, **kw):
        got = await real(self, oid, *a, **kw)
        n[0] += 1
        if n[0] % 5 == 0 and got:
            got = got[:-1] + bytes([got[-1] ^ 1])
        return got

    return _patched(IoCtx, "read", read)


def half_dropped():
    """Every second write is acknowledged without being sent."""
    from ceph_tpu.client.rados import IoCtx
    real, n = IoCtx.write_full, [0]

    async def write_full(self, oid, data):
        n[0] += 1
        if n[0] % 2:
            await real(self, oid, data)

    return _patched(IoCtx, "write_full", write_full)


def host_ec():
    """The EC path runs on the host codec: no device dispatch."""
    from ceph_tpu.ec import batcher
    return _patched(batcher, "device_offload_enabled", lambda: False)


def _mapping(alter):
    from ceph_tpu.parallel import mapping
    real = mapping.OSDMapMapping

    class Faulty(real):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            alter(self)

    return _patched(mapping, "OSDMapMapping", Faulty)


def stale_mapping():
    """Every remap after the first returns the table before it."""
    last = {}

    def alter(mp):
        for pid, pm in mp.pools.items():
            fresh = (pm.up, pm.up_primary, pm.acting, pm.acting_primary)
            if pid in last and last[pid][0].shape == pm.up.shape:
                (pm.up, pm.up_primary, pm.acting,
                 pm.acting_primary) = last[pid]
            last[pid] = fresh

    return _mapping(alter)


def altered_rows():
    """Every seventh PG's first two OSDs change places in up and acting."""

    def alter(mp):
        for pm in mp.pools.values():
            for arr in (pm.up, pm.acting):
                arr[::7, :2] = arr[::7, 1::-1].copy()

    return _mapping(alter)


FAULTS = {f.__name__: f for f in (parity_zeroed, altered_read, half_dropped,
                                  host_ec, stale_mapping, altered_rows)}
