"""Faults planted under the overwrite cell's timed path (beside faults.py
and faults_read.py): each must make a run come out `correct: false`.  Used
by test_rehearsal_rbd.py at a tiny size on the CPU and by control_rbd.py at
the cell's own size on the chip.

  parity_delta_dropped   the control: the parity delta of an overwrite is
                         never computed (the cheapest "speed-up" of a
                         partial write), so the data shards are written and
                         the parities stay as they were.  Every healthy
                         read still returns the bytes written: only the
                         shards in the stores and a read with a data shard
                         lost can tell.
  delta_misplaced        a parity shard's ranged write lands one word off:
                         the bytes differ from the encode of the data
                         shards and from the crc the primary stored.
  fallback_forced        no partial write takes the parity-delta path (the
                         codec shows no matrix): every one reads and
                         re-encodes its whole object; the bytes stay right.
"""

from .faults import _patched


def parity_delta_dropped():
    """Every parity delta comes back as zeros of the right length."""
    from ceph_tpu.ec.base import ErasureCode
    real = ErasureCode.delta_async

    async def delta_async(self, deltas, *a, **kw):
        out = await real(self, deltas, *a, **kw)
        return {i: bytes(len(d)) for i, d in out.items()}

    return _patched(ErasureCode, "delta_async", delta_async)


def delta_misplaced():
    """A parity position's ranged sub-write (one without a truncate: a
    whole shard's write starts with one) is applied one word later."""
    from ceph_tpu.osd.ecbackend import ECPGBackend
    from ceph_tpu.store.objectstore import (OP_TRUNCATE, OP_WRITE,
                                            Transaction)
    from ceph_tpu.utils import denc
    real = ECPGBackend.handle_sub_write

    def handle_sub_write(self, conn, msg):
        codec = self.codec(self.osd.osdmap.pools[msg.pool])
        t = Transaction.from_wire(denc.decode(msg.txn))
        if msg.shard >= codec.get_data_chunk_count() and not any(
                op[0] == OP_TRUNCATE for op in t.ops):
            t.ops = [op[:3] + (op[3] + 1,) + op[4:] if op[0] == OP_WRITE
                     else op for op in t.ops]
            msg.txn = denc.encode(t.to_wire())
        return real(self, conn, msg)

    return _patched(ECPGBackend, "handle_sub_write", handle_sub_write)


def fallback_forced():
    """The OSDs' codec shows `_try_delta_write` no matrix; everything
    else of it is the codec's own."""
    from ceph_tpu.osd.ecbackend import ECPGBackend
    real = ECPGBackend.codec

    class NoMatrix:
        matrix = None

        def __init__(self, codec):
            self._codec = codec

        def __getattr__(self, name):
            return getattr(self._codec, name)

    def codec(self, pool):
        return NoMatrix(real(self, pool))

    return _patched(ECPGBackend, "codec", codec)


FAULTS = {f.__name__: f for f in (parity_delta_dropped, delta_misplaced,
                                  fallback_forced)}
