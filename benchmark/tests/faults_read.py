"""Faults planted under the read cells' timed path (beside faults.py,
which holds the write cells' and the remap's): each must make a run come
out `correct: false`.  Used by test_rehearsal_read.py at a tiny size on
the CPU and by control_read.py at the cell's own size on the chip.

  reconstruct_zeroed   the control: a reconstruction returns zeros for
                       every position it rebuilt (the cheapest "speed-up"
                       of a degraded read: no decode at all), so a read
                       of an object whose data shard is lost no longer
                       returns the bytes written.
  victim_spared        nobody is stopped in the degraded cell: the run
                       measures a healthy pool under the degraded cell's
                       name, and no read is reconstructed.
"""

from .faults import _patched


def reconstruct_zeroed():
    """Every rebuilt position comes back as zeros of the right length."""
    from ceph_tpu.ec.base import ErasureCode
    real = ErasureCode.decode_async

    async def decode_async(self, want_to_read, chunks, *a, **kw):
        out = await real(self, want_to_read, chunks, *a, **kw)
        return {i: buf if i in chunks else bytes(len(buf))
                for i, buf in out.items()}

    return _patched(ErasureCode, "decode_async", decode_async)


def victim_spared():
    """The kill does nothing and is believed at once."""
    import contextlib

    from ceph_tpu.testing.cluster import LocalCluster

    async def nothing(self, i, *a, **kw):
        return None

    @contextlib.contextmanager
    def both():
        with _patched(LocalCluster, "kill_osd", nothing), \
                _patched(LocalCluster, "wait_osd_down", nothing):
            yield

    return both()


FAULTS = {f.__name__: f for f in (reconstruct_zeroed, victim_spared)}
