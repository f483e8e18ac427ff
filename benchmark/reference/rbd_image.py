"""What `rbd bench --io-type write --io-pattern rand` leaves in an image,
made from the seed alone: the image's first contents, the block and the
bytes of every op, and what a block may hold once the ops are over.

The image starts fully written: object n holds
`rados_payload.Payloads(seed, object_bytes, ring).data(n)` (a
preconditioned disk).  Op i overwrites block `block(i)`, a seeded uniform
draw over the image's aligned blocks of `io_size` bytes, with `payload(i)`:
a buffer of a seeded ring with its first 16 bytes replaced by a stamp of
the seed and i, so no two ops write the same bytes.

Several writers are in flight, so two ops can meet on one block.  The
image is told when each op was submitted and when it was acknowledged
(`submitted`, `acknowledged`, in the order the driver saw them) and
answers, for a block, the contents a linearizable register may hold at
the end: the payload of any write W to it such that no other write to it
was submitted after W was acknowledged; a write never acknowledged may or
may not have landed ("either"); the prefill's bytes only where no write to
the block was acknowledged.  Imports nothing of the program."""

import struct

import numpy as np

from .rados_payload import Payloads

STAMP = struct.Struct("<QQ")


class Image:
    def __init__(self, seed: int, image_bytes: int, object_bytes: int,
                 io_size: int, ring_buffers: int):
        if image_bytes % object_bytes or object_bytes % io_size:
            raise ValueError("the image is whole objects of whole blocks")
        self.seed, self.io_size = seed, io_size
        self.image_bytes, self.object_bytes = image_bytes, object_bytes
        self.blocks = image_bytes // io_size
        self.objects = image_bytes // object_bytes
        self.prefill = Payloads(seed, object_bytes, ring_buffers)
        rng = np.random.default_rng([seed, io_size, 3])
        self.ring = [rng.integers(0, 256, io_size, dtype=np.uint8).tobytes()
                     for _ in range(ring_buffers)]
        self._draws = {}
        self._clock = 0
        self._at = {}           # op -> [block, submitted at, acked at]
        self._by_block = {}     # block -> [op, ...]

    # -- what the generator sends --------------------------------------------

    def object(self, n: int) -> bytes:
        """The prefill of object n."""
        return self.prefill.data(n)

    def block(self, i: int) -> int:
        """The aligned block op i overwrites: uniform over the image."""
        page, at = divmod(i, 4096)
        if page not in self._draws:
            self._draws[page] = np.random.default_rng(
                [self.seed, page, 4]).integers(0, self.blocks, 4096)
        return int(self._draws[page][at])

    def payload(self, i: int) -> bytes:
        return (STAMP.pack(self.seed, i)
                + self.ring[i % len(self.ring)][STAMP.size:])

    # -- what the driver saw -------------------------------------------------

    def submitted(self, i: int) -> None:
        self._clock += 1
        b = self.block(i)
        self._at[i] = [b, self._clock, None]
        self._by_block.setdefault(b, []).append(i)

    def acknowledged(self, i: int) -> None:
        self._clock += 1
        self._at[i][2] = self._clock

    # -- what the image may hold ---------------------------------------------

    def overwritten(self) -> list:
        """Blocks some acknowledged op wrote."""
        return sorted(b for b, ops in self._by_block.items()
                      if any(self._at[i][2] is not None for i in ops))

    def prefill_block(self, b: int) -> bytes:
        n, at = divmod(b * self.io_size, self.object_bytes)
        return self.object(n)[at:at + self.io_size]

    def allowed(self, b: int) -> set:
        """The contents block b may hold after every op ended."""
        ops = self._by_block.get(b, [])
        out = set()
        for w in ops:
            acked = self._at[w][2]
            if acked is None or not any(
                    self._at[o][1] > acked for o in ops if o != w):
                out.add(self.payload(w))
        if not any(self._at[o][2] is not None for o in ops):
            out.add(self.prefill_block(b))
        return out

    def readable(self, b: int) -> set:
        """What a read of block b may return while ops are in flight: the
        prefill or any write submitted so far (a loose bound: nothing
        that was never written)."""
        return {self.prefill_block(b)} | {
            self.payload(w) for w in self._by_block.get(b, [])}

    def mismatched_blocks(self, n: int, got: bytes) -> int:
        """How many blocks of object n, as read back, hold something they
        may not; a short or missing object counts every block of it."""
        per = self.object_bytes // self.io_size
        if len(got) != self.object_bytes:
            return per
        want = bytearray(self.object(n))
        first = n * per
        touched = [b for b in range(first, first + per)
                   if b in self._by_block]
        bad = 0
        for b in touched:
            at = (b - first) * self.io_size
            have = got[at:at + self.io_size]
            if have not in self.allowed(b):
                bad += 1
            want[at:at + self.io_size] = have    # judged; not again below
        if bytes(want) != got:
            untouched = np.frombuffer(bytes(want), np.uint8) \
                != np.frombuffer(got, np.uint8)
            bad += len(set(np.flatnonzero(untouched) // self.io_size))
        return bad
