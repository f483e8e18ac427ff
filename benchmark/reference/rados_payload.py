"""What the rados cells write, made from the seed alone: the generator
sends it and the correctness pass expects it back, byte for byte.

A ring of random buffers is made once (set-up); object n is the ring's
buffer n mod ring with its first 16 bytes replaced by a stamp of the seed
and n, so that no two objects of a run are equal and an object that comes
back with another's bytes is seen.

Every seed writes the same names, object0, object1, ..., in another order:
the i-th op of a run writes object `number(i)`, the seed's shuffle of i
within its block of 64.  Names decide placement (name -> PG -> OSDs), and
with a name set of its own each seed had a latency tail of its own (PERF.md,
PR 26); so the seed draws the bytes and the order, not the placement.
Imports nothing of the program."""

import struct

import numpy as np

STAMP = struct.Struct("<QQ")
BLOCK = 64


class Payloads:
    def __init__(self, seed: int, object_bytes: int, ring_buffers: int):
        if object_bytes < STAMP.size:
            raise ValueError("objects are stamped with %d bytes" % STAMP.size)
        rng = np.random.default_rng([seed, object_bytes])
        self.seed = seed
        self.ring = [rng.integers(0, 256, object_bytes, dtype=np.uint8)
                     .tobytes() for _ in range(ring_buffers)]

        self._shuffles = {}

    def number(self, i: int) -> int:
        """The object the i-th op writes."""
        block, at = divmod(i, BLOCK)
        if block not in self._shuffles:
            self._shuffles[block] = np.random.default_rng(
                [self.seed, block]).permutation(BLOCK)
        return block * BLOCK + int(self._shuffles[block][at])

    def name(self, n: int) -> str:
        return "benchmark_data_object%d" % n

    def data(self, n: int) -> bytes:
        return (STAMP.pack(self.seed, n)
                + self.ring[n % len(self.ring)][STAMP.size:])
