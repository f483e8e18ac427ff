"""Reed-Solomon over GF(2^8) as isa-l's `reed_sol_van` codes it, in plain
numpy table look-ups: what the parity shards beside k data shards must be.

The field is GF(2^8) with the polynomial x^8+x^4+x^3+x^2+1 (0x11d).  The
coding rows are those isa-l's `gf_gen_rs_matrix` puts under the identity:
row r is the powers of 2^r, `[1, g, g^2, ..., g^(k-1)]` with `g = 2^r`, so
row 0 is all ones and parity 0 is the XOR of the data chunks.

A healthy read never reads parity, and a one-erasure reconstruct uses row 0
alone: the shards of rows 1 and 2 have no other witness than an encode of
the data shards beside them, which is what this file is for.  Its own
witnesses are closed forms and the isa k=8,m=3 entry of
tests/golden/ec_corpus.json (benchmark/tests/test_reference_rbd.py); that
entry is a self-vector of this repository, not upstream's bytes.
Imports nothing of the program."""

import numpy as np

POLY = 0x11d


def _tables():
    exp, log = np.zeros(512, np.uint8), np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i], log[x] = x, i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    a = np.arange(256)
    mul = exp[(log[a][:, None] + log[a][None, :])]
    mul[0, :] = mul[:, 0] = 0
    return exp, log, mul


EXP, LOG, MUL = _tables()     # MUL[a, b] = a * b, 64 KiB


def gf_mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def gf_pow(a: int, n: int) -> int:
    return 1 if n == 0 else (0 if a == 0 else
                             int(EXP[(int(LOG[a]) * n) % 255]))


def coding_rows(k: int, m: int) -> list:
    """The m rows `gf_gen_rs_matrix(a, k + m, k)` leaves under the
    identity."""
    return [[gf_pow(gf_pow(2, r), j) for j in range(k)] for r in range(m)]


def encode(data_chunks: list, m: int) -> list:
    """The m parity chunks of k equally long data chunks, as bytes."""
    k = len(data_chunks)
    data = [np.frombuffer(c, np.uint8) for c in data_chunks]
    if len({len(d) for d in data}) != 1:
        raise ValueError("data chunks of differing lengths")
    out = []
    for row in coding_rows(k, m):
        acc = np.zeros(len(data[0]), np.uint8)
        for coeff, d in zip(row, data):
            acc ^= MUL[coeff][d]
        out.append(acc.tobytes())
    return out
